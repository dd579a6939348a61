"""A configuration's deployment, generated from the run's seed: the shared
repository and the stream of tenants that keeps the cohort full.

Everything the program receives is built here from the configuration
file and ``--seed``: the repository's runs (``RunRecord``s of the
program's own type) and one ``SearchRequest`` per tenant whose profiler
is the benchmark's copy of the scout emulator.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

from bench.scout import Emulator


def subseed(seed: int, *path: int) -> int:
    """A 31-bit seed drawn from the run's seed and a path of indices (the
    run's seed may exceed 32 bits; the program's PRNG keys take less)."""
    ss = np.random.SeedSequence([int(seed) % (1 << 63), *path])
    return int(ss.generate_state(1, np.uint32)[0] >> 1)


@dataclasses.dataclass
class Tenant:
    """What the benchmark knows of one tenant: enough to rebuild its
    search for the reference."""
    index: int                  # tenant counter (0, 1, 2, ...)
    workload: str
    seed: int                   # the SearchRequest's seed
    objectives: List[str]
    constraints: Dict[str, float]   # measure -> upper bound
    n_mc: int


class Cohort:
    """The deployment of one configuration under one seed."""

    def __init__(self, config: Dict, seed: int):
        self.config = config
        self.seed = int(seed)
        self.emu = Emulator()
        self.workloads = self.emu.workload_ids()
        self.tenants: List[Tenant] = []
        self._targets = {}

    # -- the shared repository ---------------------------------------------
    def repository_runs(self):
        """(anonymous id, config, measures, metrics) of every shared run:
        each workload profiled by ``collaborators`` anonymous
        collaborators, ``runs_each`` uniformly chosen configurations
        each."""
        rep = self.config["repository"]
        rng = np.random.default_rng(subseed(self.seed, 1))
        configs = self.emu.configs
        out = []
        j = 0
        for wid in self.workloads:
            for _ in range(rep["collaborators"]):
                for ci in rng.choice(len(configs), rep["runs_each"],
                                     replace=False):
                    measures, metrics = self.emu.run(wid, configs[int(ci)],
                                                     rng)
                    out.append((f"anon-{j}", dict(configs[int(ci)]),
                                measures, metrics))
                j += 1
        return out

    def build_repository(self):
        from repro.core import Repository
        from repro.core.types import RunRecord
        repo = Repository()
        for anon, config, measures, metrics in self.repository_runs():
            repo.add_run(RunRecord(anon, config, metrics, measures))
        return repo

    # -- tenants ------------------------------------------------------------
    def _target(self, wid: str, pct: float) -> float:
        k = (wid, pct)
        if k not in self._targets:
            self._targets[k] = self.emu.runtime_target(wid, pct)
        return self._targets[k]

    def next_tenant(self) -> Tenant:
        t = len(self.tenants)
        wid = self.workloads[t % len(self.workloads)]
        cons = {c["measure"]: self._target(wid, c["percentile"])
                for c in self.config.get("constraints", ())}
        tenant = Tenant(t, wid, subseed(self.seed, 2, t),
                        list(self.config["objectives"]), cons,
                        int(self.config.get("n_mc", 64)))
        self.tenants.append(tenant)
        return tenant

    def profile_fn(self, tenant: Tenant):
        rng = np.random.default_rng(subseed(self.seed, 3, tenant.index))
        emu, wid = self.emu, tenant.workload
        return lambda config: emu.run(wid, config, rng)

    def request(self, tenant: Tenant, space):
        """The program's ``SearchRequest`` for ``tenant``."""
        from repro.core import BOConfig, Constraint, Objective
        from repro.serve.search_service import SearchRequest
        bo = BOConfig(**self.config["bo"])
        cons = [Constraint(m, ub) for m, ub in tenant.constraints.items()]
        objs = [Objective(o) for o in tenant.objectives]
        common = dict(method=self.config["method"], bo_config=bo,
                      seed=tenant.seed)
        if len(objs) == 1:
            return SearchRequest(space, self.profile_fn(tenant), objs[0],
                                 cons, **common)
        return SearchRequest(space, self.profile_fn(tenant), None, cons,
                             objectives=objs, n_mc=tenant.n_mc, **common)

    def slots(self) -> int:
        return int(self.config["tenants"])

