"""Bring-up check: the multi-tenant SearchService on a TPU.

Drives the service's main path once, through the calls a deployment
makes — ``submit`` -> ``precompile`` -> ``step`` -> ``collect`` — on the
scout deployment of paper §IV-A, all generated from ``--seed``:

- the 69-configuration scout search space (encoded d=7) with all 18
  emulated workloads;
- a shared repository in which each workload was profiled by 3
  anonymous collaborators, 12 uniformly chosen runs each (648 runs);
- 64 concurrent karasu tenants, ``BOConfig(n_init=1, max_iters=20)``:
  48 single-objective (cost under a 50th-percentile runtime target),
  12 two-objective (cost, energy), 4 three-objective (cost, energy,
  runtime), profiled in-process by ``SyncProfileExecutor``.

The run serves ``STEPS`` scheduling steps, so every tenant makes 3 BO
iterations: the cold fit rung on the first, the warm rung after. It
checks, failing on the first miss:

- each Pallas kernel of the plan layer against its XLA reference twin,
  on the chip, at one bucket shape of this cohort;
- that precompile warms every launch the steps then use
  (``plan_compile_misses == 0``) and that at least one fit bucket runs
  the Pallas ``fused_fit`` kernel;
- every single-objective decision of the steps against constrained EI
  recomputed in float64 numpy from that tenant's observations, fitted
  hyperparameters, support models and RGPE weights.

``--chips 4`` runs only the sharded path instead: the same cohort served
over a 4-device data mesh, cold and then warm, against the same cohort on
one device, in this one process. The discrete trajectories must match
and the warm sharded run must compile nothing.

Usage::

    python chip_smoke.py [--seed N]
    python chip_smoke.py --chips 4 [--seed N]

Exits non-zero when JAX finds no TPU or any check fails; the last line
of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time
from typing import Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import random_profiled_repo  # noqa: E402
from repro.core import (BOConfig, Constraint, Objective,  # noqa: E402
                        scout_search_space)
from repro.core.plan import CohortLimits, PlanExecutor  # noqa: E402
from repro.launch.compile_stats import (CompileWatcher,  # noqa: E402
                                        use_compile_cache)
from repro.serve.search_service import (SearchRequest,  # noqa: E402
                                        SearchService)
from repro.simdata import make_emulator  # noqa: E402

TENANTS = 64
SO_TENANTS, MOO2_TENANTS, MOO3_TENANTS = 48, 12, 4
COLLABORATORS = 3          # anonymous collaborators per workload
RUNS_EACH = 12             # uniformly chosen runs per collaborator
BO = BOConfig(n_init=1, max_iters=20)
STEPS = 3                  # BO iterations per tenant in this run
JITTER = 1e-6              # core.gp.JITTER
# a pick ties the float64 argmax within f32 resolution: relative to the
# best EI, and absolute on the standardised scale, where sigma ~ 1 and
# f32 resolves ~1e-7 (one observation standardises to y_std = 1e-8 and
# can leave every candidate's EI at zero)
EI_RTOL, EI_ATOL = 1e-3, 1e-6
# where the runtime target is all but sure to fail everywhere, every
# candidate's EI is at most EI_ATOL and any pick passes: a first
# observation that breaks the target standardises to a constraint
# probability of exactly zero, and later steps can leave it below 1e-6.
# Such flat decisions are counted, and may make up at most one step's
# worth, so that two thirds of the decisions test the pick.
MAX_FLAT = SO_TENANTS
NOISE = 0.1                # the fit kernels' default noise
# Adam turns f32 roundoff into parameter drift along flat NLML directions
# over 120 steps, and so does a restart from a converged point (the fit
# tests' contract there is in function space): two implementations must
# reach the same NLML, and factorise their own parameters to f32 accuracy
NLML_ATOL, FACTOR_RTOL = 1e-3, 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def wait_device() -> None:
    """Block until every live device array is computed (donated inputs
    are deleted, not pending)."""
    jax.block_until_ready([a for a in jax.live_arrays()
                           if not a.is_deleted()])


# -- the deployment ---------------------------------------------------------

def build_cohort(seed: int):
    """(space, repository, requests) of the scout deployment."""
    emu = make_emulator()
    space = scout_search_space()
    wids = emu.workload_ids()
    # one anonymous collaborator id per (workload, collaborator) pair
    repo = random_profiled_repo(
        [w for w in wids for _ in range(COLLABORATORS)], RUNS_EACH,
        seed=seed)
    cost, energy, runtime = (Objective("cost"), Objective("energy"),
                             Objective("runtime"))
    requests = []
    for t in range(TENANTS):
        wid = wids[t % len(wids)]
        rng = np.random.default_rng((seed, t))
        run = (lambda c, wid=wid, rng=rng: emu.run(wid, c, rng=rng))
        common = dict(method="karasu", bo_config=BO, seed=seed * 1000 + t)
        if t < SO_TENANTS:
            cons = [Constraint("runtime", emu.runtime_target(wid, 50))]
            requests.append(SearchRequest(space, run, cost, cons, **common))
        elif t < SO_TENANTS + MOO2_TENANTS:
            requests.append(SearchRequest(space, run, None, (),
                                          objectives=[cost, energy],
                                          **common))
        else:
            requests.append(SearchRequest(space, run, None, (),
                                          objectives=[cost, energy,
                                                      runtime], **common))
    return space, repo, requests


def cohort_limits(space) -> CohortLimits:
    """Bounds on every launch the ``STEPS``-step run can make. A tenant
    decides on at most ``n_init + STEPS - 1`` observations, a support
    model holds its collaborator's ``RUNS_EACH``. Model lanes per
    launch: each (tenant, measure) target plus ``n_support`` support
    models — (48*2 + 12*2 + 4*3) * (1 + 3) = 528. A 3-objective front
    of k points splits into at most (k + 1)^3 boxes."""
    d = space.all_encoded().shape[1]
    front = BO.n_init + STEPS - 1
    measures = SO_TENANTS * 2 + MOO2_TENANTS * 2 + MOO3_TENANTS * 3
    return CohortLimits(
        d=d, q_grid=len(space), max_obs=max(front, RUNS_EACH),
        max_lanes=measures * (1 + BO.n_support),
        n_samples=(BO.rgpe_samples,), n_mc=(64,), n_objectives=(2, 3),
        max_ehvi_boxes=(front + 1) ** 3, noises=(BO.noise,))


class RecordingExecutor(PlanExecutor):
    """The default executor, counting the impl each bucket ran."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.impls = collections.defaultdict(collections.Counter)

    def execute(self, plan, **kw):
        for b in plan.buckets:
            self.impls[b.kind][self.bucket_impl(b, kw.get("impl"))] += 1
        return super().execute(plan, **kw)


def log_spans(stats, label: str, since=None) -> None:
    """The service's span totals (self seconds per span) and the programs
    each span built, as ``stats`` holds them (less ``since``)."""
    since = since or {}
    for prefix in ("span_s.", "compiles."):
        got = {k[len(prefix):]: v - since.get(k, 0)
               for k, v in sorted(stats.items()) if k.startswith(prefix)}
        log(f"{label} {prefix.rstrip('.')}: "
            + ", ".join(f"{k} {v:.6g}" for k, v in got.items() if v))


class RecordingService(SearchService):
    """The service, keeping each step's scattered posteriors and RGPE
    support stacks for the float64 decision check."""

    def _posterior_phase(self, sessions):
        self.bases = {}
        self.posts = super()._posterior_phase(sessions)
        return self.posts

    def _rgpe_jobs(self, groups, tgts, owners):
        jobs = super()._rgpe_jobs(groups, tgts, owners)
        for s, m, bases, _job in jobs:
            self.bases[(s.rid, m)] = bases
        return jobs


# -- float64 reference ------------------------------------------------------

def _phi_cdf(z):
    from math import erf
    return 0.5 * (1.0 + np.vectorize(erf)(z / np.sqrt(2.0)))


def _matern64(a, b, log_ls, log_sf):
    """Matern-5/2 kernel matrix in float64."""
    ls = np.exp(np.asarray(log_ls, np.float64))
    sf = float(np.exp(np.float64(log_sf)))
    a, b = np.asarray(a, np.float64) / ls, np.asarray(b, np.float64) / ls
    d2 = (np.sum(a * a, 1)[:, None] + np.sum(b * b, 1)[None, :]
          - 2.0 * a @ b.T)
    r = np.sqrt(np.maximum(d2, 0.0) + 1e-12)
    return sf * (1.0 + np.sqrt(5.0) * r + 5.0 / 3.0 * d2) \
        * np.exp(-np.sqrt(5.0) * r)


def _nlml64(x, y, log_ls, log_sf, noise):
    """Negative log marginal likelihood of one GP in float64."""
    k = _matern64(x, x, log_ls, log_sf) + (noise + JITTER) * np.eye(len(x))
    chol = np.linalg.cholesky(k)
    a = np.linalg.solve(chol, np.asarray(y, np.float64))
    return (0.5 * a @ a + np.sum(np.log(np.diag(chol)))
            + 0.5 * len(x) * np.log(2.0 * np.pi))


def _gp_post64(x, y, log_ls, log_sf, noise, xq):
    """Posterior mean and variance of one GP in float64."""
    sf = float(np.exp(np.float64(log_sf)))
    k = _matern64(x, x, log_ls, log_sf) + (noise + JITTER) * np.eye(len(x))
    ks = _matern64(xq, x, log_ls, log_sf)
    mu = ks @ np.linalg.solve(k, y)
    var = sf - np.sum(ks * np.linalg.solve(k, ks.T).T, axis=1)
    return mu, np.maximum(var, 1e-10)


def check_fit64(name, x, y, counts, got, want) -> None:
    """A fit's outputs against float64: each lane's NLML at the fitted
    parameters within ``NLML_ATOL`` of the twin fit's, and its Cholesky
    factor and ``alpha`` within ``FACTOR_RTOL`` of the lane's largest
    entry of the float64 factorisation at its own parameters."""
    ls, sf, chol, alpha = (np.asarray(a) for a in got)
    gap = err = 0.0
    for i, c in enumerate(counts):
        xi, yi = x[i, :c], y[i, :c]
        gap = max(gap, abs(_nlml64(xi, yi, ls[i], sf[i], NOISE)
                           - _nlml64(xi, yi, want[0][i], want[1][i],
                                     NOISE)))
        k = (_matern64(xi, xi, ls[i], sf[i])
             + (NOISE + JITTER) * np.eye(c))
        l64 = np.linalg.cholesky(k)
        a64 = np.linalg.solve(k, np.asarray(yi, np.float64))
        err = max(err,
                  np.abs(chol[i, :c, :c] - l64).max() / np.abs(l64).max(),
                  np.abs(alpha[i, :c] - a64).max() / np.abs(a64).max())
    log(f"parity {name}: largest NLML gap {gap!r}, largest relative "
        f"factorisation error {err!r}")
    if not (gap <= NLML_ATOL and err <= FACTOR_RTOL):
        raise AssertionError(f"{name}: pallas fit differs from xla")


def reference_acquisition(svc, s, obs, xq):
    """Constrained EI over the grid, recomputed in float64 from the
    observations ``obs`` the step decided on, the step's fitted
    hyperparameters, its RGPE support stacks and weights."""
    x = np.stack([o.x for o in obs]).astype(np.float64)
    post = {}
    for m in s.measures:
        yr = np.array([o.measures[m] for o in obs], np.float64)
        y_mean = yr.mean()
        y_std = max(np.sqrt(np.mean((yr - y_mean) ** 2)), 1e-8)
        _, ls, sf = s.fit_cache[m]
        mu, var = _gp_post64(x, (yr - y_mean) / y_std, ls, sf,
                             s.cfg.noise, xq)
        bases = svc.bases.get((s.rid, m))
        if bases is not None:
            w = np.asarray(svc.posts[s.rid][m]["weights"], np.float64)
            mus, vs = [], []
            for i in range(bases.m):
                n = int(bases.counts[i])
                mb, vb = _gp_post64(
                    np.asarray(bases.x[i, :n], np.float64),
                    np.asarray(bases.y[i, :n], np.float64),
                    np.asarray(bases.log_lengthscales[i]),
                    np.asarray(bases.log_signal[i]), bases.noise, xq)
                mus.append(mb)
                vs.append(vb)
            mu = np.sum(w[:-1, None] * np.stack(mus), 0) + w[-1] * mu
            var = np.maximum(np.sum(w[:-1, None] ** 2 * np.stack(vs), 0)
                             + w[-1] ** 2 * var, 1e-10)
        post[m] = (mu, var, y_mean, y_std)
    obj = s.req.objective.name
    feas = [o.measures[obj] for o in obs
            if all(o.measures[c.name] <= c.upper_bound
                   for c in s.req.constraints)]
    best = min(feas) if feas else min(o.measures[obj] for o in obs)
    mu, var, y_mean, y_std = post[obj]
    sigma = np.sqrt(np.maximum(var, 1e-12))
    z = ((best - y_mean) / y_std - mu) / sigma
    acq = np.maximum(sigma * (z * _phi_cdf(z)
                              + np.exp(-0.5 * z * z) / np.sqrt(2 * np.pi)),
                     0.0)
    for c in s.req.constraints:
        mu_c, var_c, ym_c, ys_c = post[c.name]
        acq = acq * _phi_cdf(((c.upper_bound - ym_c) / ys_c - mu_c)
                             / np.sqrt(np.maximum(var_c, 1e-12)))
    return acq


def check_decisions(svc, xq) -> Tuple[int, int]:
    """Every single-objective tenant's pick of the step — its newest
    observation, the synchronous executor having landed it — must be
    the float64 argmax over the configs it had not profiled, or tie
    with it. Returns (decisions checked, decisions whose best float64
    EI is at most ``EI_ATOL``): those pass whatever was picked, so they
    test nothing."""
    checked = flat = 0
    for rid, s in svc.active.items():
        if s.is_moo:
            continue
        configs = s.req.space.configs
        chosen = next(ci for ci in s.profiled
                      if configs[ci] == s.observations[-1].config)
        rem = [ci for ci in range(len(configs))
               if ci == chosen or ci not in s.profiled]
        acq = reference_acquisition(svc, s, s.observations[:-1],
                                    xq)[np.asarray(rem)]
        top = float(acq.max())
        got = float(acq[rem.index(chosen)])
        flat += top <= EI_ATOL
        if got < top - EI_RTOL * top - EI_ATOL:
            raise AssertionError(
                f"tenant {rid} picked config {chosen} with float64 EI "
                f"{got!r}, below the argmax {rem[int(acq.argmax())]} "
                f"at {top!r}")
        checked += 1
    return checked, flat


# -- phases -----------------------------------------------------------------

def kernel_parity(seed: int) -> None:
    """Each plan-layer Pallas kernel against its XLA twin on the chip,
    at the interpret-mode tests' tolerances. The fit runs at a bucket
    the default ``impl="auto"`` routes to Pallas in this cohort's
    precompile — 512 lanes of 5..12 observations padded to 16, d=7,
    packed as the executor packs them — on the cold rung (120 steps
    from zero, compared by each lane's float64 NLML) and then the warm
    rung (16 steps from the cold fit, compared by value). (Lanes of one
    to four observations leave the lengthscales in flat NLML directions,
    where Adam turns f32 roundoff into hyperparameter drift between any
    two implementations.)"""
    from repro.core.acquisition import nondominated_boxes, pareto_front
    from repro.core.gp import _pack_fit_lanes
    from repro.kernels.fused_ehvi import fused_ehvi
    from repro.kernels.fused_fit import fused_fit
    from repro.kernels.fused_posterior import fused_posterior_ei
    from repro.kernels.ranking_loss import ranking_loss_padded
    from repro.kernels.routing import resolve_impl

    rng = np.random.default_rng(seed)
    m, n, d, q = 512, 16, 7, 69
    counts = [int(c) for c in rng.integers(5, RUNS_EACH + 1, m)]
    xs = [rng.random((c, d)) for c in counts]
    ys = [np.sin(xi.sum(1)) + 0.1 * rng.normal(size=len(xi)) for xi in xs]
    x, y, mask, _, _ = _pack_fit_lanes(xs, ys, counts, n)
    zero_ls = np.zeros((m, d), np.float32)
    zero_sf = np.zeros((m,), np.float32)

    def close(name, got, want, atol, rtol=0.0):
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=atol, rtol=rtol, err_msg=name)
        log(f"parity {name}: pallas == xla")

    limits = CohortLimits(d=d, q_grid=q, max_obs=n)
    init = (zero_ls, zero_sf)
    for steps in (limits.fit_steps, limits.fit_warm_steps):
        if resolve_impl("auto", cells=m * n * n * steps) != "pallas":
            raise AssertionError(f"fit bucket {m}x{n} at {steps} steps "
                                 f"does not route to Pallas")
        want = fused_fit(x, y, mask, *init, steps=steps, impl="xla")
        got = fused_fit(x, y, mask, *init, steps=steps, impl="pallas")
        check_fit64(f"fused_fit {m}x{n} steps={steps}", x, y, counts,
                    got, want)
        init = want[:2]
    ls, sf, chol, alpha = want
    xq = np.broadcast_to(rng.random((q, d)).astype(np.float32), (m, q, d))
    best = rng.normal(size=m).astype(np.float32)
    args = (ls, sf, x, mask, chol, alpha, xq, best)
    close("fused_posterior", fused_posterior_ei(*args, impl="pallas"),
          fused_posterior_ei(*args, impl="xla"), 1e-4)

    lanes, s, qe, n_obj = 16, 64, 72, 3
    boxes, refs = [], []
    for _ in range(lanes):
        observed = rng.normal(size=(4, n_obj))
        refs.append(observed.max(0) * 1.1 + 1e-9)
        boxes.append(nondominated_boxes(pareto_front(observed), refs[-1]))
    k = max(lo.shape[0] for lo, _ in boxes)
    los, his = (np.stack([np.pad(b[i], ((0, k - len(b[i])), (0, 0)),
                                 constant_values=np.inf) for b in boxes])
                .astype(np.float32) for i in (0, 1))
    args = (los, his, np.stack(refs).astype(np.float32),
            rng.normal(size=(lanes, n_obj, qe)).astype(np.float32),
            rng.uniform(0.1, 1.0, (lanes, n_obj, qe)).astype(np.float32),
            rng.normal(size=(lanes, n_obj)).astype(np.float32),
            rng.uniform(0.5, 1.5, (lanes, n_obj)).astype(np.float32),
            rng.normal(size=(lanes, n_obj, s, qe)).astype(np.float32))
    close("fused_ehvi", [fused_ehvi(*args, impl="pallas")],
          [fused_ehvi(*args, impl="xla")], 1e-4)

    r = 1024
    preds = rng.normal(size=(r, n)).astype(np.float32)
    ys = rng.normal(size=(r, n)).astype(np.float32)
    nv = rng.integers(0, n + 1, r).astype(np.int32)
    got = ranking_loss_padded(preds, ys, nv, impl="pallas")
    want = ranking_loss_padded(preds, ys, nv, impl="xla")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    log("parity ranking_loss_padded: pallas == xla")


def serve_one_chip(seed: int) -> None:
    space, repo, requests = build_cohort(seed)
    xq = space.all_encoded().astype(np.float64)
    executor = RecordingExecutor()
    svc = RecordingService(repo, slots=TENANTS, plan_executor=executor)
    for req in requests:
        svc.submit(req)

    t0 = time.perf_counter()
    pre = svc.precompile(cohort_limits(space))
    log(f"precompile: {time.perf_counter() - t0:.3f} s, "
        f"{pre['buckets']} buckets, {pre['compiles']} compiles")
    for kind, c in sorted(executor.impls.items()):
        log(f"precompile impl {kind}: {dict(c)}")
    log_spans(svc.stats, "precompile")
    if executor.impls["fit"]["pallas"] == 0:
        raise AssertionError("no fit bucket resolved to the Pallas kernel")
    executor.impls.clear()

    stats0 = dict(svc.stats)
    # support fits are unpadded and outside the precompiled vocabulary:
    # counted apart from the plan launches, which must not compile
    watch = CompileWatcher()
    walls, checked, flat = [], 0, 0
    for step in range(STEPS):
        t0 = time.perf_counter()
        svc.step()
        wait_device()
        walls.append(time.perf_counter() - t0)
        c, f = check_decisions(svc, xq)
        checked, flat = checked + c, flat + f
        log(f"step {step}: {walls[-1]:.6f} s, "
            f"plan_compile_misses={svc.stats['plan_compile_misses']}, "
            f"{c} decisions checked, {f} with best EI <= {EI_ATOL}")
    for kind, c in sorted(executor.impls.items()):
        log(f"serving impl {kind}: {dict(c)}")
    log(f"steps: {len(walls)}, p50 step wall {np.median(walls):.6f} s")
    log_spans(svc.stats, f"{len(walls)} steps", since=stats0)
    log(f"float64 EI decisions checked: {checked}, of which {flat} "
        f"had best EI <= {EI_ATOL} (any pick passes)")
    if checked != SO_TENANTS * STEPS:
        raise AssertionError(f"checked {checked} single-objective "
                             f"decisions, expected {SO_TENANTS * STEPS}")
    if flat > MAX_FLAT:
        raise AssertionError(f"{flat} decisions had an all-but-zero EI, "
                             f"more than {MAX_FLAT}")
    compiled = watch.delta()
    support = compiled.pop("support_fit", 0)
    log(f"plan_compile_misses: {svc.stats['plan_compile_misses']} "
        f"({support} support fits)")
    if compiled:
        raise AssertionError(f"plan launches compiled after precompile: "
                             f"{compiled}")
    iters = [len(s.observations) - BO.n_init for s in svc.active.values()]
    done = svc.collect()
    log(f"collect: {len(done)} finished, {len(svc.active)} active, "
        f"BO iterations per tenant {min(iters)}..{max(iters)}")
    if len(svc.active) + len(done) != TENANTS or min(iters) < STEPS:
        raise AssertionError("a tenant was lost or fell behind")


def trajectories(svc) -> dict:
    return {rid: [(tuple(sorted(o.config.items())),
                   tuple(sorted(o.measures.items())))
                  for o in s.observations]
            for rid, s in svc.active.items()}


def serve_sharded(seed: int, chips: int) -> None:
    """The cohort on one device, then over a ``chips``-device data mesh
    cold and warm; the three trajectories must agree and the warm
    sharded run must compile no plan launch."""
    mesh = jax.make_mesh((chips,), ("data",))
    runs = {}
    for name, m in (("one device", None), ("sharded cold", mesh),
                    ("sharded warm", mesh)):
        _, repo, requests = build_cohort(seed)
        svc = SearchService(repo, slots=TENANTS, mesh=m)
        for req in requests:
            svc.submit(req)
        walls = []
        for _ in range(STEPS):
            t0 = time.perf_counter()
            svc.step()
            wait_device()
            walls.append(time.perf_counter() - t0)
        runs[name] = svc
        log(f"{name}: {STEPS} steps, p50 step wall "
            f"{np.median(walls):.6f} s, plan_compile_misses="
            f"{svc.stats['plan_compile_misses']}")
    base = trajectories(runs["one device"])
    for name in ("sharded cold", "sharded warm"):
        if trajectories(runs[name]) != base:
            raise AssertionError(f"{name} trajectory differs from one "
                                 f"device")
        log(f"{name}: same trajectory as one device "
            f"({len(base)} tenants)")
    for k in ("plan_batches", "plan_queries", "steps"):
        if runs["sharded warm"].stats[k] != runs["one device"].stats[k]:
            raise AssertionError(f"stats[{k!r}] differs under the mesh")
    warm = runs["sharded warm"].stats["plan_compile_misses"]
    log(f"sharded warm plan_compile_misses: {warm}")
    if warm:
        raise AssertionError(f"warm sharded run compiled {warm} launches")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        log(f"no TPU: JAX backend is {backend!r}")
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices, JAX sees "
            f"{len(devices)}")
        return 2
    dev = devices[0]
    log(f"platform: {dev.platform}, device_kind: {dev.device_kind}, "
        f"devices: {len(devices)}, jax {jax.__version__}")
    log(f"compile cache: {use_compile_cache()}")

    if args.chips == 1:
        kernel_parity(args.seed)
        serve_one_chip(args.seed)
    else:
        serve_sharded(args.seed, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
