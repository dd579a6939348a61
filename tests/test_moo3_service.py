"""A three-objective cohort through ``SearchService`` after ``precompile``:
no tracked launch compiles while it serves, every decision's EHVI row
(the box-decomposition launch) matches the float64 recursive-sweep
oracle ``mc_ehvi_nd`` on the same draws, and the configuration each
tenant launched is the oracle's argmax.

Two of the four tenants profile a synthetic job whose (cost, energy,
runtime) are mutually non-dominated on every configuration, so their
fronts grow by one point a decision; past about 12 points the 3-D grid
decomposition passes ``EHVI_BOX_CHUNK`` boxes, and the launch's chunked
scan, with the shallow fronts of the other lanes padded to the same box
axis, is compared with the oracle too."""
import dataclasses

import numpy as np
import pytest

from repro.core import (BOConfig, Objective, Repository,
                        scout_search_space)
from repro.core.acquisition import (EHVI_BOX_CHUNK, hv_nd, mc_ehvi_nd,
                                    pareto_front)
from repro.core.plan import CohortLimits, EhviQuery, PlanExecutor
from repro.launch.compile_stats import CompileWatcher
from repro.serve.search_service import SearchRequest, SearchService
from repro.simdata import make_emulator

EMU = make_emulator()
WID = EMU.workload_ids()[6]
SPACE = dataclasses.replace(scout_search_space(), name="scout-16",
                            configs=scout_search_space().configs[:16])
MAX_ITERS = 14
N_MC = 8
OBJECTIVES = ("cost", "energy", "runtime")
# The launch computes each box's overlap with [p, ref] in float32: every
# extent is a difference of measures of the size of the reference point,
# rounded to 24 bits, and a row sums up to K such boxes. Its error is a
# small multiple of 6e-8 of the front's dominated volume (times sqrt(K)
# at worst a few hundred), not of the row, which may be nearly zero where
# no draw improves the front. So rows are compared to 1e-4 of the larger
# of that volume and the row's maximum; a dropped, duplicated or
# misplaced box moves a row by a whole box's share of the improvement.
RTOL = 1e-4


def _scale(query, want):
    front = pareto_front(np.asarray(query.observed, np.float64))
    return max(hv_nd(front, query.ref), float(np.abs(want).max()))


def _index(config):
    return SPACE.configs.index(config)


def _deep_front(config):
    """Measures on one anti-correlated surface: lower cost always costs
    energy, so no configuration dominates another."""
    _, metrics = EMU.run(WID, config, rng=None)
    a = _index(config) / (len(SPACE) - 1)
    return ({"cost": 1.0 + 4.0 * a, "energy": 5.0 - 4.0 * a,
             "runtime": 1.0 + 4.0 * ((a * 7.3) % 1.0)}, metrics)


class Recording(PlanExecutor):
    """Keeps every EHVI query it ran, its row and its box pad."""

    def __init__(self):
        super().__init__()
        self.on = False
        self.ehvi = []

    def execute(self, plan, **kw):
        out = super().execute(plan, **kw)
        if self.on:
            for b in plan.buckets:
                if b.kind == "ehvi":
                    self.ehvi += [(plan.queries[i], np.asarray(out[i]),
                                   b.pads["k_pad"]) for i in b.indices]
        return out


class Service(SearchService):
    """Keeps, for every EHVI row it scatters, the tenant and the
    candidates the row scores."""

    def _apply_pof(self, s, post, idx, acq):
        self.rows.append((s.rid, np.asarray(idx).copy()))
        return super()._apply_pof(s, post, idx, acq)


@pytest.fixture(scope="module")
def served():
    repo = Repository()
    rng = np.random.default_rng(11)
    for u in range(2):
        for ci in rng.choice(len(SPACE), 8, replace=False):
            repo.add_run(EMU.make_record(f"anon-{u}", WID,
                                         SPACE.configs[ci], rng))
    executor = Recording()
    svc = Service(repo, slots=4, plan_executor=executor)
    svc.rows = []
    limits = CohortLimits(d=SPACE.all_encoded().shape[1], q_grid=len(SPACE),
                          max_obs=MAX_ITERS, max_lanes=64, n_samples=(32,),
                          n_mc=(N_MC,), n_objectives=(3,),
                          max_ehvi_boxes=(MAX_ITERS + 2) ** 3)
    svc.precompile(limits)
    cfg = BOConfig(n_init=1, max_iters=MAX_ITERS, rgpe_samples=32)
    for t in range(4):
        fn = (_deep_front if t % 2 else
              (lambda c: EMU.run(WID, c, rng=None)))
        svc.submit(SearchRequest(SPACE, fn, None, (), method="karasu",
                                 bo_config=cfg, seed=300 + t,
                                 objectives=[Objective(o)
                                             for o in OBJECTIVES],
                                 n_mc=N_MC))
    watch = CompileWatcher()
    executor.on = True
    decisions = []       # (rid, candidates scored, EHVI row, query, k_pad,
    #                      configuration launched)
    while svc.active or svc.queue:
        sessions = list(svc.active.values()) + list(svc.queue)
        before = {s.rid: set(s.profiled) for s in sessions}
        n_rows, n_ehvi = len(svc.rows), len(executor.ehvi)
        svc.step()
        launched = {s.rid: (s.profiled - before[s.rid]) for s in sessions}
        for (rid, idx), (query, row, k_pad) in zip(
                svc.rows[n_rows:], executor.ehvi[n_ehvi:]):
            decisions.append((rid, idx, row, query, k_pad, launched[rid]))
        assert len(svc.rows) - n_rows == len(executor.ehvi) - n_ehvi
    return svc, watch.delta(), decisions


def test_no_tracked_launch_compiles_after_precompile(served):
    svc, compiled, decisions = served
    assert decisions
    # support-model fits are unpadded and compile once per history
    # length, outside the precompiled vocabulary
    support = compiled.pop("support_fit", 0)
    assert compiled == {}
    assert svc.stats["plan_compile_misses"] == support


def test_ehvi_rows_match_the_float64_oracle(served):
    _, _, decisions = served
    for rid, idx, row, query, k_pad, _ in decisions:
        assert isinstance(query, EhviQuery) and len(query.samples) == 3
        want = mc_ehvi_nd(list(query.samples), query.observed, query.ref)
        assert row.shape == want.shape == idx.shape
        scale = _scale(query, want)
        np.testing.assert_allclose(row, want, rtol=0, atol=RTOL * scale,
                                   err_msg=f"tenant {rid}, k_pad {k_pad}")


def test_the_chunked_scan_was_compared(served):
    svc, _, decisions = served
    pads = {k_pad for *_, k_pad, _ in decisions}
    assert max(pads) > EHVI_BOX_CHUNK and max(pads) % EHVI_BOX_CHUNK == 0
    # shallow fronts shared those launches, padded to the deep one's box
    # axis
    assert svc.stats["ehvi_boxes_live"] < svc.stats["ehvi_boxes_padded"]


def test_launched_configuration_is_the_oracles_argmax(served):
    _, _, decisions = served
    exact = 0
    for rid, idx, row, query, _, launched in decisions:
        want = mc_ehvi_nd(list(query.samples), query.observed, query.ref)
        # a tenant's first step also launches its initial run, which is
        # not among the candidates its first decision scores
        (ci,) = launched & set(idx.tolist())
        best = int(idx[int(np.argmax(want))])
        # a tie within the rows' roundoff may go either way
        scale = _scale(query, want)
        assert want[list(idx).index(ci)] >= want.max() - RTOL * scale, rid
        exact += ci == best
    assert exact >= 0.9 * len(decisions)
