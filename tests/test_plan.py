"""The query-plan layer (core/plan.py): golden bucketing (query set ->
exact bucket keys / pad shapes), mixed-kind execution parity against the
per-query references, scatter-to-owner semantics, and the plan-stats
invariants on a mixed SO + MOO + 3-objective service cohort."""
import jax
import numpy as np
import pytest

from repro.core import (BOConfig, Constraint, Objective, Repository,
                        scout_search_space)
from repro.core.acquisition import mc_ehvi_batched, mc_ehvi_nd
from repro.core.gp import (batched_posterior, batched_sample, fit_gp,
                           fit_gp_batched, gp_loo_samples)
from repro.core.plan import (CohortLimits, EhviQuery, FitQuery,
                             LooSampleQuery, PlanExecutor,
                             PosteriorDrawQuery, PosteriorQuery,
                             SampleQuery, StepPlanner)
from repro.serve.search_service import SearchRequest, SearchService
from repro.simdata import make_emulator

TOL = 1e-4


def _stack(rng, sizes, d=3):
    xs = [rng.random((n, d)) for n in sizes]
    return fit_gp_batched(xs, [x[:, 0] + np.sin(3 * x[:, 1]) for x in xs])


def _by_kind(plan):
    return {(b.kind, b.key): b for b in plan.buckets}


def test_golden_bucketing_posterior():
    """(q, d) bucket keys; observation axis rounds to 8, fused lane axis
    to a power of two — asserted from the PLAN alone, nothing runs."""
    rng = np.random.default_rng(0)
    st_a = _stack(rng, (5, 9))          # m=2, n_max=9
    st_b = _stack(rng, (4,))            # m=1, n_max=4
    g25, g13 = rng.random((25, 3)), rng.random((13, 3))
    plan = StepPlanner().plan([
        PosteriorQuery(st_a, g25), PosteriorQuery(st_b, g25),
        PosteriorQuery(st_a, g13)])
    assert plan.stats() == {"batches": 2, "queries": 3}
    b = _by_kind(plan)
    big = b[("posterior", (25, 3))]
    assert big.indices == (0, 1)
    assert big.pads == {"n_pad": 16, "m_pad": 4, "lanes": 3}
    small = b[("posterior", (13, 3))]
    assert small.indices == (2,)
    assert small.pads == {"n_pad": 16, "m_pad": 2, "lanes": 2}


def test_golden_bucketing_sample_loo_ehvi_draw():
    """(S, q, d) / (S, n) / (n_obj, S, q) / (S, q) bucket keys with the
    grid axis rounding to 8 and EHVI boxes to a power of two."""
    rng = np.random.default_rng(1)
    st = _stack(rng, (5, 9))
    xt = rng.random((6, 3))
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    gp = fit_gp(rng.random((6, 2)), rng.random(6))
    obs2 = np.array([[1.0, 3.0], [2.0, 2.0], [3.0, 1.0]])
    sa = rng.normal(2.0, 1.0, (16, 9))
    obs3 = np.array([[1.0, 2.0, 3.0], [3.0, 2.0, 1.0]])
    planner = StepPlanner()
    plan = planner.plan([
        SampleQuery(st, xt, keys, 32),
        LooSampleQuery(gp, jax.random.PRNGKey(1), 32),
        EhviQuery((sa, sa + 1.0), obs2, np.array([4.0, 4.0])),
        EhviQuery((sa, sa, sa), obs3, np.array([4.0, 4.0, 4.0])),
        PosteriorDrawQuery(np.zeros(9), np.ones(9), 0.0, 1.0,
                           jax.random.PRNGKey(2), 16),
    ])
    assert plan.stats() == {"batches": 5, "queries": 5}
    b = _by_kind(plan)
    assert b[("sample", (32, 6, 3))].pads == \
        {"n_pad": 16, "q_pad": 8, "m_pad": 2, "lanes": 2}
    assert b[("loo", (32, 6))].pads == {"n_pad": 8, "l_pad": 1, "lanes": 1}
    # 3 staircase points -> 4 segments (already a power of two)
    assert b[("ehvi", (2, 16, 9))].pads == \
        {"k_pad": 4, "q_pad": 16, "l_pad": 1, "lanes": 1}
    # 2 front points, 3 objectives: the coordinate grid has 3*2*3 = 18
    # cells, of which 3 are dominated -> 15 boxes, padded up to 16
    e3 = b[("ehvi", (3, 16, 9))]
    assert e3.pads["k_pad"] == 16 and e3.pads["q_pad"] == 16
    # draw queries deliberately stay exact (not jitted)
    assert b[("draw", (16, 9))].pads == {"lanes": 1}


def test_policy_knobs_live_in_planner():
    """Overriding the planner's policy changes the pads — no other
    module needs touching (the acceptance criterion: one home for
    shape policy)."""
    rng = np.random.default_rng(2)
    st = _stack(rng, (5, 9))
    loose = StepPlanner(obs_round_to=1, m_round_pow2=False)
    plan = loose.plan([PosteriorQuery(st, rng.random((25, 3)))])
    assert plan.buckets[0].pads == {"n_pad": 9, "m_pad": 2, "lanes": 2}


def test_mixed_kind_plan_executes_and_scatters_in_order():
    """One plan carrying every node kind: per-query results match the
    per-query references, and callable owners fire in query order."""
    rng = np.random.default_rng(3)
    st = _stack(rng, (5, 9))
    grid = rng.random((12, 3))
    xt = rng.random((6, 3))
    skeys = jax.random.split(jax.random.PRNGKey(4), 2)
    gp = fit_gp(rng.random((7, 2)), rng.random(7))
    lkey = jax.random.PRNGKey(5)
    dkey = jax.random.PRNGKey(6)
    mu_row, var_row = rng.random(12), rng.random(12) + 0.1
    obs = rng.random((5, 2)) * 3.0
    ref = obs.max(axis=0) * 1.1 + 1e-9
    sa, sb = rng.normal(2, 1, (16, 12)), rng.normal(2, 1, (16, 12))

    fired = []
    queries = [
        PosteriorQuery(st, grid, owner=lambda r: fired.append("post")),
        SampleQuery(st, xt, skeys, 32,
                    owner=lambda r: fired.append("sample")),
        LooSampleQuery(gp, lkey, 32, owner=lambda r: fired.append("loo")),
        PosteriorDrawQuery(mu_row, var_row, 2.0, 3.0, dkey, 16,
                           owner=lambda r: fired.append("draw")),
        EhviQuery((sa, sb), obs, ref, owner=lambda r: fired.append("ehvi")),
    ]
    planner = StepPlanner()
    res = PlanExecutor().execute(planner.plan(queries), counters=(c := {}))
    assert fired == ["post", "sample", "loo", "draw", "ehvi"]
    assert set(c) == {"posterior", "sample", "loo", "draw", "ehvi"}
    assert all(v["launches"] == 1 and v["queries"] == 1
               for v in c.values())

    mu, var = res[0]
    mu0, var0 = batched_posterior(st, grid)
    np.testing.assert_allclose(np.asarray(mu), np.asarray(mu0), atol=TOL)
    np.testing.assert_allclose(np.asarray(var), np.asarray(var0), atol=TOL)
    np.testing.assert_allclose(np.asarray(res[1]),
                               np.asarray(batched_sample(st, xt, skeys, 32)),
                               atol=TOL)
    np.testing.assert_allclose(np.asarray(res[2]),
                               np.asarray(gp_loo_samples(gp, lkey, 32)),
                               atol=TOL)
    eps = jax.random.normal(dkey, (16, 12))
    want_draw = (mu_row[None] + np.asarray(eps) * np.sqrt(var_row)[None]) \
        * 3.0 + 2.0
    np.testing.assert_allclose(np.asarray(res[3]), want_draw, atol=TOL)
    want_ehvi = mc_ehvi_batched(sa, sb, obs, ref)
    scale = max(1.0, float(np.abs(want_ehvi).max()))
    np.testing.assert_allclose(res[4], want_ehvi, atol=TOL * scale)


def test_ehvi_node_three_objectives_matches_oracle():
    """The fused box-decomposition EHVI node vs the recursive-sweep f64
    oracle, n=3 — including an empty and a single-point front sharing
    one launch."""
    rng = np.random.default_rng(7)
    fronts = [rng.random((5, 3)) * 4.0,
              np.array([[1.0, 1.0, 1.0]]),
              np.empty((0, 3))]
    queries, oracles = [], []
    for obs in fronts:
        ref = (obs.max(axis=0) * 1.1 + 1e-9 if len(obs)
               else np.array([4.0, 4.0, 4.0]))
        samples = tuple(rng.normal(2.0, 1.5, (8, 6)) for _ in range(3))
        queries.append(EhviQuery(samples, obs, ref))
        oracles.append(mc_ehvi_nd(samples, obs, ref))
    plan = StepPlanner().plan(queries)
    assert plan.stats() == {"batches": 1, "queries": 3}
    res = PlanExecutor().execute(plan)
    for got, want in zip(res, oracles):
        scale = max(1.0, float(np.abs(want).max()))
        np.testing.assert_allclose(got, want, atol=TOL * scale)


def test_ehvi_deep_front_chunks_box_axis_and_matches_oracle():
    """A deep 3-objective front whose decomposition exceeds one launch
    block: the planner pads the box axis to a chunk multiple (not a
    power of two) and the scanned launch still matches the oracle."""
    from repro.core.acquisition import EHVI_BOX_CHUNK
    rng = np.random.default_rng(9)
    # anti-correlated points are mutually non-dominated -> deep front
    a = np.linspace(0.0, 1.0, 12)
    obs = np.column_stack([a, 1.0 - a, (a * 7.3) % 1.0]) * 4.0
    ref = obs.max(axis=0) * 1.1 + 1e-9
    samples = tuple(rng.normal(2.0, 1.5, (4, 3)) for _ in range(3))
    plan = StepPlanner().plan([EhviQuery(samples, obs, ref)])
    k_pad = plan.buckets[0].pads["k_pad"]
    assert k_pad > EHVI_BOX_CHUNK and k_pad % EHVI_BOX_CHUNK == 0
    (got,) = PlanExecutor().execute(plan)
    want = mc_ehvi_nd(samples, obs, ref)
    scale = max(1.0, float(np.abs(want).max()))
    np.testing.assert_allclose(got, want, atol=TOL * scale)


def test_ehvi_observed_shape_mismatch_rejected():
    """observed columns must match the objective count — a mismatch is
    an immediate planning error, not a silently garbled front."""
    rng = np.random.default_rng(10)
    sa = rng.normal(2.0, 1.0, (4, 3))
    with pytest.raises(ValueError, match="observed"):
        StepPlanner().plan([EhviQuery((sa, sa, sa),
                                      rng.random((3, 2)) * 4.0,
                                      np.array([4.0, 4.0, 4.0]))])


def test_enumerate_buckets_covers_live_plan_signatures():
    """The enumerated vocabulary is CLOSED over a cohort within its
    limits: every bucket a live mixed plan produces (draw excepted —
    unjitted) has a launch signature among the enumerated ones, with
    exact key dims normalised to their padded values."""
    rng = np.random.default_rng(11)
    st = _stack(rng, (5, 9))                       # m=2, n<=9, d=3
    xt = rng.random((6, 3))
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    gp = fit_gp(rng.random((6, 3)), rng.random(6))
    obs2 = np.array([[1.0, 3.0], [2.0, 2.0], [3.0, 1.0]])
    sa = rng.normal(2.0, 1.0, (16, 9))
    planner = StepPlanner()
    plan = planner.plan([
        PosteriorQuery(st, rng.random((25, 3))),
        SampleQuery(st, xt, keys, 32),
        LooSampleQuery(gp, jax.random.PRNGKey(1), 32),
        EhviQuery((sa, sa + 1.0), obs2, np.array([4.0, 4.0])),
        PosteriorDrawQuery(np.zeros(9), np.ones(9), 0.0, 1.0,
                           jax.random.PRNGKey(2), 16),
    ])
    limits = CohortLimits(d=3, q_grid=25, max_obs=9, max_lanes=2,
                          n_samples=(32,), n_mc=(16,),
                          n_objectives=(2,), max_ehvi_boxes=4)
    enumerated = planner.enumerate_buckets(limits)
    sigs = {planner.launch_signature(b) for b in enumerated}
    # no duplicate shapes, no unjitted draw buckets in the vocabulary
    assert len(sigs) == len(enumerated)
    assert all(b.kind != "draw" for b in enumerated)
    for b in plan.buckets:
        if b.kind == "draw":
            continue
        assert planner.launch_signature(b) in sigs, (b.kind, b.key, b.pads)
    # signature normalisation: the live sample bucket keys the EXACT
    # grid length (6) but signs at the padded one (8), equal to its
    # enumerated twin
    live = {b.kind: b for b in plan.buckets}
    assert live["sample"].key == (32, 6, 3)
    assert planner.launch_signature(live["sample"]) == \
        ("sample", 32, 8, 3, 16, 2)


def test_plan_executor_fused_posterior_matches_default():
    """PlanExecutor(fused_posterior=True) routes posterior buckets
    through the fused kernel dispatch: (mu, var) match the vmapped
    baseline, and the in-kernel EI head matches the eager
    expected_improvement chain the default path uses."""
    rng = np.random.default_rng(12)
    st_a = _stack(rng, (5, 9))
    st_b = _stack(rng, (4,))
    grid = rng.random((13, 3))

    def queries():
        return [PosteriorQuery(st_a, grid),
                PosteriorQuery(st_b, grid, best=0.4)]

    planner = StepPlanner()
    base = PlanExecutor().execute(planner.plan(queries()))
    fused = PlanExecutor(fused_posterior=True).execute(
        planner.plan(queries()))
    assert len(base[0]) == 2 and len(base[1]) == 3
    for b, f in zip(base, fused):
        assert len(b) == len(f)
        for want, got in zip(b, f):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=TOL)


# -- where each leg's results live --------------------------------------------


@pytest.fixture
def launched(monkeypatch):
    """Every bucket launch's device outputs as ``(kind, out)``, in launch
    order: the executor's jitted launches and the unjitted draw."""
    from repro.core import plan as plan_mod
    outs = []

    def recording(kind, fn):
        def run(*args, **kw):
            out = fn(*args, **kw)
            outs.append((kind, out))
            return out
        return run

    launch = PlanExecutor._launch
    monkeypatch.setattr(PlanExecutor, "_launch",
                        lambda self, kind, *a: recording(
                            kind, launch(self, kind, *a)))
    monkeypatch.setattr(plan_mod, "_draw_launch",
                        recording("draw", plan_mod._draw_launch))
    return outs


def _host_leg_queries(rng):
    """Two posterior buckets (one with an EI head), a draw bucket, and
    an EHVI bucket mixing the sample and posterior forms."""
    st_a, st_b = _stack(rng, (5, 9)), _stack(rng, (4,))
    obs = np.array([[1.0, 3.0], [2.0, 2.0], [3.0, 1.0]])
    ref = np.array([4.0, 4.0])
    sa = rng.normal(2.0, 1.0, (16, 9)).astype(np.float32)
    rows = [rng.normal(size=9).astype(np.float32) for _ in range(2)]
    vrows = [rng.uniform(0.1, 1.0, 9).astype(np.float32) for _ in range(2)]
    keys = [jax.random.PRNGKey(i) for i in range(4)]
    return [
        PosteriorQuery(st_a, rng.random((13, 3))),
        PosteriorQuery(st_b, rng.random((13, 3)), best=0.4),
        PosteriorQuery(st_a, rng.random((7, 3))),
        PosteriorDrawQuery(rows[0], vrows[0], np.float32(2.0),
                           np.float32(3.0), keys[0], 16),
        PosteriorDrawQuery(rows[1], vrows[1], np.float32(-1.0),
                           np.float32(0.5), keys[1], 16),
        EhviQuery((sa, sa + 1.0), obs, ref),
        EhviQuery(None, obs, ref, mu=tuple(rows), var=tuple(vrows),
                  y_mean=(2.0, -1.0), y_std=(3.0, 0.5),
                  keys=tuple(keys[2:]), n_mc=16),
    ]


@pytest.mark.parametrize("fused", [False, True],
                         ids=["vmapped", "fused"])
def test_host_legs_hand_back_numpy_rows(launched, fused):
    """The posterior, draw and EHVI legs (vmapped, and the fused
    posterior and EHVI kernels) hand each query numpy rows, bitwise
    equal to slicing the launch's own device outputs; the EI row of a
    query with ``best`` equals ``expected_improvement`` of its rows."""
    from repro.core.acquisition import expected_improvement
    rng = np.random.default_rng(31)
    queries = _host_leg_queries(rng)
    plan = StepPlanner().plan(queries)
    ex = PlanExecutor(fused_posterior=fused, fused_ehvi=fused,
                      impl="xla", donate=False)
    res = ex.execute(plan)
    by_kind = {}
    for kind, out in launched:
        by_kind.setdefault(kind.replace("fused_", ""), []).append(out)
    for b in plan.buckets:
        # buckets launch in plan order; a vmapped EHVI bucket's
        # posterior-form lane draws after the draw bucket's launch
        out = by_kind[b.kind].pop(0)
        off = 0
        for j, i in enumerate(b.indices):
            r = res[i]
            if b.kind == "posterior":
                m = queries[i].stack.m
                rows = slice(off, off + m)
                off += m
                want = [out[0][rows], out[1][rows]]
                if queries[i].best is not None:
                    want.append(out[2][rows] if fused else
                                expected_improvement(out[0][rows],
                                                     out[1][rows],
                                                     queries[i].best))
                assert len(r) == len(want)
            else:
                r, want = [r], [out[j] if b.kind == "draw"
                                else out[j][:9]]
            for got, w in zip(r, want):
                assert isinstance(got, np.ndarray)
                np.testing.assert_array_equal(got, np.asarray(w))


def test_device_legs_hand_back_device_arrays():
    """The sample, LOO and fit legs feed further launches (the ranking
    loss, the posterior round), so their results stay on the device."""
    rng = np.random.default_rng(32)
    st = _stack(rng, (5, 9))
    gp = fit_gp(rng.random((6, 3)), rng.random(6))
    res = PlanExecutor().execute(StepPlanner().plan([
        SampleQuery(st, rng.random((6, 3)),
                    jax.random.split(jax.random.PRNGKey(0), 2), 8),
        LooSampleQuery(gp, jax.random.PRNGKey(1), 8),
        _fit_q(rng, 6, 16)]))
    assert isinstance(res[0], jax.Array) and res[0].shape == (2, 8, 6)
    assert isinstance(res[1], jax.Array) and res[1].shape == (8, 6)
    stack, lane = res[2]
    assert lane == 0
    for f in ("x", "y", "chol", "alpha", "log_lengthscales"):
        assert isinstance(getattr(stack, f), jax.Array), f


def test_host_unpack_counters_of_a_mixed_plan():
    """``host_unpack_rows`` counts the posterior lanes plus one row per
    draw and EHVI query; ``host_unpack_reads`` is 2 per posterior launch
    (3 where a query asks for the EI row) and 1 per draw or EHVI launch;
    the sample, LOO and fit legs add neither."""
    from repro.core.plan import flatten_counters
    rng = np.random.default_rng(33)
    st = _stack(rng, (5, 9))
    gp = fit_gp(rng.random((6, 3)), rng.random(6))
    queries = _host_leg_queries(rng) + [
        SampleQuery(st, rng.random((6, 3)),
                    jax.random.split(jax.random.PRNGKey(0), 2), 8),
        LooSampleQuery(gp, jax.random.PRNGKey(1), 8),
        _fit_q(rng, 6, 16)]
    c = {}
    PlanExecutor().execute(StepPlanner().plan(queries), counters=c)
    # posterior buckets: (13, 3) carries 2 + 1 lanes and an EI head,
    # (7, 3) carries 2 lanes
    assert c["posterior"]["launches"] == 2
    assert c["posterior"]["host_unpack_rows"] == 5
    assert c["posterior"]["host_unpack_reads"] == 3 + 2
    assert c["draw"]["host_unpack_rows"] == 2
    assert c["draw"]["host_unpack_reads"] == 1
    assert c["ehvi"]["host_unpack_rows"] == 2
    assert c["ehvi"]["host_unpack_reads"] == 1
    for kind in ("sample", "loo", "fit"):
        assert "host_unpack_rows" not in c[kind]
        assert "host_unpack_reads" not in c[kind]
    flat = {}
    flatten_counters(c, flat, ("posterior", "draw", "ehvi", "sample"))
    assert flat["host_unpack_rows"] == 9
    assert flat["host_unpack_reads"] == 7


# -- plan stats on a live mixed cohort ---------------------------------------


EMU = make_emulator()
SPACE = scout_search_space()
WID = EMU.workload_ids()[6]


def _support_repo(wid=WID, users=2, runs=12, seed=99):
    repo = Repository()
    rng = np.random.default_rng(seed)
    for u in range(users):
        for ci in rng.choice(len(SPACE), runs, replace=False):
            repo.add_run(EMU.make_record(f"anon-{u}", wid,
                                         SPACE.configs[ci], rng))
    return repo


def test_plan_stats_invariants_mixed_so_moo_3obj_cohort():
    """plan_batches <= plan_queries always, and the aggregate counters
    are exactly the sum of the per-kind ones — on a cohort mixing
    single-objective, 2-objective, and 3-objective karasu tenants."""
    svc = SearchService(_support_repo(), slots=3)
    cons = [Constraint("runtime", EMU.runtime_target(WID, 50))]
    cfg = BOConfig(max_iters=5)
    svc.submit(SearchRequest(SPACE, lambda c: EMU.run(WID, c, rng=None),
                             Objective("cost"), cons, method="karasu",
                             bo_config=cfg, seed=0))
    svc.submit(SearchRequest(
        SPACE, lambda c: EMU.run(WID, c, rng=None), None, cons,
        method="karasu", bo_config=cfg, seed=1,
        objectives=[Objective("cost"), Objective("energy")], n_mc=8))
    svc.submit(SearchRequest(
        SPACE, lambda c: EMU.run(WID, c, rng=None), None, cons,
        method="karasu", bo_config=cfg, seed=2,
        objectives=[Objective("cost"), Objective("energy"),
                    Objective("runtime")], n_mc=8))
    done = {c.rid: c.result for c in svc.run()}
    assert sorted(done) == [0, 1, 2]
    # the 3-objective session produced a (k, 3) front
    front = done[2].meta["pareto_front"]
    assert front.ndim == 2 and front.shape[1] == 3 and len(front) >= 1

    s = svc.stats
    assert s["plan_batches"] >= 1
    assert s["plan_batches"] <= s["plan_queries"]
    assert s["plan_batches"] == (s["posterior_batches"]
                                 + s["sample_batches"] + s["ehvi_batches"]
                                 + s["fit_batches"])
    assert s["plan_queries"] == (s["posterior_queries"]
                                 + s["sample_queries"] + s["ehvi_jobs"]
                                 + s["fit_jobs"])
    # fusion engaged on every leg, the fit round included
    assert s["posterior_batches"] < s["posterior_queries"]
    assert s["sample_batches"] < s["sample_queries"]
    assert s["ehvi_batches"] <= s["ehvi_jobs"]
    assert 0 < s["fit_batches"] < s["fit_jobs"]


def test_plan_stats_zero_without_fusion():
    """The loop baselines never enter the plan: with
    fuse_posteriors=False, fuse_samples=False the only planned launches
    are the fit rounds, which ALWAYS ride the plan (the fit leg is a
    first-class plan node with no loop twin)."""
    svc = SearchService(_support_repo(), slots=1, fuse_posteriors=False,
                        fuse_samples=False)
    svc.submit(SearchRequest(
        SPACE, lambda c: EMU.run(WID, c, rng=None), None,
        [Constraint("runtime", EMU.runtime_target(WID, 50))],
        method="karasu", bo_config=BOConfig(max_iters=4), seed=0,
        objectives=[Objective("cost"), Objective("energy"),
                    Objective("runtime")], n_mc=8))
    (c,) = svc.run()
    assert len(c.result.observations) == 4
    assert svc.stats["plan_batches"] == svc.stats["fit_batches"] > 0
    assert svc.stats["plan_queries"] == svc.stats["fit_jobs"]
    assert svc.stats["posterior_batches"] == 0
    assert svc.stats["sample_batches"] == 0
    assert svc.stats["ehvi_batches"] == 0


def test_posterior_form_ehvi_query_shares_sample_form_bucket():
    """A posterior-form EhviQuery (mu/var rows + PRNG keys, no
    materialised samples) must land in the same ``("ehvi", (n_obj, S,
    q))`` bucket as its sample-form twin — the fused executor relies on
    mixed buckets, and the AOT vocabulary must not split on form."""
    rng = np.random.default_rng(9)
    obs = np.array([[1.0, 3.0], [2.0, 2.0], [3.0, 1.0]])
    ref = np.array([4.0, 4.0])
    sa = rng.normal(2.0, 1.0, (16, 9))
    sample_q = EhviQuery((sa, sa + 1.0), obs, ref)
    post_q = EhviQuery(
        None, obs, ref,
        mu=(rng.normal(size=9), rng.normal(size=9)),
        var=(rng.uniform(0.1, 1.0, 9), rng.uniform(0.1, 1.0, 9)),
        y_mean=(0.0, 0.0), y_std=(1.0, 1.0),
        keys=(jax.random.PRNGKey(0), jax.random.PRNGKey(1)), n_mc=16)
    planner = StepPlanner()
    assert planner.bucket_key(post_q) == planner.bucket_key(sample_q) \
        == ("ehvi", (2, 16, 9))
    plan = planner.plan([sample_q, post_q])
    assert plan.stats() == {"batches": 1, "queries": 2}
    # both forms execute through one launch, fused or vmapped, and agree
    from repro.core.plan import PlanExecutor
    outs = {}
    for name, ex in (("vmapped", PlanExecutor(donate=False)),
                     ("fused", PlanExecutor(fused_ehvi=True, impl="xla",
                                            donate=False))):
        got = []
        q1 = EhviQuery((sa, sa + 1.0), obs, ref,
                       owner=lambda r: got.append(np.asarray(r)))
        q2 = EhviQuery(None, obs, ref, mu=post_q.mu, var=post_q.var,
                       y_mean=post_q.y_mean, y_std=post_q.y_std,
                       keys=post_q.keys, n_mc=16,
                       owner=lambda r: got.append(np.asarray(r)))
        ex.execute(planner.plan([q1, q2]))
        outs[name] = got
    for a, b in zip(outs["vmapped"], outs["fused"]):
        np.testing.assert_allclose(a, b, atol=1e-5)


def _fit_q(rng, n, steps, d=3, warm=False):
    x = rng.random((n, d)).astype(np.float32)
    y = (x[:, 0] + np.sin(3 * x[:, 1])).astype(np.float32)
    if warm:
        return FitQuery(x, y, 0.1, steps,
                        init_ls=rng.normal(0, 0.3, d).astype(np.float32),
                        init_sf=np.float32(rng.normal(0, 0.3)))
    return FitQuery(x, y, 0.1, steps)


def test_golden_bucketing_fit_warm_and_cold():
    """Warm (short-refine) and cold (full-schedule) FitQuery nodes of
    one step land in DIFFERENT buckets by construction — ``steps`` and
    ``noise`` are jit-static on the fit launch, so both sit in the
    bucket key — while the padded shapes follow the shared policy:
    observation axis to multiples of 8, lane axis to a power of two."""
    rng = np.random.default_rng(21)
    plan = StepPlanner().plan([
        _fit_q(rng, 5, 120), _fit_q(rng, 9, 120),
        _fit_q(rng, 7, 16, warm=True), _fit_q(rng, 4, 16, warm=True)])
    assert plan.stats() == {"batches": 2, "queries": 4}
    b = _by_kind(plan)
    cold = b[("fit", (3, 120, 0.1))]
    assert cold.indices == (0, 1)
    assert cold.pads == {"n_pad": 16, "m_pad": 2, "lanes": 2}
    warm = b[("fit", (3, 16, 0.1))]
    assert warm.indices == (2, 3)
    assert warm.pads == {"n_pad": 8, "m_pad": 2, "lanes": 2}
    # the signature names the schedule rung and noise explicitly, so
    # they can never be confused with the (positional) axis pads
    planner = StepPlanner()
    assert planner.launch_signature(cold) == \
        ("fit", 3, 16, 2, ("steps", 120), ("noise", 0.1))
    assert planner.launch_signature(warm) == \
        ("fit", 3, 8, 2, ("steps", 16), ("noise", 0.1))


def test_enumerate_buckets_walks_both_fit_rungs():
    """The AOT vocabulary carries BOTH fit schedule rungs (warm refine
    + cold full fit) across the whole (n_pad, m_pad) ladder, and live
    warm/cold fit buckets sign inside it. Disabling warm starting
    (``fit_warm_steps=None``) collapses the ladder to the cold rung
    only — at which point a live warm bucket is out-of-vocabulary."""
    planner = StepPlanner()
    limits = CohortLimits(d=3, q_grid=8, max_obs=9, max_lanes=2)
    assert planner.fit_step_rungs(limits) == [16, 120]
    cold_only = CohortLimits(d=3, q_grid=8, max_obs=9, max_lanes=2,
                             fit_warm_steps=None)
    assert planner.fit_step_rungs(cold_only) == [120]
    sigs = {planner.launch_signature(b)
            for b in planner.enumerate_buckets(limits) if b.kind == "fit"}
    # full cross product: 2 rungs x obs pads {8, 16} x lane pads {1, 2}
    assert sigs == {("fit", 3, n, m, ("steps", s), ("noise", 0.1))
                    for s in (16, 120) for n in (8, 16) for m in (1, 2)}
    rng = np.random.default_rng(22)
    live = StepPlanner().plan([
        _fit_q(rng, 9, 120), _fit_q(rng, 6, 16, warm=True)])
    for b in live.buckets:
        assert planner.launch_signature(b) in sigs, (b.key, b.pads)
    cold_sigs = {planner.launch_signature(b)
                 for b in planner.enumerate_buckets(cold_only)
                 if b.kind == "fit"}
    warm_live = next(b for b in live.buckets if b.key[1] == 16)
    assert planner.launch_signature(warm_live) not in cold_sigs


def test_ehvi_vocabulary_walks_reachable_candidates_and_moo_lanes():
    """EHVI buckets span only the remaining-candidate counts a session
    of at most ``max_obs`` observations can score, and a lane ladder up
    to the ``ehvi_lanes`` bound (default: the model-lane bound) — and
    the closure analysis still finds every live EHVI signature inside."""
    from repro.analysis.vocab_closure import check_closure
    limits = CohortLimits(d=3, q_grid=40, max_obs=9, max_lanes=64,
                          n_mc=(16,), n_objectives=(2, 3),
                          max_ehvi_boxes=64)
    planner = StepPlanner()
    ehvi = [b for b in planner.enumerate_buckets(limits, ehvi_lanes=3)
            if b.kind == "ehvi"]
    assert {b.pads["q_pad"] for b in ehvi} == {32, 40}
    assert {b.pads["l_pad"] for b in ehvi} == {1, 2, 4}
    assert {b.pads["l_pad"] for b in planner.enumerate_buckets(limits)
            if b.kind == "ehvi"} == {1, 2, 4, 8, 16, 32, 64}
    assert check_closure(limits=limits, shard_sizes=(1, 2)) == []
