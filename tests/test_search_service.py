"""Multi-tenant SearchService: batching, shared cache, serving semantics,
async profiling (ProfileExecutor backends + WAITING_PROFILE overlap),
fused posterior/acquisition query plan, multi-objective sessions."""
import threading

import jax

import numpy as np
import pytest

from repro.core import (BOConfig, Constraint, Objective, Repository,
                        run_search, run_search_moo, scout_search_space)
from repro.launch.compile_stats import CompileWatcher
from repro.serve.profile_executor import (FakeProfileExecutor,
                                          ProcessPoolProfileExecutor,
                                          ProfileJob, SyncProfileExecutor,
                                          ThreadPoolProfileExecutor)
from repro.serve.search_service import (SearchRequest, SearchService)
from repro.simdata import make_emulator

EMU = make_emulator()
SPACE = scout_search_space()
WIDS = EMU.workload_ids()
WID = WIDS[6]
RT = EMU.runtime_target(WID, 50)
OPT = EMU.optimal_cost(WID, RT)


def _request(seed, *, method="naive", wid=WID, max_iters=6, **kw):
    rng = np.random.default_rng(seed)
    return SearchRequest(
        SPACE, lambda c: EMU.run(wid, c, rng=rng), Objective("cost"),
        [Constraint("runtime", EMU.runtime_target(wid, 50))],
        method=method, bo_config=BOConfig(max_iters=max_iters), seed=seed,
        **kw)


def _support_repo(wid=WID, users=2, runs=12, seed=99):
    repo = Repository()
    rng = np.random.default_rng(seed)
    for u in range(users):
        for ci in rng.choice(len(SPACE), runs, replace=False):
            repo.add_run(EMU.make_record(f"anon-{u}", wid,
                                         SPACE.configs[ci], rng))
    return repo


def test_service_completes_all_tenants_batched():
    svc = SearchService(Repository(), slots=3)
    rids = [svc.submit(_request(s)) for s in range(3)]
    done = svc.run()
    assert sorted(c.rid for c in done) == rids
    for c in done:
        assert len(c.result.observations) == 6
        assert c.result.best_index_per_iter[-1] >= 0
    # 3 tenants x 2 measures x 3 model iterations collapsed into 3
    # fit batches (one per step), not 18 separate fits
    assert svc.stats["fit_jobs"] == 18
    assert svc.stats["fit_batches"] == 3
    assert svc.collect() == []          # collect drains


def test_service_queueing_beyond_slots():
    svc = SearchService(Repository(), slots=2)
    for s in range(5):
        svc.submit(_request(s, max_iters=4))
    done = svc.run()
    assert len(done) == 5


def test_service_karasu_uses_shared_store():
    repo = _support_repo()
    svc = SearchService(repo, slots=4)
    for s in range(4):
        svc.submit(_request(s, method="karasu"))
    done = svc.run()
    assert len(done) == 4
    for c in done:
        assert c.result.meta["selected"], "karasu never selected supports"
    ctx, = svc._contexts.values()
    # 2 support workloads x 2 measures fit exactly once, shared by all 4
    # tenants across all iterations
    assert ctx.store.misses == 4
    assert ctx.store.hits > ctx.store.misses


def test_service_matches_run_search_quality():
    repo = _support_repo()
    svc = SearchService(repo, slots=2)
    for s in range(2):
        svc.submit(_request(s, method="karasu", max_iters=8))
    gaps_svc = []
    for c in svc.run():
        i = c.result.best_index_per_iter[-1]
        gaps_svc.append(c.result.observations[i].measures["cost"] / OPT - 1)
    gaps_loop = []
    for s in range(2):
        rng = np.random.default_rng(s)
        r = run_search(SPACE, lambda c: EMU.run(WID, c, rng=rng),
                       Objective("cost"), [Constraint("runtime", RT)],
                       method="karasu", repository=_support_repo(),
                       bo_config=BOConfig(max_iters=8), seed=s)
        i = r.best_index_per_iter[-1]
        gaps_loop.append(r.observations[i].measures["cost"] / OPT - 1)
    assert np.mean(gaps_svc) <= np.mean(gaps_loop) + 0.25, (gaps_svc,
                                                            gaps_loop)


def test_service_publish_invalidates_incrementally():
    repo = _support_repo(users=1)
    svc = SearchService(repo, slots=2)
    svc.submit(_request(0, method="karasu", share_as="tenant-0"))
    svc.submit(_request(1, method="karasu"))
    n0 = len(repo)
    done = svc.run()
    assert len(done) == 2
    # tenant 0 published every profiling run to the shared repository
    assert len(repo.runs("tenant-0")) == 6
    assert len(repo) == n0 + 6
    # and the repository version moved, so later searches see fresh data
    assert repo.version("tenant-0") == 6
    # a publishing tenant must never select its OWN runs as support
    # (they score ~1.0 against themselves and bypass the LOO safeguard);
    # the non-publishing tenant is free to consume them
    r0 = next(c.result for c in done if c.rid == 0)
    assert all("tenant-0" not in sel for sel in r0.meta["selected"])


def test_service_early_stop():
    svc = SearchService(Repository(), slots=1)
    rng = np.random.default_rng(0)
    req = SearchRequest(
        SPACE, lambda c: EMU.run(WID, c, rng=rng), Objective("cost"),
        [Constraint("runtime", RT)], method="naive",
        bo_config=BOConfig(max_iters=20, early_stop=True), seed=0)
    svc.submit(req)
    done = svc.run()
    assert len(done) == 1
    res = done[0].result
    assert res.meta["n_profiled"] >= 6
    assert res.meta["n_profiled"] <= 20


def test_service_rejects_unknown_method():
    svc = SearchService()
    with pytest.raises(ValueError):
        svc.submit(_request(0, method="bogus"))


def test_service_rejects_unknown_wait_mode():
    with pytest.raises(ValueError):
        SearchService(wait_mode="bogus")


def test_collect_empty_service_returns_immediately():
    """Regression: collect() on a service with zero submitted searches
    must return [] instead of blocking or raising — with and without
    wait semantics, for every executor backend."""
    for executor in (None, SyncProfileExecutor(),
                     ThreadPoolProfileExecutor(max_workers=1),
                     FakeProfileExecutor()):
        svc = SearchService(executor=executor)
        assert svc.collect() == []
        assert svc.collect(wait=True) == []          # must not block
        assert svc.collect(wait=True, timeout=0.01) == []
        svc.close()


def _noise_free_request(seed, *, method="naive", max_iters=6,
                        barrier=None):
    """profile_fn without shared RNG state: safe to call from executor
    threads in any order, so sync and async services see identical data."""
    def fn(c):
        out = EMU.run(WID, c, rng=None)
        if barrier is not None:
            barrier.wait(timeout=30)
        return out
    return SearchRequest(SPACE, fn, Objective("cost"),
                         [Constraint("runtime", RT)], method=method,
                         bo_config=BOConfig(max_iters=max_iters), seed=seed)


def _result_fingerprint(res):
    return (tuple(tuple(sorted(o.config.items())) for o in res.observations),
            tuple(tuple(sorted(o.measures.items()))
                  for o in res.observations),
            tuple(res.best_index_per_iter), res.stopped_at)


def test_async_threadpool_bitwise_matches_sync():
    """Thread-pool execution with a barrier forcing each round's arrival
    order must produce bitwise-identical BOResults to the synchronous
    path: same configs, same measures, same incumbents."""
    n = 3
    sync_svc = SearchService(Repository(), slots=n)
    for s in range(n):
        sync_svc.submit(_noise_free_request(s))
    sync_done = {c.rid: c.result for c in sync_svc.run()}

    # all n tenants advance in lockstep (same max_iters, no early stop),
    # so every wave is exactly n profiling runs: a Barrier(n) holds each
    # wave's results back until all have executed, forcing arrival order
    barrier = threading.Barrier(n)
    async_svc = SearchService(
        Repository(), slots=n,
        executor=ThreadPoolProfileExecutor(max_workers=n),
        wait_mode="all")
    for s in range(n):
        async_svc.submit(_noise_free_request(s, barrier=barrier))
    async_done = {c.rid: c.result for c in async_svc.run()}
    async_svc.close()

    assert sorted(sync_done) == sorted(async_done)
    for rid in sync_done:
        assert (_result_fingerprint(sync_done[rid])
                == _result_fingerprint(async_done[rid])), rid


def test_async_fake_executor_overlaps_heterogeneous_latencies():
    """With per-tenant latencies of 1..4 virtual ticks and wait_mode
    'any', fast sessions keep stepping while slow profilers are in
    flight (WAITING_PROFILE), and every session still completes its
    full budget with the same per-session data as the sync path."""
    n = 4
    latency = {rid: rid + 1 for rid in range(n)}
    exe = FakeProfileExecutor(lambda job: latency[job.rid])
    svc = SearchService(Repository(), slots=n, executor=exe,
                        wait_mode="any")
    for s in range(n):
        svc.submit(_noise_free_request(s, max_iters=5))
    done = {c.rid: c.result for c in svc.run()}
    assert sorted(done) == list(range(n))
    for res in done.values():
        assert len(res.observations) == 5
    # the service had to block on stragglers at least once...
    assert svc.stats["profile_waits"] > 0
    # ...and virtual time advanced instead of wall-clock sleeping
    assert exe.ticks > 0

    # per-session trajectories match a synchronous service: overlap must
    # not change WHAT a session profiles, only WHEN results land
    sync_svc = SearchService(Repository(), slots=n)
    for s in range(n):
        sync_svc.submit(_noise_free_request(s, max_iters=5))
    sync_done = {c.rid: c.result for c in sync_svc.run()}
    for rid in done:
        assert (_result_fingerprint(done[rid])
                == _result_fingerprint(sync_done[rid])), rid


def test_profile_executor_error_propagates():
    def boom(c):
        raise RuntimeError("cluster fell over")
    svc = SearchService(Repository(), slots=1)
    svc.submit(SearchRequest(SPACE, boom, Objective("cost"), [],
                             bo_config=BOConfig(max_iters=4), seed=0))
    with pytest.raises(RuntimeError, match="cluster fell over"):
        svc.run()
    # the erroring session is settled, not wedged in WAITING_PROFILE:
    # every failed run decremented inflight before raising
    assert all(s.inflight == 0 for s in svc.active.values())


def test_session_error_does_not_strand_held_outcomes():
    """An errored outcome must not stop the drain of later outcomes the
    executor already handed over, nor leave the session WAITING."""
    from repro.serve.profile_executor import ProfileOutcome
    from repro.serve.search_service import READY, _Session
    s = _Session(0, _noise_free_request(0))
    j0, j1 = s.launch(10), s.launch(11)
    meas, metr = EMU.run(WID, SPACE.configs[11], rng=None)
    # seq 1 lands first and is held back behind outstanding seq 0
    s.record(ProfileOutcome(j1, meas, metr), None)
    assert s.observations == [] and s.inflight == 2
    # then seq 0 lands with an error: raise, but drain seq 1 and settle
    with pytest.raises(RuntimeError, match="boom"):
        s.record(ProfileOutcome(j0, error=RuntimeError("boom")), None)
    assert len(s.observations) == 1
    assert s.inflight == 0 and s.state == READY


def test_fake_executor_fractional_timeout_progresses():
    """A sub-tick timeout must still advance the virtual clock (ceil),
    not busy-spin with a zero tick budget."""
    exe = FakeProfileExecutor(lambda job: 1)
    exe.submit(ProfileJob(0, 0, {}),
               lambda c: ({"cost": 1.0}, np.zeros((6, 5))))
    assert len(exe.collect(timeout=0.5)) == 1
    assert exe.ticks == 1


def test_collect_wait_timeout_honored_with_slow_profiler():
    """collect(wait=True, timeout=...)'s deadline must cap the executor
    waits inside step(), not just be checked between steps."""
    import time as _t

    def slow(c):
        _t.sleep(1.5)
        return EMU.run(WID, c, rng=None)

    svc = SearchService(Repository(), slots=1,
                        executor=ThreadPoolProfileExecutor(max_workers=1))
    svc.submit(SearchRequest(SPACE, slow, Objective("cost"), [],
                             bo_config=BOConfig(n_init=1, max_iters=3),
                             seed=0))
    t0 = _t.monotonic()
    assert svc.collect(wait=True, timeout=0.3) == []
    assert _t.monotonic() - t0 < 1.2    # returned before the 1.5 s run
    svc.close()

    # wait_mode="all" makes TWO executor waits per step (drain, then
    # collect); they must share one deadline, not double it
    svc2 = SearchService(Repository(), slots=1, wait_mode="all",
                         executor=ThreadPoolProfileExecutor(max_workers=1))
    svc2.submit(SearchRequest(SPACE, slow, Objective("cost"), [],
                              bo_config=BOConfig(n_init=1, max_iters=3),
                              seed=0))
    t0 = _t.monotonic()
    assert svc2.collect(wait=True, timeout=0.3) == []
    assert _t.monotonic() - t0 < 1.0
    svc2.close()


def test_service_cross_tenant_rgpe_batched_in_one_call():
    """All (tenant, measure) karasu ensembles of a step go through ONE
    padded ranking-loss launch: rgpe_batches counts steps (per kernel
    impl), not tenants x measures."""
    repo = _support_repo()
    svc = SearchService(repo, slots=4)
    for s in range(4):
        svc.submit(_request(s, method="karasu"))
    svc.run()
    assert svc.stats["rgpe_jobs"] > svc.stats["rgpe_batches"]
    # 3 scoring steps (obs 3 -> 6), one batch each
    assert svc.stats["rgpe_batches"] == 3


# -- fused posterior query plan + multi-objective serving --------------------


def _moo_request(seed, *, method="naive", wid=WID, max_iters=5, n_mc=16,
                 **kw):
    return SearchRequest(
        SPACE, lambda c: EMU.run(wid, c, rng=None), None,
        [Constraint("runtime", EMU.runtime_target(wid, 50))],
        method=method, bo_config=BOConfig(max_iters=max_iters), seed=seed,
        objectives=[Objective("cost"), Objective("energy")], n_mc=n_mc,
        **kw)


def test_service_rejects_malformed_moo_requests():
    svc = SearchService()
    # objective AND objectives
    with pytest.raises(ValueError, match="either objective or objectives"):
        svc.submit(SearchRequest(
            SPACE, lambda c: EMU.run(WID, c), Objective("cost"),
            objectives=[Objective("cost"), Objective("energy")]))
    # wrong arity
    with pytest.raises(ValueError, match="two or more"):
        svc.submit(SearchRequest(SPACE, lambda c: EMU.run(WID, c), None,
                                 objectives=[Objective("cost")]))
    # neither
    with pytest.raises(ValueError, match="needs an objective"):
        svc.submit(SearchRequest(SPACE, lambda c: EMU.run(WID, c), None))
    # augmented has no MOO path
    with pytest.raises(ValueError, match="naive|karasu"):
        svc.submit(_moo_request(0, method="augmented"))


def test_service_step_fuses_all_grid_posteriors():
    """A single-space cohort's step executes EVERY grid posterior —
    targets, all RGPE support stacks, SO and MOO tenants — in ONE padded
    batched_posterior launch: posterior_batches counts scoring steps,
    posterior_queries the fused stacks."""
    repo = _support_repo()
    svc = SearchService(repo, slots=4)
    for s in range(2):
        svc.submit(_request(s, method="karasu", max_iters=6))
    for s in range(2):
        svc.submit(_moo_request(10 + s, method="karasu", max_iters=6))
    done = svc.run()
    assert len(done) == 4
    # lockstep cohort: scoring steps = max_iters - n_init = 3, and every
    # step fused its targets + all ensembles into one launch
    assert svc.stats["posterior_batches"] == 3
    # each scoring step queried 1 target stack + one support stack per
    # (karasu tenant, measure): strictly more queries than launches
    assert svc.stats["posterior_queries"] > svc.stats["posterior_batches"]


def test_service_fused_posteriors_match_per_session_loop():
    """Acceptance: fused-plan posteriors/acquisitions agree with the
    per-session-loop path (fuse_posteriors=False) to 1e-4."""
    def build(fuse):
        svc = SearchService(_support_repo(), slots=4,
                            fuse_posteriors=fuse)
        for s in range(2):
            svc.submit(_request(s, method="karasu"))
        svc.submit(_moo_request(7, method="karasu"))
        svc.step()          # admit + init + first scoring round
        return svc

    fused, loop = build(True), build(False)
    s_f = [fused.active[r] for r in sorted(fused.active)]
    s_l = [loop.active[r] for r in sorted(loop.active)]
    # both services took identical trajectories so far
    for a, b in zip(s_f, s_l):
        assert [o.config for o in a.observations] == \
            [o.config for o in b.observations]
    posts_f = fused._posterior_phase(s_f)
    posts_l = loop._posterior_phase(s_l)
    assert fused.stats["posterior_batches"] >= 1
    assert loop.stats["posterior_batches"] == 0
    for a in s_f:
        for m in a.measures:
            np.testing.assert_allclose(
                np.asarray(posts_f[a.rid][m]["mu"]),
                np.asarray(posts_l[a.rid][m]["mu"]), atol=1e-4)
            np.testing.assert_allclose(
                np.asarray(posts_f[a.rid][m]["var"]),
                np.asarray(posts_l[a.rid][m]["var"]), atol=1e-4)
    # MOO acquisition: batched EHVI vs the per-candidate reference loop
    # on the same posteriors
    moo_f = next(s for s in s_f if s.is_moo)
    rem = moo_f.remaining()
    acq_f = fused._moo_acquisition(moo_f, posts_f[moo_f.rid], rem)
    acq_l = loop._moo_acquisition(moo_f, posts_f[moo_f.rid], rem)
    np.testing.assert_allclose(acq_f, acq_l, atol=1e-4)


def test_service_mixed_so_moo_cohort_deterministic():
    """Acceptance: a mixed single-objective + MOO multi-tenant cohort on
    the fake executor is bit-for-bit deterministic across runs."""
    def run_once():
        latency = {0: 2, 1: 1, 2: 3, 3: 1}
        svc = SearchService(
            _support_repo(), slots=4,
            executor=FakeProfileExecutor(lambda j: latency[j.rid]),
            wait_mode="any")
        svc.submit(_request(0, method="karasu", max_iters=5))
        svc.submit(_request(1, method="naive", max_iters=5))
        svc.submit(_moo_request(2, method="karasu"))
        svc.submit(_moo_request(3, method="naive"))
        return {c.rid: c.result for c in svc.run()}

    a, b = run_once(), run_once()
    assert sorted(a) == sorted(b) == [0, 1, 2, 3]
    for rid in a:
        assert (_result_fingerprint(a[rid])
                == _result_fingerprint(b[rid])), rid
    # MOO results carry their Pareto front
    for rid in (2, 3):
        assert a[rid].meta["moo"] is True
        front = a[rid].meta["pareto_front"]
        assert front.ndim == 2 and front.shape[1] == 2 and len(front) >= 1
        np.testing.assert_array_equal(front, b[rid].meta["pareto_front"])


def test_service_step_fuses_sample_draws():
    """All RGPE support-sample draws and MOO EHVI draws of a step ride
    the sample query plan: sample_batches counts fused launches, far
    fewer than the (tenant, measure/objective) draws they carry."""
    repo = _support_repo()
    svc = SearchService(repo, slots=4)
    for s in range(2):
        svc.submit(_request(s, method="karasu", max_iters=6))
    for s in range(2):
        svc.submit(_moo_request(10 + s, method="karasu", max_iters=6))
    done = svc.run()
    assert len(done) == 4
    assert svc.stats["sample_batches"] >= 1
    assert svc.stats["sample_queries"] > svc.stats["sample_batches"]
    # both MOO sessions' EHVI staircases shared vmapped launches
    assert svc.stats["ehvi_jobs"] > svc.stats["ehvi_batches"] >= 1

    # the loop baseline never enters the plan
    svc_l = SearchService(_support_repo(), slots=2, fuse_samples=False)
    for s in range(2):
        svc_l.submit(_request(s, method="karasu", max_iters=5))
    svc_l.run()
    assert svc_l.stats["sample_batches"] == 0
    assert svc_l.stats["ehvi_batches"] == 0


def test_service_fused_samples_match_loop():
    """Acceptance: fuse_samples=True (fused RGPE draws + vmapped EHVI)
    agrees with the per-job/per-session loop baseline to 1e-4 — same
    PRNG streams, so RGPE weights are identical and EHVI differs only
    by f32-vs-f64 roundoff."""
    def build(fuse):
        svc = SearchService(_support_repo(), slots=4, fuse_samples=fuse)
        for s in range(2):
            svc.submit(_request(s, method="karasu"))
        svc.submit(_moo_request(7, method="karasu"))
        svc.step()
        return svc

    fused, loop = build(True), build(False)
    s_f = [fused.active[r] for r in sorted(fused.active)]
    s_l = [loop.active[r] for r in sorted(loop.active)]
    for a, b in zip(s_f, s_l):
        assert [o.config for o in a.observations] == \
            [o.config for o in b.observations]
    posts_f = fused._posterior_phase(s_f)
    posts_l = loop._posterior_phase(s_l)
    assert fused.stats["sample_batches"] >= 1
    assert loop.stats["sample_batches"] == 0
    for a in s_f:
        for m in a.measures:
            if "weights" in posts_f[a.rid][m]:
                np.testing.assert_allclose(
                    posts_f[a.rid][m]["weights"],
                    posts_l[a.rid][m]["weights"], atol=1e-4)
            np.testing.assert_allclose(
                np.asarray(posts_f[a.rid][m]["mu"]),
                np.asarray(posts_l[a.rid][m]["mu"]), atol=1e-4)
    moo_f = next(s for s in s_f if s.is_moo)
    moo_l = next(s for s in s_l if s.is_moo)
    rem = moo_f.remaining()
    acq_f = fused._moo_phase([(moo_f, rem)], posts_f)[moo_f.rid]
    acq_l = loop._moo_phase([(moo_l, rem)], posts_l)[moo_l.rid]
    scale = max(1.0, float(np.abs(acq_l).max()))
    np.testing.assert_allclose(acq_f, acq_l, atol=1e-4 * scale)


def test_service_three_objective_session_end_to_end():
    """Acceptance: a 3-objective session runs end to end through the
    service — (k, 3) Pareto front, EHVI fused-vs-oracle parity <= 1e-4
    (the loop baseline for n >= 3 IS the recursive-sweep f64 oracle
    mc_ehvi_nd), and bit-for-bit determinism across runs."""
    def _req3(seed, **kw):
        return SearchRequest(
            SPACE, lambda c: EMU.run(WID, c, rng=None), None,
            [Constraint("runtime", RT)], method="karasu",
            bo_config=BOConfig(max_iters=5), seed=seed,
            objectives=[Objective("cost"), Objective("energy"),
                        Objective("runtime")], n_mc=8, **kw)

    def build(fuse):
        svc = SearchService(_support_repo(), slots=2, fuse_samples=fuse)
        svc.submit(_req3(0))
        svc.submit(_request(1, method="karasu"))
        svc.step()
        return svc

    fused, loop = build(True), build(False)
    s_f = [fused.active[r] for r in sorted(fused.active)]
    s_l = [loop.active[r] for r in sorted(loop.active)]
    for a, b in zip(s_f, s_l):
        assert [o.config for o in a.observations] == \
            [o.config for o in b.observations]
    posts_f = fused._posterior_phase(s_f)
    posts_l = loop._posterior_phase(s_l)
    moo_f = next(s for s in s_f if s.is_moo)
    moo_l = next(s for s in s_l if s.is_moo)
    rem = moo_f.remaining()
    acq_f = fused._moo_phase([(moo_f, rem)], posts_f)[moo_f.rid]
    acq_l = loop._moo_phase([(moo_l, rem)], posts_l)[moo_l.rid]
    scale = max(1.0, float(np.abs(acq_l).max()))
    np.testing.assert_allclose(acq_f, acq_l, atol=1e-4 * scale)
    assert fused.stats["ehvi_batches"] >= 1

    # end to end: completes, carries a 3-column front, deterministic
    def run_once():
        svc = SearchService(_support_repo(), slots=2)
        svc.submit(_req3(0))
        svc.submit(_request(1, method="karasu", max_iters=5))
        return {c.rid: c.result for c in svc.run()}

    a, b = run_once(), run_once()
    assert sorted(a) == [0, 1]
    front = a[0].meta["pareto_front"]
    assert front.ndim == 2 and front.shape[1] == 3 and len(front) >= 1
    for rid in a:
        assert (_result_fingerprint(a[rid])
                == _result_fingerprint(b[rid])), rid
    np.testing.assert_array_equal(front, b[0].meta["pareto_front"])


# -- process-pool profiling -------------------------------------------------

# forkserver: workers descend from a clean exec'd server process, not a
# fork of this (JAX-threaded) one — no inherited locks to deadlock on.
# Workers are long-lived, so the one-time import cost amortises.
import multiprocessing

MP_CTX = multiprocessing.get_context("forkserver")


def _pp_profile(config):
    """Module-level (picklable) noise-free profile fn for the process
    pool: workers resolve it by qualified name."""
    return EMU.run(WID, config, rng=None)


def _pp_boom(config):
    raise RuntimeError("cluster fell over")


def test_process_pool_executor_matches_sync_service():
    """Profiling on a process pool must complete every tenant with the
    exact per-session trajectories of the synchronous service — jobs,
    outcomes, and the profile_fn all cross the pickle boundary."""
    n = 2
    exe = ProcessPoolProfileExecutor(max_workers=n, mp_context=MP_CTX)
    svc = SearchService(Repository(), slots=n, executor=exe)
    for s in range(n):
        svc.submit(SearchRequest(SPACE, _pp_profile, Objective("cost"),
                                 [Constraint("runtime", RT)],
                                 bo_config=BOConfig(max_iters=4), seed=s))
    done = {c.rid: c.result for c in svc.run()}
    svc.close()
    assert sorted(done) == list(range(n))

    sync_svc = SearchService(Repository(), slots=n)
    for s in range(n):
        sync_svc.submit(SearchRequest(SPACE, _pp_profile,
                                      Objective("cost"),
                                      [Constraint("runtime", RT)],
                                      bo_config=BOConfig(max_iters=4),
                                      seed=s))
    sync_done = {c.rid: c.result for c in sync_svc.run()}
    for rid in done:
        assert (_result_fingerprint(done[rid])
                == _result_fingerprint(sync_done[rid])), rid


def test_process_pool_executor_error_propagates():
    """A profiler exception in the worker process is pickled back onto
    the outcome and re-raised by the service, which settles (not
    wedges) the session — same contract as every other backend."""
    exe = ProcessPoolProfileExecutor(max_workers=1, mp_context=MP_CTX)
    svc = SearchService(Repository(), slots=1, executor=exe)
    svc.submit(SearchRequest(SPACE, _pp_boom, Objective("cost"), [],
                             bo_config=BOConfig(max_iters=4), seed=0))
    with pytest.raises(RuntimeError, match="cluster fell over"):
        svc.run()
    # the remaining init runs are still in flight (async backend): each
    # raises as it lands, and the session settles once all are absorbed
    for _ in range(10):
        if not (svc.executor.pending()
                or any(s.inflight for s in svc.active.values())):
            break
        with pytest.raises(RuntimeError, match="cluster fell over"):
            svc.step()
    assert all(s.inflight == 0 for s in svc.active.values())
    svc.close()


def test_process_pool_executor_drain_and_order():
    """poll/collect/drain semantics on the process pool: outcomes come
    back in submission order among the completed set."""
    exe = ProcessPoolProfileExecutor(max_workers=2, mp_context=MP_CTX)
    try:
        for ci in range(3):
            exe.submit(ProfileJob(0, ci, SPACE.configs[ci], "init", ci),
                       _pp_profile)
        outs = exe.drain(timeout=60)
        assert exe.pending() == 0
        assert [o.job.seq for o in outs] == [0, 1, 2]
        assert all(o.error is None and o.measures for o in outs)
    finally:
        exe.shutdown()


def test_prng_key_schedule_collision_free():
    """Regression for the arithmetic key tags (1000 + it*10 + oi): every
    (purpose, iteration, index) must derive a distinct key, and the two
    purposes' subtrees must never overlap for any (it, index) pair."""
    from repro.core.bo import (KEY_PURPOSE_MOO_EHVI, KEY_PURPOSE_RGPE,
                               derive_key)
    import jax
    base = jax.random.PRNGKey(42)
    seen = set()
    for purpose in (KEY_PURPOSE_RGPE, KEY_PURPOSE_MOO_EHVI):
        for it in range(25):
            for idx in range(10):
                k = tuple(np.asarray(
                    jax.random.key_data(derive_key(base, purpose, it, idx))
                ).ravel().tolist())
                assert k not in seen, (purpose, it, idx)
                seen.add(k)
    assert len(seen) == 2 * 25 * 10


@pytest.mark.parametrize("seed", [0, 42, 2**31 + 5, 3100001301])
def test_derive_keys_match_derive_key_bitwise(seed):
    """The step's RGPE keys come from ONE batched launch, read back as
    host uint32 data: every row equals the per-call ``derive_key`` bit
    for bit (ragged iterations and measure indices, across a padded
    row count), and a raw host key splits exactly like the device
    key it came from."""
    import jax

    from repro.core.bo import KEY_PURPOSE_RGPE, derive_key, derive_keys
    bases = [jax.random.PRNGKey(seed + t) for t in range(3)]
    rows = [(t, it, mi) for t in range(3) for it in (1, 7, 19)
            for mi in range(t + 1)]
    keys = derive_keys(np.stack([np.asarray(bases[t]) for t, _, _ in rows]),
                       KEY_PURPOSE_RGPE, [it for _, it, _ in rows],
                       [mi for _, _, mi in rows])
    assert isinstance(keys, np.ndarray) and keys.dtype == np.uint32
    for key, (t, it, mi) in zip(keys, rows):
        ref = derive_key(bases[t], KEY_PURPOSE_RGPE, it, mi)
        np.testing.assert_array_equal(key, np.asarray(ref))
        np.testing.assert_array_equal(np.asarray(jax.random.split(key, 4)),
                                      np.asarray(jax.random.split(ref, 4)))


def _per_tenant_rgpe_jobs(svc, groups, tgts, owners):
    """The select phase one tenant at a time, as it ran before the
    step-wide batching: a candidate-index query, then per measure a
    support stack, a device ``extract`` of the target and a
    ``derive_key``."""
    from repro.core.bo import KEY_PURPOSE_RGPE, _target_runs, derive_key
    from repro.core.rgpe import WeightJob
    jobs = []
    for gk, group in groups.items():
        for s in group:
            if s.req.method != "karasu":
                continue
            ctx = svc.context_for(s)
            exclude = (s.req.share_as,) if s.req.share_as else None
            selected = ctx.candidate_index().query(
                _target_runs(s.observations), s.cfg.n_support,
                impl=s.cfg.kernel_impl, exclude=exclude)
            s.meta["selected"].append([z for z, _ in selected])
            if not selected:
                continue
            it = len(s.observations)
            job_of = {m: ji for ji, (o, m) in enumerate(owners[gk])
                      if o is s}
            for mi, m in enumerate(s.measures):
                bases, _ = ctx.store.get_stacked([z for z, _ in selected],
                                                 m)
                if bases is None:
                    continue
                key = derive_key(s.key, KEY_PURPOSE_RGPE, it, mi)
                jobs.append((s, m, bases,
                             WeightJob(bases, tgts[gk].extract(job_of[m]),
                                       key, s.cfg.rgpe_samples)))
    return jobs


def test_step_wide_select_matches_per_tenant_loop():
    """A small cohort with ragged observation counts (n_init 1, 2, 3),
    run through ``precompile`` and 3 steps: the step-wide select (one
    Pearson launch, host target slices, one key launch) gives the same
    ``meta["selected"]``, the same job inputs bit for bit, the same RGPE
    weights and the same decisions as the per-tenant loop — and no
    tracked launch compiles after ``precompile`` but the support fits."""
    import dataclasses

    from repro.core.plan import CohortLimits

    space = dataclasses.replace(SPACE, name="scout-mini",
                                configs=SPACE.configs[:8])

    def repo():
        r = Repository()
        rng = np.random.default_rng(11)
        for u, wid in enumerate((WID, WIDS[2], WIDS[9])):
            for ci in rng.choice(len(space), 6, replace=False):
                r.add_run(EMU.make_record(f"anon-{u}", wid,
                                          space.configs[ci], rng))
        return r

    def service():
        svc = SearchService(repo(), slots=4)
        for t in range(4):
            cfg = BOConfig(n_init=1 + t % 3, max_iters=8, n_support=2,
                           rgpe_samples=32)
            svc.submit(SearchRequest(
                space, lambda c: EMU.run(WID, c, rng=None),
                Objective("cost"), [Constraint("runtime", RT)],
                method="naive" if t == 3 else "karasu", bo_config=cfg,
                seed=70 + t))
        log = []
        score = svc._score_weights

        def scored(jobs):
            ws = score(jobs)
            log.append([(s.rid, m, job, np.asarray(ws[i]))
                        for i, (s, m, _b, job) in enumerate(jobs)])
            return ws
        svc._score_weights = scored
        return svc, log

    svc, log = service()
    svc.precompile(CohortLimits(d=space.all_encoded().shape[1], q_grid=8,
                                max_obs=8, max_lanes=32, n_samples=(32,)))
    watch = CompileWatcher()
    for _ in range(3):
        svc.step()
    compiled = watch.delta()
    compiled.pop("support_fit", 0)
    assert compiled == {}
    assert svc.stats["select_pearson_launches"] == 3
    assert svc.stats["select_tenants"] == 9
    assert svc.stats["support_stack_misses"] > 0

    ref, ref_log = service()
    ref._rgpe_jobs = lambda groups, tgts, owners: _per_tenant_rgpe_jobs(
        ref, groups, tgts, owners)
    for _ in range(3):
        ref.step()
    assert ref.stats["select_pearson_launches"] == 0

    assert len(log) == len(ref_log) == 3
    assert sum(len(step) for step in log) > 0
    for step, ref_step in zip(log, ref_log):
        assert [(rid, m) for rid, m, *_ in step] == \
            [(rid, m) for rid, m, *_ in ref_step]
        for (_, _, job, w), (_, _, rjob, rw) in zip(step, ref_step):
            np.testing.assert_array_equal(job.key, np.asarray(rjob.key))
            for f in ("x", "y_raw", "y", "y_mean", "y_std", "chol",
                      "alpha"):
                np.testing.assert_array_equal(
                    getattr(job.target, f),
                    np.asarray(getattr(rjob.target, f)))
            for f in ("log_lengthscales", "log_signal"):
                np.testing.assert_array_equal(
                    getattr(job.target.params, f),
                    np.asarray(getattr(rjob.target.params, f)))
            np.testing.assert_array_equal(w, rw)
    assert set(svc.active) == set(ref.active) == {0, 1, 2, 3}
    for rid, s in svc.active.items():
        r = ref.active[rid]
        assert s.meta["selected"] == r.meta["selected"]
        assert [o.config for o in s.observations] == \
            [o.config for o in r.observations]


def test_host_rows_match_device_loop_after_precompile():
    """A small cohort of single-objective and two-objective karasu
    tenants, over several steps after ``precompile``: the host-row
    posteriors (numpy, RGPE-mixed in numpy) equal the
    ``fuse_posteriors=False`` loop (device rows, mixed as jax ops)
    within float32 roundoff, the launched configurations are the same,
    no tracked launch compiles in the window, and the host-unpack
    counters follow the executed plans: the posterior lanes plus one
    row per draw and EHVI query, 2 reads per posterior launch and 1 per
    draw or EHVI launch."""
    import dataclasses

    from repro.core.plan import CohortLimits, PlanExecutor

    space = dataclasses.replace(SPACE, name="scout-mini",
                                configs=SPACE.configs[:12])

    class Recording(PlanExecutor):
        def execute(self, plan, **kw):
            for b in plan.buckets:
                qs = [plan.queries[i] for i in b.indices]
                if b.kind == "posterior":
                    self.rows += sum(q.stack.m for q in qs)
                    self.reads += 2
                elif b.kind in ("draw", "ehvi"):
                    self.rows += len(qs)
                    self.reads += 1
            return super().execute(plan, **kw)

    def service(fuse):
        repo = Repository()
        rng = np.random.default_rng(11)
        # as many support workloads as n_support: every support model is
        # fitted in the first step, so none compiles in the window
        for u, wid in enumerate((WID, WIDS[2])):
            for ci in rng.choice(len(space), 8, replace=False):
                repo.add_run(EMU.make_record(f"anon-{u}", wid,
                                             space.configs[ci], rng))
        ex = Recording()
        ex.rows = ex.reads = 0
        svc = SearchService(repo, slots=4, fuse_posteriors=fuse,
                            plan_executor=ex)
        for t in range(4):
            kw = (dict(objective=Objective("cost"),
                       constraints=[Constraint("runtime", RT)]) if t < 2
                  else dict(objectives=[Objective("cost"),
                                        Objective("energy")], n_mc=8))
            svc.submit(SearchRequest(
                space, lambda c: EMU.run(WID, c, rng=None),
                method="karasu", seed=40 + t,
                bo_config=BOConfig(n_init=2, max_iters=8, n_support=2,
                                   rgpe_samples=32), **kw))
        log = []
        phase = svc._posterior_phase

        def recorded(sessions):
            posts = phase(sessions)
            log.append(posts)
            return posts
        svc._posterior_phase = recorded
        return svc, log

    host, host_log = service(True)
    loop, loop_log = service(False)
    host.precompile(CohortLimits(
        d=space.all_encoded().shape[1], q_grid=12, max_obs=8,
        max_lanes=32, n_samples=(32,), n_mc=(8,), n_objectives=(2,),
        max_ehvi_boxes=16))
    host.step()
    loop.step()
    ex = host.plan_executor
    rows0, reads0 = ex.rows, ex.reads
    stats0 = dict(host.stats)
    watch = CompileWatcher()
    for _ in range(3):
        host.step()
        loop.step()
    assert watch.delta() == {}
    assert host.stats["host_unpack_rows"] - stats0["host_unpack_rows"] \
        == ex.rows - rows0 > 0
    assert host.stats["host_unpack_reads"] - stats0["host_unpack_reads"] \
        == ex.reads - reads0 > 0

    assert len(host_log) == len(loop_log) == 4
    mixed = 0
    for posts_h, posts_l in zip(host_log, loop_log):
        assert posts_h.keys() == posts_l.keys()
        for rid in posts_h:
            for m, p in posts_h[rid].items():
                q = posts_l[rid][m]
                mixed += "weights" in p
                assert ("weights" in p) == ("weights" in q)
                for f in ("mu", "var"):
                    assert isinstance(p[f], np.ndarray)
                    assert p[f].dtype == np.float32
                    np.testing.assert_allclose(p[f], np.asarray(q[f]),
                                               rtol=1e-6)
                for f in ("y_mean", "y_std"):
                    assert float(p[f]) == float(q[f])
    assert mixed > 0
    assert host.active.keys() == loop.active.keys() == {0, 1, 2, 3}
    for rid, s in host.active.items():
        assert [o.config for o in s.observations] == \
            [o.config for o in loop.active[rid].observations]


def test_mix_weighted_same_rows_on_numpy_and_jax():
    """``mix_weighted`` evaluates its one formula in the array module
    of its rows: numpy rows mix on the host in float32, device rows as
    jax ops, and both give the same rows."""
    import jax.numpy as jnp

    from repro.core.rgpe import mix_weighted
    rng = np.random.default_rng(5)
    mu_b = rng.normal(size=(3, 69)).astype(np.float32)
    var_b = rng.uniform(1e-12, 2.0, (3, 69)).astype(np.float32)
    mu_t = rng.normal(size=69).astype(np.float32)
    var_t = rng.uniform(1e-12, 2.0, 69).astype(np.float32)
    var_t[:4] = 0.0                      # the variance floor engages
    w = rng.dirichlet(np.ones(4)).astype(np.float32)
    w[-1] = 1e-4
    host = mix_weighted(mu_b, var_b, mu_t, var_t, jnp.asarray(w))
    dev = mix_weighted(*(jnp.asarray(a)
                         for a in (mu_b, var_b, mu_t, var_t, w)))
    for h, d in zip(host, dev):
        assert isinstance(h, np.ndarray) and h.dtype == np.float32
        assert isinstance(d, jax.Array)
        np.testing.assert_allclose(h, np.asarray(d), rtol=1e-6)
    assert host[1][:4].min() >= np.float32(1e-10)


def test_prng_consumers_bitwise_deterministic():
    """Bit-for-bit determinism across BOTH derived-key consumers (RGPE
    support draws and MOO EHVI draws) on the fake executor: a karasu
    MOO tenant exercises RGPE and EHVI keys every scoring step, and two
    runs must produce identical trajectories and Pareto fronts."""
    def run_once():
        svc = SearchService(
            _support_repo(), slots=2,
            executor=FakeProfileExecutor(lambda j: 1 + j.rid),
            wait_mode="any")
        svc.submit(_moo_request(3, method="karasu", max_iters=6))
        svc.submit(_request(4, method="karasu", max_iters=6))
        done = {c.rid: c.result for c in svc.run()}
        assert svc.stats["rgpe_jobs"] > 0 and svc.stats["ehvi_jobs"] > 0
        return done

    a, b = run_once(), run_once()
    for rid in a:
        assert (_result_fingerprint(a[rid])
                == _result_fingerprint(b[rid])), rid
    np.testing.assert_array_equal(a[0].meta["pareto_front"],
                                  b[0].meta["pareto_front"])


def test_run_search_moo_routes_through_service():
    """run_search_moo is a thin driver over SearchService: one slot,
    sync executor, identical trajectory to an explicit submission."""
    rng = np.random.default_rng(0)
    r = run_search_moo(SPACE, lambda c: EMU.run(WID, c, rng=rng),
                       [Objective("cost"), Objective("energy")],
                       [Constraint("runtime", RT)], method="naive",
                       bo_config=BOConfig(max_iters=6), seed=3, n_mc=16)
    assert len(r.observations) == 6
    assert r.meta["moo"] is True and r.meta["n_profiled"] == 6

    rng = np.random.default_rng(0)
    svc = SearchService(slots=1)
    svc.submit(SearchRequest(
        SPACE, lambda c: EMU.run(WID, c, rng=rng), None,
        [Constraint("runtime", RT)], method="naive",
        bo_config=BOConfig(max_iters=6), seed=3,
        objectives=[Objective("cost"), Objective("energy")], n_mc=16))
    (c,) = svc.run()
    assert _result_fingerprint(c.result) == _result_fingerprint(r)


# -- compile-once steady state -----------------------------------------------


def test_precompile_zero_recompile_under_mixed_tenant_churn():
    """200 scheduling steps of a churning SO + 2-objective +
    3-objective cohort after an AOT bucket precompile: every planned
    launch signature lands in the precompiled vocabulary and no plan
    launch recompiles (``plan_compile_misses`` counts only the support
    fits that the published runs force)."""
    import dataclasses

    from repro.core.plan import CohortLimits, StepPlanner

    class RecordingPlanner(StepPlanner):
        def __init__(self):
            super().__init__()
            self.signatures = set()

        def plan(self, queries):
            p = super().plan(queries)
            for b in p.buckets:
                if b.kind != "draw":        # unjitted, no vocabulary
                    self.signatures.add(self.launch_signature(b))
            return p

    space = dataclasses.replace(SPACE, name="scout-mini",
                                configs=SPACE.configs[:8])
    repo = Repository()
    rng = np.random.default_rng(5)
    for u in range(2):
        for ci in rng.choice(len(space), 6, replace=False):
            repo.add_run(EMU.make_record(f"anon-{u}", WID,
                                         space.configs[ci], rng))
    planner = RecordingPlanner()
    svc = SearchService(repo, slots=3, planner=planner)
    # lane bound: 8 target lanes (sum of the cohort's measures) plus
    # 8 RGPE jobs x up to 3 support bases fused into the same buckets
    limits = CohortLimits(d=space.all_encoded().shape[1], q_grid=8,
                          max_obs=8, max_lanes=32, n_samples=(32,),
                          n_mc=(8,), n_objectives=(2, 3),
                          max_ehvi_boxes=256)
    pre = svc.precompile(limits)
    assert pre["buckets"] == len(svc.precompiled_signatures)
    assert svc.stats["precompiled_buckets"] == pre["buckets"]
    assert svc.stats["precompile_compiles"] == pre["compiles"]

    cfg = BOConfig(n_init=2, max_iters=5, rgpe_samples=32)
    cons = [Constraint("runtime", EMU.runtime_target(WID, 50))]

    def submit(i):
        runner = lambda c: EMU.run(WID, c, rng=None)
        if i % 3 == 0:
            svc.submit(SearchRequest(
                space, runner, Objective("cost"), cons, method="karasu",
                bo_config=cfg, seed=100 + i,
                share_as="tenant-0" if i == 0 else None))
        elif i % 3 == 1:
            svc.submit(SearchRequest(
                space, runner, None, cons, method="karasu",
                bo_config=cfg, seed=100 + i,
                objectives=[Objective("cost"), Objective("energy")],
                n_mc=8))
        else:
            svc.submit(SearchRequest(
                space, runner, None, (), method="karasu",
                bo_config=cfg, seed=100 + i,
                objectives=[Objective("cost"), Objective("energy"),
                            Objective("runtime")], n_mc=8))

    submitted = 0
    watch = CompileWatcher()
    for _ in range(200):
        while len(svc.active) + len(svc.queue) < 3:
            submit(submitted)
            submitted += 1
        svc.step()
    assert svc.stats["steps"] == 200
    # churn actually happened: tenants retired and were replaced
    assert len(svc.done) >= 10
    # every planned launch came from the precompiled vocabulary...
    assert {"posterior", "sample", "loo", "ehvi", "fit"} <= \
        {sig[0] for sig in planner.signatures}
    assert planner.signatures <= svc.precompiled_signatures
    # ...and no plan launch compiled while serving: the misses are the
    # support fits alone (tenant-0 publishes, so support histories
    # grow; the unpadded support fit is outside the vocabulary)
    compiled = watch.delta()
    support = compiled.pop("support_fit", 0)
    assert compiled == {}
    assert svc.stats["plan_compile_misses"] == support


def test_fused_ehvi_service_matches_default_executor():
    """A fused-EHVI executor must serve bitwise-identical MOO
    trajectories to the default vmapped executor: the EHVI queries carry
    posterior rows + PRNG keys instead of materialised draws, and the
    kernel applies the exact same derive_key/affine recipe — so the
    only visible difference is the eliminated draw round."""
    from repro.core.plan import PlanExecutor

    def run(executor):
        svc = SearchService(Repository(), slots=3, plan_executor=executor)
        for s in range(3):
            svc.submit(_moo_request(20 + s, method="naive"))
        done = {c.rid: c.result for c in svc.run()}
        return svc, done

    base_svc, base = run(PlanExecutor(donate=False))
    fused_svc, fused = run(PlanExecutor(fused_ehvi=True, impl="xla",
                                        donate=False))
    assert base.keys() == fused.keys()
    for rid in base:
        assert _result_fingerprint(base[rid]) == \
            _result_fingerprint(fused[rid])
    # the fused path consumes posterior rows directly: no separate
    # draw launches, fewer plan rounds, same ehvi bucket accounting
    assert base_svc.stats["sample_batches"] > 0
    assert fused_svc.stats["sample_batches"] == 0
    assert fused_svc.stats["plan_batches"] < base_svc.stats["plan_batches"]
    assert fused_svc.stats["ehvi_batches"] == base_svc.stats["ehvi_batches"]


def test_precompile_zero_recompile_fused_donated_executor():
    """The churn guarantee must survive the fused + donated executor:
    precompile walks the same donate/fused launch choices the serving
    path makes (the donated twins are pinned at executor construction,
    not resolved per call), so a mixed SO + MOO cohort still hits only
    precompiled signatures and no plan launch recompiles."""
    import dataclasses

    from repro.core.plan import CohortLimits, PlanExecutor, StepPlanner

    class RecordingPlanner(StepPlanner):
        def __init__(self):
            super().__init__()
            self.signatures = set()

        def plan(self, queries):
            p = super().plan(queries)
            for b in p.buckets:
                if b.kind != "draw":
                    self.signatures.add(self.launch_signature(b))
            return p

    space = dataclasses.replace(SPACE, name="scout-mini",
                                configs=SPACE.configs[:8])
    repo = Repository()
    rng = np.random.default_rng(5)
    for u in range(2):
        for ci in rng.choice(len(space), 6, replace=False):
            repo.add_run(EMU.make_record(f"anon-{u}", WID,
                                         space.configs[ci], rng))
    planner = RecordingPlanner()
    executor = PlanExecutor(fused_posterior=True, fused_ehvi=True,
                            donate=True, impl="xla")
    svc = SearchService(repo, slots=3, planner=planner,
                        plan_executor=executor)
    limits = CohortLimits(d=space.all_encoded().shape[1], q_grid=8,
                          max_obs=8, max_lanes=32, n_samples=(32,),
                          n_mc=(8,), n_objectives=(2, 3),
                          max_ehvi_boxes=256)
    svc.precompile(limits)

    cfg = BOConfig(n_init=2, max_iters=5, rgpe_samples=32)
    cons = [Constraint("runtime", EMU.runtime_target(WID, 50))]

    def submit(i):
        runner = lambda c: EMU.run(WID, c, rng=None)
        if i % 3 == 0:
            svc.submit(SearchRequest(
                space, runner, Objective("cost"), cons, method="karasu",
                bo_config=cfg, seed=100 + i))
        elif i % 3 == 1:
            svc.submit(SearchRequest(
                space, runner, None, cons, method="karasu",
                bo_config=cfg, seed=100 + i,
                objectives=[Objective("cost"), Objective("energy")],
                n_mc=8))
        else:
            svc.submit(SearchRequest(
                space, runner, None, (), method="karasu",
                bo_config=cfg, seed=100 + i,
                objectives=[Objective("cost"), Objective("energy"),
                            Objective("runtime")], n_mc=8))

    submitted = 0
    watch = CompileWatcher()
    for _ in range(120):
        while len(svc.active) + len(svc.queue) < 3:
            submit(submitted)
            submitted += 1
        svc.step()
    assert len(svc.done) >= 6
    assert planner.signatures <= svc.precompiled_signatures
    # no plan launch compiled; a support fit at a history length this
    # process has not fitted before is the only miss there can be
    compiled = watch.delta()
    support = compiled.pop("support_fit", 0)
    assert compiled == {}
    assert svc.stats["plan_compile_misses"] == support


def test_fit_leg_warm_and_cold_rungs_zero_recompile():
    """Warm (short-refine) and COLD (full-schedule) fit buckets serve
    in the SAME scheduling step without leaving the precompiled
    vocabulary: staggered tenant lifetimes put a fresh tenant's first
    fit (cold — no warm cache yet) alongside running tenants' warm
    refines, both rungs land in distinct precompiled buckets, and
    ``plan_compile_misses`` stays 0."""
    import dataclasses

    from repro.core.plan import CohortLimits, StepPlanner

    class RecordingPlanner(StepPlanner):
        def __init__(self):
            super().__init__()
            self.signatures = set()
            self.fit_rungs = []          # steps rungs per fit round

        def plan(self, queries):
            p = super().plan(queries)
            for b in p.buckets:
                if b.kind != "draw":
                    self.signatures.add(self.launch_signature(b))
            rungs = {b.key[1] for b in p.buckets if b.kind == "fit"}
            if rungs:
                self.fit_rungs.append(rungs)
            return p

    space = dataclasses.replace(SPACE, name="scout-mini",
                                configs=SPACE.configs[:8])
    planner = RecordingPlanner()
    svc = SearchService(Repository(), slots=2, planner=planner)
    limits = CohortLimits(d=space.all_encoded().shape[1], q_grid=8,
                          max_obs=8, max_lanes=8)
    svc.precompile(limits)

    def submit(i):
        rng = np.random.default_rng(i)
        svc.submit(SearchRequest(
            space, lambda c: EMU.run(WID, c, rng=rng),
            Objective("cost"), [Constraint("runtime", RT)],
            method="naive",
            bo_config=BOConfig(n_init=2, max_iters=4 + (i % 3)),
            seed=10 + i))

    submitted = 0
    for _ in range(40):
        while len(svc.active) + len(svc.queue) < 2:
            submit(submitted)
            submitted += 1
        svc.step()

    assert svc.stats["fit_warm_lanes"] > 0
    assert svc.stats["fit_cold_lanes"] > 0
    assert svc.stats["fit_fused_batches"] > 0
    # both rungs were planned, and at least one round carried BOTH at
    # once (a cold newcomer sharing the step with warm incumbents)
    rungs = {r for s in planner.fit_rungs for r in s}
    assert rungs == {svc.fit_warm_steps, svc.fit_steps}
    assert any(len(s) == 2 for s in planner.fit_rungs)
    fit_sigs = {s for s in planner.signatures if s[0] == "fit"}
    assert {dict(p for p in s if isinstance(p, tuple))["steps"]
            for s in fit_sigs} == rungs
    # vocabulary closed, zero serving-time compiles
    assert planner.signatures <= svc.precompiled_signatures
    assert svc.stats["plan_compile_misses"] == 0


def test_fit_warm_steps_disabled_runs_every_lane_cold():
    """``fit_warm_steps=None`` turns the warm cache off: every fit
    lane runs the full cold schedule and the warm counter stays 0."""
    svc = SearchService(Repository(), slots=2, fit_warm_steps=None)
    for s in range(2):
        svc.submit(_request(s, max_iters=4))
    svc.run()
    assert svc.stats["fit_cold_lanes"] > 0
    assert svc.stats["fit_warm_lanes"] == 0


def test_support_fit_at_a_new_history_length_counts_a_miss():
    """``plan_compile_misses`` counts support-model fits: once a
    collaborator's workload grows to a history length no support fit
    has had, the next step refits it, compiling ``support_fit`` — and
    nothing else — and the step counts that miss."""
    repo = _support_repo(runs=12)
    svc = SearchService(repo, slots=1)
    # a noise level no other test fits at: the support fit's compiles
    # here cannot have been built before
    svc.submit(SearchRequest(
        SPACE, lambda c: EMU.run(WID, c, rng=None), Objective("cost"),
        [Constraint("runtime", RT)], method="karasu",
        bo_config=BOConfig(max_iters=8, noise=0.1093), seed=4))
    svc.step()
    svc.step()                 # both fit rungs built
    rng = np.random.default_rng(5)
    have = {tuple(sorted(r.config.items()))
            for r in repo.runs("anon-0")}
    fresh = [ci for ci in range(len(SPACE))
             if tuple(sorted(SPACE.configs[ci].items())) not in have]
    for ci in fresh[:3]:       # 12 -> 15 runs: the same stack padding
        repo.add_run(EMU.make_record("anon-0", WID, SPACE.configs[ci],
                                     rng))
    misses = svc.stats["plan_compile_misses"]
    watch = CompileWatcher()
    svc.step()
    assert watch.delta() == {"support_fit": 1}
    assert svc.stats["plan_compile_misses"] - misses == 1
