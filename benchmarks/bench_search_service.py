"""Multi-tenant SearchService vs looped single-tenant run_search.

The ROADMAP's serving scenario: N users each run a Karasu search against
one shared repository. The baseline loops ``run_search`` per tenant
(each search refits every target and support GP in Python loops); the
service batches all tenants' target fits into one vmapped Cholesky per
step and shares one incremental support-model store.

Emits (CSV, benchmarks/run.py format):
  search_service_loop     — looped baseline, us per tenant-iteration
  search_service_batched  — SearchService,   us per tenant-iteration
  search_service_speedup  — derived = loop_wall / service_wall (~1.8x
                            since run_search adopted the jit-stable
                            batched fit; the >= 2.0 acceptance now
                            lives on search_service_async_speedup)

With ``--slow-profilers`` (or REPRO_BENCH_SLOW_PROFILERS=1) it instead
measures the async-profiling path: 8 tenants whose profile_fns carry
heterogeneous artificial latencies (100..800 ms), synchronous executor
vs thread pool. The synchronous service pays the SUM of the latencies every
round; the async service pays ~the MAX, because WAITING_PROFILE sessions
overlap their cluster runs while landed sessions keep fitting:
  search_service_sync_profilers   — us per tenant-iteration
  search_service_async_profilers  — us per tenant-iteration
  search_service_async_speedup    — derived (acceptance: >= 2.0)

With ``--moo`` it measures the fused posterior + sample query plans on
a mixed single-objective + multi-objective karasu cohort: the fused
service (one padded batched_posterior launch per step, fused RGPE
support-sample draws via batched_sample_multi, vmapped multi-session
MC-EHVI) vs the loop path (``fuse_posteriors=False, fuse_samples=False``
— per-ensemble posteriors, per-job sample draws, per-candidate EHVI
reference):
  search_service_moo_loop     — loop path,             us/tenant-iter
  search_service_moo_fused    — fused query plans,     us/tenant-iter
  search_service_moo_speedup  — derived (acceptance: >= 2.0 at 8 tenants)
  search_service_moo_sample_speedup — fused-samples-vs-sample-loop
                                contribution (posteriors fused in both)

With ``--smoke`` it runs a tiny mixed cohort (4 tenants: naive SO,
karasu SO, karasu 2-objective, karasu 3-objective; 4 iterations) end to
end — twice: the first pass compiles every launch shape, the repeat
must hit the compile-once steady state (``plan_compile_misses == 0``,
with the executor dispatching the fused EHVI bucket launch)
— and asserts completion AND that the query-plan layer actually
engaged (``plan_batches <= plan_queries`` with fusion on every leg:
posterior/sample/EHVI) — the CPU CI hook that fails fast when the
serving path regresses, instead of waiting for the weekly slow job.
``REPRO_BENCH_STATS_JSON=path`` (or ``--stats-json path``) additionally
dumps the service stats as JSON, which CI uploads as an artifact so
fusion regressions are diagnosable from the run page.

With ``--steady-state`` it measures the compile-once serving claim
directly: per-step latency of a churning mixed cohort served cold vs
after ``SearchService.precompile`` (asserting zero tracked recompiles
post-precompile), and the fused posterior+EI and fused
draw+EHVI bucket kernels vs the vmapped XLA chains:
  search_service_steady_cold_step / _warm_step  — us per service step
  search_service_precompile                     — one-time warmup cost
  search_service_steady_misses                  — must be 0
  fused_posterior_launch / _vs_vmapped_speedup
  fused_ehvi_launch / _vs_vmapped_speedup

With ``--mesh N`` (or REPRO_BENCH_MESH=N) it forces an N-device host
platform (``--xla_force_host_platform_device_count``, staged before jax
imports) and measures data-parallel serving: a 64-tenant karasu cohort
served warm on the single-device executor vs with every bucket's lane
axis sharded over the N-device ``("data",)`` mesh, asserting the warm
sharded pass holds ``plan_compile_misses == 0``:
  search_service_mesh1_step / _mesh<N>_step — us per service step
  search_service_mesh_scaling               — measured step-time ratio
  search_service_mesh_misses                — must be 0
  search_service_mesh*_fit_wall             — fit leg dispatch wall
``--mesh`` composes with ``--smoke``: the CI mesh leg runs the smoke
cohort through the sharded executor under REPRO_BENCH_MESH=4.
"""
from __future__ import annotations

import json
import os
import sys
import time


def _parse_mesh_argv() -> int:
    n = int(os.environ.get("REPRO_BENCH_MESH", "0") or 0)
    if "--mesh" in sys.argv[1:]:
        at = sys.argv.index("--mesh")
        if at + 1 >= len(sys.argv):
            raise SystemExit("--mesh needs a device-count argument")
        n = int(sys.argv[at + 1])
    return n


# --mesh N (or REPRO_BENCH_MESH=N) serves the cohort through the
# data-parallel plan executor on an N-device host platform. XLA reads
# --xla_force_host_platform_device_count once at backend init, so the
# flag must be staged into the environment HERE, before the repro
# imports below pull in jax (external XLA_FLAGS already forcing a
# device count are respected as-is).
MESH_N = _parse_mesh_argv()
if MESH_N > 1 and "jax" not in sys.modules:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            f"{_flags} --xla_force_host_platform_device_count={MESH_N}"
        ).strip()

import numpy as np

from repro.core import (BOConfig, Constraint, Objective, Repository,
                        run_search)
from repro.serve.profile_executor import (SyncProfileExecutor,
                                          ThreadPoolProfileExecutor)
from repro.serve.search_service import SearchRequest, SearchService

from . import common as C

N_TENANTS = {"ci": 8, "mid": 8, "full": 16}
MAX_ITERS = {"ci": 10, "mid": 12, "full": 20}

_MESH_CACHE: dict = {}


def _mesh():
    """The benchmark's data mesh: ``Mesh((MESH_N,), ("data",))`` when
    mesh mode is on, else None (single-device executor). Cached so every
    service of the run shares ONE mesh object — the sharded launch twins
    are cached per mesh, and repeat cohorts must re-enter the same jit
    caches for the compile-once assertions to hold."""
    if MESH_N <= 1:
        return None
    if "mesh" not in _MESH_CACHE:
        import jax
        if len(jax.devices()) < MESH_N:
            raise SystemExit(
                f"--mesh {MESH_N} needs {MESH_N} devices but the backend "
                f"has {len(jax.devices())} (is XLA_FLAGS= "
                f"--xla_force_host_platform_device_count set before jax "
                f"init?)")
        _MESH_CACHE["mesh"] = jax.make_mesh((MESH_N,), ("data",))
    return _MESH_CACHE["mesh"]


def _setup(n_tenants: int):
    emu = C.emulator()
    sp = C.space()
    wids = emu.workload_ids()
    tenants = [wids[i % len(wids)] for i in range(n_tenants)]
    # shared repository: uniformly profiled collaborator runs of the
    # tenants' workloads (case-D-like, 12 runs each)
    repo = C.random_profiled_repo(sorted(set(tenants)), 12, seed=7)
    targets = {w: emu.runtime_target(w, 50) for w in set(tenants)}
    return sp, tenants, repo, targets


def _fresh_repo(repo: Repository) -> Repository:
    # both paths mutate nothing, but rebuild anyway so neither inherits
    # the other's version counters
    out = Repository()
    for z, rs in repo.all_runs().items():
        out.add_runs(rs)
    return out


def _loop(sp, tenants, repo, targets, max_iters: int) -> float:
    t0 = time.time()
    for t, wid in enumerate(tenants):
        run_search(sp, C.profile_fn(wid, t), Objective("cost"),
                   [Constraint("runtime", targets[wid])], method="karasu",
                   repository=repo,
                   bo_config=BOConfig(max_iters=max_iters), seed=t)
    return time.time() - t0


def _service(sp, tenants, repo, targets, max_iters: int) -> float:
    t0 = time.time()
    svc = SearchService(repo, slots=len(tenants))
    for t, wid in enumerate(tenants):
        svc.submit(SearchRequest(sp, C.profile_fn(wid, t),
                                 Objective("cost"),
                                 [Constraint("runtime", targets[wid])],
                                 method="karasu",
                                 bo_config=BOConfig(max_iters=max_iters),
                                 seed=t))
    done = svc.run()
    assert len(done) == len(tenants)
    return time.time() - t0


def _slow_profile_fn(wid: str, seed: int, latency_s: float):
    # a fresh Generator per call, seeded from (workload, tenant, config):
    # the thread pool may run one tenant's init jobs concurrently, and
    # numpy Generators are not thread-safe — per-call seeding keeps the
    # draws deterministic no matter how the pool schedules them
    import zlib
    base = (zlib.crc32(wid.encode()) & 0xFFFF, seed)

    def fn(config):
        time.sleep(latency_s)      # stand-in for the cluster run
        rng = np.random.default_rng(
            base + (int(config["node_count"]),
                    zlib.crc32(str(config["machine_type"]).encode())))
        return C.emulator().run(wid, config, rng=rng)

    return fn


def _service_with_executor(sp, tenants, repo, targets, max_iters,
                           latencies, executor, wait_mode) -> float:
    svc = SearchService(repo, slots=len(tenants), executor=executor,
                        wait_mode=wait_mode)
    for t, wid in enumerate(tenants):
        svc.submit(SearchRequest(
            sp, _slow_profile_fn(wid, t, latencies[t]), Objective("cost"),
            [Constraint("runtime", targets[wid])], method="naive",
            bo_config=BOConfig(max_iters=max_iters), seed=t))
    t0 = time.time()
    done = svc.run()
    assert len(done) == len(tenants)
    svc.close()
    return time.time() - t0


def slow_profilers() -> None:
    """Async vs synchronous profiling at 8 tenants with heterogeneous
    profile latencies (the ISSUE-2 acceptance scenario).

    Real cluster bring-up takes minutes, so the profiling-bound regime
    is the honest one; we emulate it with 100..800 ms sleeps (an 8x
    spread, as between a smoke-test config and a many-node cluster
    bring-up). NaiveBO
    keeps the model math identical across tenants so the measurement
    isolates profiling overlap; karasu's extra fit work is the same in
    both paths and only dilutes the contrast."""
    n_tenants = 8
    max_iters = MAX_ITERS.get(C.SCALE, 10)
    sp, tenants, repo, targets = _setup(n_tenants)
    iters_total = n_tenants * max_iters
    latencies = [0.1 * (t + 1) for t in range(n_tenants)]

    # untimed jit warmup at the TIMED shapes (8 tenants -> 16-model pow2
    # bucket; 9 obs -> 16-obs round_to bucket) with zero latency, so
    # neither timed run is charged for one-time XLA compiles
    _service_with_executor(sp, tenants, _fresh_repo(repo), targets,
                           min(9, max_iters), [0.0] * n_tenants,
                           SyncProfileExecutor(), "any")

    sync_s = _service_with_executor(
        sp, tenants, _fresh_repo(repo), targets, max_iters, latencies,
        SyncProfileExecutor(), "any")
    async_s = _service_with_executor(
        sp, tenants, _fresh_repo(repo), targets, max_iters, latencies,
        ThreadPoolProfileExecutor(max_workers=n_tenants), "any")

    C.emit("search_service_sync_profilers", sync_s * 1e6 / iters_total,
           f"{n_tenants}tenants")
    C.emit("search_service_async_profilers", async_s * 1e6 / iters_total,
           f"{n_tenants}tenants")
    C.emit("search_service_async_speedup", 0.0,
           f"{sync_s / async_s:.2f}")


def _moo_mixed_requests(sp, tenants, targets, max_iters, *, n_mc=64):
    """Every other tenant is multi-objective (cost x energy under the
    runtime constraint); the rest single-objective. All karasu, so the
    fused plan carries targets AND support stacks for both kinds."""
    reqs = []
    for t, wid in enumerate(tenants):
        cons = [Constraint("runtime", targets[wid])]
        if t % 2 == 1:
            reqs.append(SearchRequest(
                sp, C.profile_fn(wid, t), None, cons, method="karasu",
                bo_config=BOConfig(max_iters=max_iters), seed=t,
                objectives=[Objective("cost"), Objective("energy")],
                n_mc=n_mc))
        else:
            reqs.append(SearchRequest(
                sp, C.profile_fn(wid, t), Objective("cost"), cons,
                method="karasu", bo_config=BOConfig(max_iters=max_iters),
                seed=t))
    return reqs


def _service_moo(sp, tenants, repo, targets, max_iters, *,
                 fuse: bool, fuse_samples=None) -> float:
    svc = SearchService(repo, slots=len(tenants), fuse_posteriors=fuse,
                        fuse_samples=(fuse if fuse_samples is None
                                      else fuse_samples))
    for req in _moo_mixed_requests(sp, tenants, targets, max_iters):
        svc.submit(req)
    t0 = time.time()
    done = svc.run()
    assert len(done) == len(tenants)
    return time.time() - t0


def moo_mixed() -> None:
    """Fused posterior + sample query plans vs the per-session loop on
    a mixed SO+MOO karasu cohort (the ISSUE-3/ISSUE-4 acceptance
    scenario)."""
    n_tenants = 8
    max_iters = MAX_ITERS.get(C.SCALE, 10)
    sp, tenants, repo, targets = _setup(n_tenants)
    iters_total = n_tenants * max_iters

    # untimed jit warmup at the timed shapes for every measured path —
    # FULL length: the sample plan's grid buckets track the growing
    # observation count, so a shorter warmup would charge the fused
    # path for late-step bucket compiles the loop never pays
    warm = max_iters
    _service_moo(sp, tenants, _fresh_repo(repo), targets, warm, fuse=True)
    _service_moo(sp, tenants, _fresh_repo(repo), targets, warm, fuse=False)
    _service_moo(sp, tenants, _fresh_repo(repo), targets, warm,
                 fuse=True, fuse_samples=False)

    loop_s = _service_moo(sp, tenants, _fresh_repo(repo), targets,
                          max_iters, fuse=False)
    fused_s = _service_moo(sp, tenants, _fresh_repo(repo), targets,
                           max_iters, fuse=True)
    # posterior plan fused in both; isolates the sample-draw fusion
    sloop_s = _service_moo(sp, tenants, _fresh_repo(repo), targets,
                           max_iters, fuse=True, fuse_samples=False)

    C.emit("search_service_moo_loop", loop_s * 1e6 / iters_total,
           f"{n_tenants}tenants")
    C.emit("search_service_moo_fused", fused_s * 1e6 / iters_total,
           f"{n_tenants}tenants")
    C.emit("search_service_moo_speedup", 0.0, f"{loop_s / fused_s:.2f}")
    C.emit("search_service_moo_sample_speedup", 0.0,
           f"{sloop_s / fused_s:.2f}")


def _smoke_cohort(sp, tenants, repo, targets, max_iters):
    """The 4-tenant mixed cohort smoke() measures, as a reusable run:
    returns (service, completions, elapsed seconds). The executor runs
    with ``fused_ehvi=True`` so the zero-recompile assertion covers the
    fused draw+EHVI bucket launch, not just the vmapped chain."""
    from repro.core.plan import PlanExecutor
    mesh = _mesh()
    svc = SearchService(repo, slots=4, mesh=mesh,
                        plan_executor=PlanExecutor(fused_ehvi=True,
                                                   mesh=mesh))
    wid0, wid1, wid2 = tenants[:3]
    svc.submit(SearchRequest(
        sp, C.profile_fn(wid0, 0), Objective("cost"),
        [Constraint("runtime", targets[wid0])], method="naive",
        bo_config=BOConfig(max_iters=max_iters), seed=0))
    svc.submit(SearchRequest(
        sp, C.profile_fn(wid1, 1), Objective("cost"),
        [Constraint("runtime", targets[wid1])], method="karasu",
        bo_config=BOConfig(max_iters=max_iters), seed=1))
    svc.submit(SearchRequest(
        sp, C.profile_fn(wid2, 2), None,
        [Constraint("runtime", targets[wid2])], method="karasu",
        bo_config=BOConfig(max_iters=max_iters), seed=2,
        objectives=[Objective("cost"), Objective("energy")], n_mc=8))
    # n=3 objectives: the box-decomposition EHVI plan node
    svc.submit(SearchRequest(
        sp, C.profile_fn(wid0, 3), None, [], method="karasu",
        bo_config=BOConfig(max_iters=max_iters), seed=3,
        objectives=[Objective("cost"), Objective("energy"),
                    Objective("runtime")], n_mc=8))
    t0 = time.time()
    done = {c.rid: c.result for c in svc.run()}
    return svc, done, time.time() - t0


def smoke() -> None:
    """CI smoke: a 4-tenant mixed cohort (naive SO, karasu SO, karasu
    2-objective, karasu 3-objective) over 5 iterations must complete,
    route its model math through the query-plan layer, and produce
    (k, 2) and (k, 3) Pareto fronts — fast enough for the tier-1 CPU
    job. Five iterations leave TWO model-driven steps past ``n_init``,
    so every model refits once and the second fit must ride the
    warm-start cache (``fit_warm_lanes > 0``). The cohort then runs a
    SECOND time against warm jit caches: the repeat must hit the
    compile-once steady state (``plan_compile_misses == 0``), which is
    the invariant CI asserts from the dumped stats JSON artifact."""
    sp, tenants, repo, targets = _setup(3)
    max_iters = 5
    cold_svc, done, _ = _smoke_cohort(sp, tenants, _fresh_repo(repo),
                                      targets, max_iters)
    svc, done2, dt = _smoke_cohort(sp, tenants, _fresh_repo(repo),
                                   targets, max_iters)
    assert sorted(done) == [0, 1, 2, 3], done
    assert sorted(done2) == [0, 1, 2, 3], done2
    done = done2
    # every tracked launch shape compiled in the first run; the repeat
    # cohort re-enters only precompiled buckets
    assert svc.stats["plan_compile_misses"] == 0, \
        (svc.stats["plan_compile_misses"], cold_svc.stats)
    for res in done.values():
        assert len(res.observations) == max_iters
    assert done[2].meta["moo"] is True
    assert len(done[2].meta["pareto_front"]) >= 1
    front3 = done[3].meta["pareto_front"]
    assert front3.ndim == 2 and front3.shape[1] == 3 and len(front3) >= 1
    # the query-plan layer must have engaged on every leg: far fewer
    # fused launches (plan_batches) than the query nodes they carried
    # (plan_queries), with per-kind fusion for posteriors, the RGPE/MOO
    # sample draws, and the EHVI evaluations
    s = svc.stats
    assert s["plan_batches"] >= 1, s
    assert s["plan_batches"] <= s["plan_queries"], s
    assert s["plan_batches"] == (s["posterior_batches"]
                                 + s["sample_batches"]
                                 + s["ehvi_batches"]
                                 + s["fit_batches"]), s
    assert s["posterior_batches"] < s["posterior_queries"], s
    assert s["sample_batches"] >= 1, s
    assert s["sample_queries"] > s["sample_batches"], s
    assert s["ehvi_batches"] >= 1, s
    # the fit leg rode the plan and its warm cache engaged: after each
    # measure's first (cold) fit every refit takes the short warm rung
    assert s["fit_batches"] >= 1, s
    assert s["fit_warm_lanes"] > 0, s
    assert s["fit_cold_lanes"] > 0, s
    stats_path = os.environ.get("REPRO_BENCH_STATS_JSON")
    if "--stats-json" in sys.argv[1:]:
        at = sys.argv.index("--stats-json")
        if at + 1 >= len(sys.argv):
            raise SystemExit("--stats-json needs a path argument")
        stats_path = sys.argv[at + 1]
    if stats_path:
        with open(stats_path, "w") as f:
            json.dump({**s, "elapsed_s": dt, "tenants": 4,
                       "max_iters": max_iters,
                       "cold_plan_compile_misses":
                           cold_svc.stats["plan_compile_misses"]},
                      f, indent=2)
    C.emit("search_service_smoke", dt * 1e6 / (4 * max_iters), "ok")


def _fused_kernel_numbers() -> None:
    """The fused posterior+EI bucket kernel vs the vmapped-XLA chain it
    replaces (one launch vs posterior launch + eager EI)."""
    import jax.numpy as jnp

    from repro.core.acquisition import expected_improvement
    from repro.core.gp import _batched_posterior
    from repro.kernels.fused_posterior.ops import _fused_posterior_launch

    m, n, q, d = 16, 64, 512, 7
    rng = np.random.default_rng(0)
    ls = jnp.asarray(rng.normal(0.0, 0.1, (m, d)), jnp.float32)
    sf = jnp.asarray(rng.normal(0.0, 0.1, (m,)), jnp.float32)
    x = jnp.asarray(rng.random((m, n, d)), jnp.float32)
    mask = jnp.ones((m, n), jnp.float32)
    chol = jnp.asarray(np.broadcast_to(np.eye(n, dtype=np.float32) * 1.1,
                                       (m, n, n)))
    alpha = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    xq = jnp.asarray(rng.random((m, q, d)), jnp.float32)
    best = jnp.zeros((m,), jnp.float32)
    args = (ls, sf, x, mask, chol, alpha, xq, best)

    def vmapped():
        mu, var = _batched_posterior(ls, sf, x, mask, chol, alpha, xq)
        return expected_improvement(mu, var, 0.0)

    _fused_posterior_launch(*args, impl="xla")[2].block_until_ready()
    vmapped().block_until_ready()
    reps = 20
    t0 = time.time()
    for _ in range(reps):
        _fused_posterior_launch(*args, impl="xla")[2].block_until_ready()
    fused_s = (time.time() - t0) / reps
    t0 = time.time()
    for _ in range(reps):
        vmapped().block_until_ready()
    vmap_s = (time.time() - t0) / reps
    C.emit("fused_posterior_launch", fused_s * 1e6, f"m{m}n{n}q{q}")
    C.emit("fused_posterior_vs_vmapped_speedup", 0.0,
           f"{vmap_s / fused_s:.2f}")


def _fused_ehvi_numbers() -> None:
    """The fused draw+EHVI bucket kernel vs the two-launch chain it
    replaces (eager draw combine -> vmapped box launch, with the raw-
    scale draw tensor round-tripping through HBM between them)."""
    import jax
    import jax.numpy as jnp

    from repro.core.acquisition import _ehvi_box_launch
    from repro.core.plan import _draw_launch
    from repro.kernels.fused_ehvi.ops import _fused_ehvi_launch

    l, d, s, q, k = 8, 2, 64, 512, 64
    rng = np.random.default_rng(0)
    corners = np.sort(rng.random((l, k, d)).astype(np.float32), axis=1)
    los = jnp.asarray(corners)
    his = jnp.asarray(np.concatenate(
        [corners[:, 1:], np.full((l, 1, d), np.inf, np.float32)], axis=1))
    refs = jnp.ones((l, d), jnp.float32) * 2.0
    mu = jnp.asarray(rng.normal(size=(l, d, q)), jnp.float32)
    var = jnp.asarray(rng.uniform(0.1, 1.0, (l, d, q)), jnp.float32)
    y_mean = jnp.zeros((l, d), jnp.float32)
    y_std = jnp.ones((l, d), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(0), l * d)
    draw = jax.vmap(lambda kk: jax.random.normal(kk, (s, q)))

    def fused():
        eps = draw(keys).reshape(l, d, s, q)
        return _fused_ehvi_launch(los, his, refs, mu, var, y_mean,
                                  y_std, eps, impl="xla")

    def vmapped():
        ps = _draw_launch(keys, mu.reshape(l * d, q), var.reshape(l * d, q),
                          jnp.ones((l * d,)), jnp.zeros((l * d,)),
                          n_mc=s).reshape(l, d, s, q)
        return _ehvi_box_launch(los, his, refs, ps)

    fused().block_until_ready()
    vmapped().block_until_ready()
    reps = 20
    t0 = time.time()
    for _ in range(reps):
        fused().block_until_ready()
    fused_s = (time.time() - t0) / reps
    t0 = time.time()
    for _ in range(reps):
        vmapped().block_until_ready()
    vmap_s = (time.time() - t0) / reps
    C.emit("fused_ehvi_launch", fused_s * 1e6, f"l{l}s{s}q{q}k{k}")
    C.emit("fused_ehvi_vs_vmapped_speedup", 0.0,
           f"{vmap_s / fused_s:.2f}")


def _fused_fit_numbers() -> None:
    """The fused fit kernel (masked Matern-5/2 NLML + analytic grad +
    Adam + factorisation in ONE launch) vs the two-launch vmapped chain
    it replaces (autodiff ``_fit_batched`` then ``_batched_chol_alpha``,
    with the hyperparameters round-tripping through HBM between them),
    plus the warm-vs-cold rung wall split — the kernel-level view of
    what the warm-start cache buys per fit round."""
    import jax.numpy as jnp

    from repro.core.gp import _batched_chol_alpha, _fit_batched
    from repro.kernels.fused_fit.ops import _fused_fit_launch

    m, n, d = 16, 32, 7
    cold_steps, warm_steps, noise = 120, 16, 0.1
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random((m, n, d)), jnp.float32)
    yr = np.sin(np.asarray(x).sum(axis=2)) \
        + 0.1 * rng.normal(size=(m, n)).astype(np.float32)
    y = jnp.asarray((yr - yr.mean(axis=1, keepdims=True))
                    / yr.std(axis=1, keepdims=True), jnp.float32)
    mask = jnp.ones((m, n), jnp.float32)
    zls = jnp.zeros((m, d), jnp.float32)
    zsf = jnp.zeros((m,), jnp.float32)

    def fused(steps):
        return _fused_fit_launch(x, y, mask, zls, zsf, steps=steps,
                                 noise=noise, impl="xla")

    def vmapped():
        fitted = _fit_batched(x, y, mask, steps=cold_steps, noise=noise)
        chol, alpha = _batched_chol_alpha(fitted["ls"], fitted["sf"],
                                          x, y, mask, noise)
        return fitted["ls"], fitted["sf"], chol, alpha

    ls_f, sf_f, _, _ = fused(cold_steps)
    ls_v, sf_v, _, _ = vmapped()
    # parity guard: the analytic gradient IS the autodiff gradient
    np.testing.assert_allclose(np.asarray(ls_f), np.asarray(ls_v),
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(sf_f), np.asarray(sf_v),
                               atol=1e-3)
    fused(warm_steps)[3].block_until_ready()
    reps = 10
    t0 = time.time()
    for _ in range(reps):
        fused(cold_steps)[3].block_until_ready()
    cold_s = (time.time() - t0) / reps
    t0 = time.time()
    for _ in range(reps):
        fused(warm_steps)[3].block_until_ready()
    warm_s = (time.time() - t0) / reps
    t0 = time.time()
    for _ in range(reps):
        vmapped()[3].block_until_ready()
    vmap_s = (time.time() - t0) / reps
    C.emit("fused_fit_launch", cold_s * 1e6,
           f"m{m}n{n}steps{cold_steps}")
    C.emit("fused_fit_vs_vmapped_speedup", 0.0,
           f"{vmap_s / cold_s:.2f}")
    C.emit("fused_fit_warm_rung", warm_s * 1e6,
           f"steps{warm_steps}")
    C.emit("fused_fit_warm_vs_cold_speedup", 0.0,
           f"{cold_s / warm_s:.2f}")


def _plan_host_s(stats) -> float:
    """Host seconds the plan layer's spans (``StepPlanner.plan`` and
    the executor's pack / launch / unpack / scatter) took, self time."""
    return sum(stats.get(f"span_s.{n}", 0.0)
               for n in ("plan", "pack", "launch", "unpack", "scatter"))


def steady_state() -> None:
    """Compile-once serving (the ISSUE-6 acceptance scenario): per-step
    latency of a churning mixed SO + 2-objective + 3-objective cohort
    served COLD (every launch shape compiles inline as it first
    appears) vs after ``SearchService.precompile`` has warmed the
    enumerated bucket vocabulary — where ``plan_compile_misses`` must
    stay exactly 0 — plus the fused kernel comparisons."""
    import dataclasses as dc

    from repro.core.plan import CohortLimits

    emu = C.emulator()
    sp_full = C.space()
    # a trimmed candidate space keeps the EHVI bucket vocabulary (the
    # dominant share of the precompile) proportionate to a benchmark
    sp = dc.replace(sp_full, name="scout-mini",
                    configs=sp_full.configs[:8])
    wid = emu.workload_ids()[6]
    cons = [Constraint("runtime", emu.runtime_target(wid, 50))]
    cfg = BOConfig(n_init=2, max_iters=5, rgpe_samples=32)

    def fresh_repo() -> Repository:
        repo = Repository()
        rng = np.random.default_rng(7)
        for u in range(2):
            for ci in rng.choice(len(sp), 6, replace=False):
                repo.add_run(emu.make_record(f"anon-{u}", wid,
                                             sp.configs[ci], rng))
        return repo

    def submit(svc: SearchService, i: int) -> None:
        runner = C.profile_fn(wid, 100 + i)
        if i % 3 == 0:
            svc.submit(SearchRequest(
                sp, runner, Objective("cost"), cons, method="karasu",
                bo_config=cfg, seed=100 + i))
        elif i % 3 == 1:
            svc.submit(SearchRequest(
                sp, runner, None, cons, method="karasu", bo_config=cfg,
                seed=100 + i,
                objectives=[Objective("cost"), Objective("energy")],
                n_mc=8))
        else:
            svc.submit(SearchRequest(
                sp, runner, None, (), method="karasu", bo_config=cfg,
                seed=100 + i,
                objectives=[Objective("cost"), Objective("energy"),
                            Objective("runtime")], n_mc=8))

    def run_steps(svc: SearchService, n_steps: int):
        submitted = 0
        times = []
        for _ in range(n_steps):
            while len(svc.active) + len(svc.queue) < 3:
                submit(svc, submitted)
                submitted += 1
            t0 = time.time()
            svc.step()
            times.append(time.time() - t0)
        return times

    steps = {"ci": 40, "full": 200}.get(C.SCALE, 40)

    from repro.core.plan import PlanExecutor

    # both services dispatch the fused EHVI launch, so the cold/warm
    # contrast isolates precompile (and the zero-miss assertion covers
    # the fused vocabulary)
    cold = SearchService(fresh_repo(), slots=3,
                         plan_executor=PlanExecutor(fused_ehvi=True))
    cold_times = run_steps(cold, steps)

    warm = SearchService(fresh_repo(), slots=3,
                         plan_executor=PlanExecutor(fused_ehvi=True))
    # lane bound: 8 target lanes (the cohort's measures) + 8 RGPE jobs
    # x up to 3 support bases fused into the same posterior buckets
    limits = CohortLimits(d=sp.all_encoded().shape[1], q_grid=len(sp),
                          max_obs=8, max_lanes=32, n_samples=(32,),
                          n_mc=(8,), n_objectives=(2, 3),
                          max_ehvi_boxes=256)
    t0 = time.time()
    pre = warm.precompile(limits)
    pre_s = time.time() - t0
    warm_times = run_steps(warm, steps)
    assert warm.stats["plan_compile_misses"] == 0, warm.stats
    # the churning cohort's refits must actually ride the warm rung
    assert warm.stats["fit_warm_lanes"] > 0, warm.stats

    C.emit("search_service_steady_cold_step",
           float(np.mean(cold_times)) * 1e6, f"{steps}steps")
    C.emit("search_service_steady_warm_step",
           float(np.mean(warm_times)) * 1e6, f"{steps}steps")
    C.emit("search_service_precompile", pre_s * 1e6,
           f"{pre['buckets']}buckets_{pre['compiles']}compiles")
    C.emit("search_service_steady_misses", 0.0,
           str(warm.stats["plan_compile_misses"]))
    # the plan layer's host time per service step (its spans' self
    # time), annotated with how the cohort's fit lanes split between
    # the warm refine and cold rungs
    C.emit("search_service_steady_plan_host",
           _plan_host_s(warm.stats) * 1e6 / steps,
           f"warm{warm.stats['fit_warm_lanes']}"
           f"_cold{warm.stats['fit_cold_lanes']}")
    _fused_kernel_numbers()
    _fused_ehvi_numbers()
    _fused_fit_numbers()


def mesh_scaling() -> None:
    """``--mesh N`` acceptance mode: one large karasu cohort served
    twice per executor — cold (compiling) then warm — on the
    single-device path and again with every bucket's lane axis sharded
    over the N-device data mesh. Emits warm per-step wall times for
    both plus the measured scaling ratio; the warm sharded pass must
    hold ``plan_compile_misses == 0`` (the sharded jit twins are part
    of the compile-once vocabulary). The ratio is MEASURED, never
    asserted: ``--xla_force_host_platform_device_count`` devices share
    the machine's physical cores, so near-linear scaling appears only
    on hosts that actually have N cores to back the mesh."""
    from repro.core.plan import PlanExecutor

    n_tenants = int(os.environ.get("REPRO_BENCH_MESH_TENANTS", "64"))
    # n_init < max_iters so every tenant runs real BO iterations (init
    # profiling alone must not satisfy max_iters and finish the session
    # before the plan layer ever executes)
    cfg = BOConfig(n_init=2, max_iters=6)
    sp, tenants, repo, targets = _setup(n_tenants)

    def run_cohort(mesh):
        svc = SearchService(
            _fresh_repo(repo), slots=n_tenants, mesh=mesh,
            plan_executor=PlanExecutor(fused_ehvi=True, mesh=mesh))
        for t, wid in enumerate(tenants):
            svc.submit(SearchRequest(
                sp, C.profile_fn(wid, t), Objective("cost"),
                [Constraint("runtime", targets[wid])], method="karasu",
                bo_config=cfg, seed=t))
        steps = 0
        t0 = time.time()
        while svc.active or svc.queue:
            svc.step()
            steps += 1
        return svc, (time.time() - t0) / max(1, steps), steps

    run_cohort(None)                                     # cold: compiles
    base_svc, base_step, base_steps = run_cohort(None)   # warm, timed
    assert base_svc.stats["plan_compile_misses"] == 0, base_svc.stats
    C.emit("search_service_mesh1_step", base_step * 1e6,
           f"{n_tenants}tenants_{base_steps}steps")

    mesh = _mesh()
    if mesh is None:          # --mesh 1: single-device numbers only
        return
    run_cohort(mesh)                                     # cold: compiles
    sh_svc, sh_step, sh_steps = run_cohort(mesh)         # warm, timed
    assert sh_svc.stats["plan_compile_misses"] == 0, sh_svc.stats
    C.emit(f"search_service_mesh{MESH_N}_step", sh_step * 1e6,
           f"{n_tenants}tenants_{sh_steps}steps")
    C.emit("search_service_mesh_scaling", 0.0,
           f"{base_step / sh_step:.2f}x_over_{MESH_N}dev")
    C.emit("search_service_mesh_misses", 0.0,
           str(sh_svc.stats["plan_compile_misses"]))
    # the plan layer's host time on each path (its spans' self time),
    # with the share of it spent inside the jitted launch calls
    for tag, svc in (("mesh1", base_svc), (f"mesh{MESH_N}", sh_svc)):
        s = svc.stats
        C.emit(f"search_service_{tag}_plan_host",
               _plan_host_s(s) * 1e6,
               f"launch={s.get('span_s.launch', 0.0):.3f}s")


def main() -> None:
    if "--smoke" in sys.argv[1:]:
        smoke()
        return
    if "--steady-state" in sys.argv[1:] or \
            os.environ.get("REPRO_BENCH_STEADY_STATE") == "1":
        steady_state()
        return
    if "--mesh" in sys.argv[1:]:
        mesh_scaling()
        return
    if "--moo" in sys.argv[1:] or \
            os.environ.get("REPRO_BENCH_MOO") == "1":
        moo_mixed()
        return
    if "--slow-profilers" in sys.argv[1:] or \
            os.environ.get("REPRO_BENCH_SLOW_PROFILERS") == "1":
        slow_profilers()
        return
    scale = C.SCALE
    n_tenants = N_TENANTS.get(scale, 8)
    max_iters = MAX_ITERS.get(scale, 10)
    sp, tenants, repo, targets = _setup(n_tenants)
    iters_total = n_tenants * max_iters

    # untimed warmup (2 tenants, 5 iters) so both paths measure
    # steady-state execution rather than first-call jit compilation
    _loop(sp, tenants[:2], _fresh_repo(repo), targets, 5)
    _service(sp, tenants[:2], _fresh_repo(repo), targets, 5)

    loop_s = _loop(sp, tenants, _fresh_repo(repo), targets, max_iters)
    svc_s = _service(sp, tenants, _fresh_repo(repo), targets, max_iters)

    C.emit("search_service_loop", loop_s * 1e6 / iters_total,
           f"{n_tenants}tenants")
    C.emit("search_service_batched", svc_s * 1e6 / iters_total,
           f"{n_tenants}tenants")
    C.emit("search_service_speedup", 0.0, f"{loop_s / svc_s:.2f}")


if __name__ == "__main__":
    from repro.launch.compile_stats import use_compile_cache
    use_compile_cache()
    main()
