"""BatchedGP / batched RGPE: agreement with the per-model reference path
(acceptance: <= 1e-4 on the standardised scale), the fused posterior
query plan, impl routing, and weight invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (batched_posterior, batched_posterior_multi,
                        batched_sample, batched_sample_multi, build_ensemble,
                        compute_weights, compute_weights_batched,
                        compute_weights_multi, ensemble_posterior,
                        ensemble_posterior_batched, fit_gp, fit_gp_batched,
                        gp_posterior, stack_gps)
from repro.core.rgpe import BatchedEnsemble
from repro.kernels.routing import resolve_impl

TOL = 1e-4


def _surface(x):
    return np.sin(3 * x[:, 0]) + 0.5 * x[:, 1]


def _models(seed=0, sizes=(5, 9, 14)):
    rng = np.random.default_rng(seed)
    xs = [rng.random((n, 3)) for n in sizes]
    ys = [np.sin(3 * x[:, 0]) + 0.5 * x[:, 1] - x[:, 2] for x in xs]
    return xs, ys, rng


def test_batched_fit_matches_per_model_posterior():
    xs, ys, rng = _models()
    xq = rng.random((25, 3))
    bgp = fit_gp_batched(xs, ys)
    mu_b, var_b = batched_posterior(bgp, xq)
    for i, (x, y) in enumerate(zip(xs, ys)):
        gp = fit_gp(x, y)
        mu, var = gp_posterior(gp, xq)
        np.testing.assert_allclose(np.asarray(mu_b[i]), np.asarray(mu),
                                   atol=TOL)
        np.testing.assert_allclose(np.asarray(var_b[i]), np.asarray(var),
                                   atol=TOL)
        np.testing.assert_allclose(float(bgp.y_mean[i]), float(gp.y_mean),
                                   rtol=1e-6)
        np.testing.assert_allclose(float(bgp.y_std[i]), float(gp.y_std),
                                   rtol=1e-6)


def test_padding_is_exact():
    """Extra padding must not change results beyond float32 roundoff
    (different jit shapes reassociate reductions, so not bitwise)."""
    xs, ys, rng = _models(seed=1)
    xq = rng.random((10, 3))
    a = fit_gp_batched(xs, ys)
    b = fit_gp_batched(xs, ys, n_max=32)
    mu_a, var_a = batched_posterior(a, xq)
    mu_b, var_b = batched_posterior(b, xq)
    np.testing.assert_allclose(np.asarray(mu_a), np.asarray(mu_b), atol=TOL)
    np.testing.assert_allclose(np.asarray(var_a), np.asarray(var_b),
                               atol=TOL)


def test_stack_gps_is_exact_and_extract_roundtrips():
    xs, ys, rng = _models(seed=2)
    gps = [fit_gp(x, y) for x, y in zip(xs, ys)]
    bgp = stack_gps(gps)
    xq = rng.random((12, 3))
    mu_b, var_b = batched_posterior(bgp, xq)
    for i, gp in enumerate(gps):
        mu, var = gp_posterior(gp, xq)
        np.testing.assert_allclose(np.asarray(mu_b[i]), np.asarray(mu),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(var_b[i]), np.asarray(var),
                                   atol=1e-5)
        g2 = bgp.extract(i)
        assert g2.n == gp.n
        mu2, _ = gp_posterior(g2, xq)
        np.testing.assert_allclose(np.asarray(mu2), np.asarray(mu),
                                   atol=1e-5)


def test_host_target_slice_matches_extract_bitwise():
    """``to_host`` reads a stack back once; ``extract`` on the host copy
    gives numpy arrays equal bit for bit to the device ``extract``."""
    xs, ys, _ = _models(sizes=(2, 5, 9, 14))
    bgp = fit_gp_batched(xs, ys, round_to=8, m_round_pow2=True)
    host = bgp.to_host()
    for i in range(len(xs)):
        h, d = host.extract(i), bgp.extract(i)
        assert h.n == d.n == len(ys[i])
        for f in ("x", "y_raw", "y", "y_mean", "y_std", "chol", "alpha"):
            assert isinstance(getattr(h, f), (np.ndarray, np.generic)), f
            np.testing.assert_array_equal(getattr(h, f),
                                          np.asarray(getattr(d, f)))
        for f in ("log_lengthscales", "log_signal"):
            np.testing.assert_array_equal(getattr(h.params, f),
                                          np.asarray(getattr(d.params, f)))


@pytest.mark.parametrize("batched", [False, True])
def test_host_built_support_stack_matches_stack_gps_bitwise(batched):
    """A support-stack miss stacks the host copies taken at fit time:
    each stack equals ``stack_gps`` over the device GPs bit for bit,
    whether asked for alone or among a batch (one transfer for all its
    misses, a repeated key built once), and the store counts each
    distinct miss once."""
    from repro.core import Repository, SupportModelStore
    from repro.core.plan import OBS_ROUND_TO
    from repro.simdata import make_emulator
    emu = make_emulator()
    space = emu.space
    repo = Repository()
    rng = np.random.default_rng(3)
    for u, n in enumerate((4, 7, 11)):
        for ci in rng.choice(len(space), n, replace=False):
            repo.add_run(emu.make_record(f"u{u}", emu.workload_ids()[u],
                                         space.configs[ci], rng))
    store = SupportModelStore(repo, space)
    sets = ([["u0", "u1", "u2"]] if not batched
            else [["u0", "u1", "u2"], ["u2", "u0"], ["u0", "u1", "u2"]])
    if batched:
        got = store.get_stacked_many([(ids, "cost") for ids in sets])
        assert got[2][0] is got[0][0]
    else:
        got = [store.get_stacked(sets[0], "cost")]
    assert store.stack_misses == (2 if batched else 1)
    for ids, (stack, got_ids) in zip(sets, got):
        assert got_ids == ids
        ref = stack_gps([store.get(z, "cost") for z in ids],
                        round_to=OBS_ROUND_TO)
        for f in ("x", "y", "mask", "y_mean", "y_std", "log_lengthscales",
                  "log_signal", "chol", "alpha", "counts"):
            np.testing.assert_array_equal(np.asarray(getattr(stack, f)),
                                          np.asarray(getattr(ref, f)))
        assert stack.noise == ref.noise
        assert store.get_stacked(ids, "cost")[0] is stack
    assert store.stack_misses == (2 if batched else 1)


def test_batched_sample_matches_per_model():
    xs, ys, rng = _models(seed=3)
    gps = [fit_gp(x, y) for x, y in zip(xs, ys)]
    bgp = stack_gps(gps)
    xq = rng.random((7, 3))
    keys = jax.random.split(jax.random.PRNGKey(5), len(gps))
    s = batched_sample(bgp, xq, keys, 32)
    assert s.shape == (len(gps), 32, 7)
    from repro.core.gp import gp_sample
    for i, gp in enumerate(gps):
        si = gp_sample(gp, xq, keys[i], 32)
        np.testing.assert_allclose(np.asarray(s[i]), np.asarray(si),
                                   atol=1e-5)


# -- fused sample query plan --------------------------------------------------


def test_batched_sample_multi_matches_per_stack():
    """Many stacks' posterior draws fused into one padded launch per
    (S, q, d) bucket must reproduce each per-stack ``batched_sample`` —
    including edge buckets: a single-model stack, an n_obs=1 model,
    mixed dims, and differing n_samples."""
    rng = np.random.default_rng(21)
    queries, singles = [], []
    cases = [((5, 9, 14), 3, 64, 7),     # sizes, d, S, q
             ((4, 7), 3, 64, 7),         # same bucket as above
             ((6,), 3, 64, 7),           # single-model stack, same bucket
             ((1, 8), 3, 64, 7),         # n_obs=1 lane, same bucket
             ((5, 9), 2, 64, 7),         # different dim -> own bucket
             ((5, 9), 3, 32, 7),         # different S -> own bucket
             ((5, 9), 3, 64, 11)]        # different q -> own bucket
    for j, (sizes, d, S, q) in enumerate(cases):
        xs = [rng.random((n, d)) for n in sizes]
        ys = [x[:, 0] + np.sin(3 * x[:, 1]) for x in xs]
        st = fit_gp_batched(xs, ys)
        xq = rng.random((q, d))
        keys = jax.random.split(jax.random.PRNGKey(j), len(sizes))
        queries.append((st, xq, keys, S))
        singles.append(batched_sample(st, xq, keys, S))

    counters = {}
    res = batched_sample_multi(queries, counters=counters)
    # first four cases share one (64, 7, 3) bucket; the rest are singletons
    assert counters["launches"] == 4
    assert counters["queries"] == len(cases)
    for (st, xq, _, S), got, want in zip(queries, res, singles):
        assert got.shape == (st.m, S, xq.shape[0])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=TOL)


def test_batched_sample_multi_draw_streams_are_fusion_invariant():
    """Each lane consumes normal(key_i, (S, q)) regardless of which
    other queries share its launch, so adding an unrelated query to the
    plan must not perturb existing draws (beyond posterior roundoff)."""
    rng = np.random.default_rng(22)
    xs = [rng.random((n, 2)) for n in (5, 8)]
    st = fit_gp_batched(xs, [x[:, 0] for x in xs])
    xq = rng.random((6, 2))
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    alone, = batched_sample_multi([(st, xq, keys, 48)])
    other = fit_gp_batched([rng.random((12, 2))], [np.zeros(12)])
    joined, _ = batched_sample_multi(
        [(st, xq, keys, 48), (other, xq, jax.random.split(
            jax.random.PRNGKey(9), 1), 48)])
    np.testing.assert_allclose(np.asarray(alone), np.asarray(joined),
                               atol=TOL)


def test_loo_sample_multi_matches_per_target():
    """Fused leave-one-out draws (padded cho_solve, exact-shape eps)
    must reproduce per-target gp_loo_samples — including an n_obs=1
    target and mixed observation counts in one call."""
    import jax.random as jr
    from repro.core.gp import gp_loo_samples, loo_sample_multi
    rng = np.random.default_rng(31)
    targets = []
    for n in (6, 6, 9, 1):
        x = rng.random((n, 2))
        targets.append(fit_gp(x, x[:, 0] + 0.1 * rng.normal(size=n)))
    queries = [(gp, jr.PRNGKey(i), 32) for i, gp in enumerate(targets)]
    counters = {}
    res = loo_sample_multi(queries, counters=counters)
    assert counters["launches"] == 3        # n=6 bucket shared, 9, 1
    assert counters["queries"] == 4
    for (gp, key, S), got in zip(queries, res):
        want = gp_loo_samples(gp, key, S)
        assert got.shape == want.shape == (S, gp.n)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=TOL)


def test_compute_weights_multi_fused_samples_match_loop():
    """fuse_samples=True (the sample query plan) and the per-job draw
    loop consume identical PRNG streams, so weights must agree — and
    the fused path must report its launch fusion via sample_counters."""
    from repro.core.rgpe import WeightJob
    rng = np.random.default_rng(23)
    jobs = []
    for j in range(3):
        xs = [rng.random((10 + i, 2)) for i in range(2)]
        bases = fit_gp_batched(xs, [_surface(x) for x in xs])
        xt = rng.random((6, 2))         # same n_obs -> one sample bucket
        jobs.append(WeightJob(bases, fit_gp(xt, _surface(xt)),
                              jax.random.PRNGKey(j), 64))
    # an n_obs=1 job: uniform short-circuit, never enters the plan
    x1 = rng.random((1, 2))
    jobs.append(WeightJob(bases, fit_gp(x1, x1[:, 0]),
                          jax.random.PRNGKey(9), 64))
    sc = {}
    w_fused = compute_weights_multi(jobs, fuse_samples=True,
                                    sample_counters=sc)
    w_loop = compute_weights_multi(jobs, fuse_samples=False)
    # one fused base-draw launch + one fused LOO launch for all 3 jobs
    assert sc["launches"] == 2 and sc["queries"] == 6
    for a, b in zip(w_fused, w_loop):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=TOL)
    np.testing.assert_allclose(np.asarray(w_fused[-1]),
                               np.full(3, 1.0 / 3.0), atol=1e-7)


# -- fused posterior query plan ---------------------------------------------


def test_batched_posterior_multi_matches_per_stack():
    """Many stacks of different m / n_max / grids fused into one padded
    launch must reproduce each per-stack batched_posterior."""
    rng = np.random.default_rng(11)
    stacks, grids = [], []
    for sizes in ((5, 9, 14), (4, 7), (6,)):
        xs = [rng.random((n, 3)) for n in sizes]
        ys = [x[:, 0] + np.sin(3 * x[:, 1]) for x in xs]
        stacks.append(fit_gp_batched(xs, ys))
        grids.append(rng.random((25, 3)))
    # a (q, d) group of its own: fused plan buckets by grid shape
    stacks.append(stacks[0])
    grids.append(rng.random((13, 3)))

    counters = {}
    res = batched_posterior_multi(list(zip(stacks, grids)),
                                  counters=counters)
    assert counters["launches"] == 2        # (25, 3) bucket + (13, 3)
    assert counters["queries"] == 4
    for st, xq, (mu, var) in zip(stacks, grids, res):
        mu0, var0 = batched_posterior(st, xq)
        assert mu.shape == (st.m, xq.shape[0])
        np.testing.assert_allclose(np.asarray(mu), np.asarray(mu0),
                                   atol=TOL)
        np.testing.assert_allclose(np.asarray(var), np.asarray(var0),
                                   atol=TOL)


def test_mix_weighted_matches_ensemble_posterior_batched():
    """Fusing bases + target rows through mix_weighted (as the query
    plan does) agrees with the per-ensemble mixture oracle."""
    rng = np.random.default_rng(12)
    xs = rng.random((20, 2))
    bases = stack_gps([fit_gp(xs, _surface(xs)),
                       fit_gp(rng.random((10, 2)), rng.normal(size=10))])
    x_t = rng.random((6, 2))
    target = fit_gp(x_t, _surface(x_t))
    w = compute_weights_batched(bases, target, jax.random.PRNGKey(3))
    ens = BatchedEnsemble(bases, target, w)
    xq = rng.random((30, 2))
    mu_b, var_b = batched_posterior(bases, xq)
    mu_t, var_t = gp_posterior(target, xq)
    from repro.core import mix_weighted
    mu, var = mix_weighted(mu_b, var_b, mu_t, var_t, w)
    mu0, var0 = ensemble_posterior_batched(ens, xq)
    np.testing.assert_allclose(np.asarray(mu), np.asarray(mu0), atol=TOL)
    np.testing.assert_allclose(np.asarray(var), np.asarray(var0), atol=TOL)


def test_impl_routing_resolves_auto_by_backend_and_size():
    # explicit impls pass through untouched on any backend
    for impl in ("xla", "pallas", "pallas_interpret"):
        assert resolve_impl(impl, cells=1) == impl
    # auto: pallas only on TPU and only above the cell threshold
    assert resolve_impl("auto", cells=1 << 30, backend="tpu") == "pallas"
    assert resolve_impl("auto", cells=8, backend="tpu") == "xla"
    assert resolve_impl("auto", cells=1 << 30, backend="cpu") == "xla"
    assert resolve_impl("auto", cells=1 << 30, backend="gpu") == "xla"
    # threshold override
    assert resolve_impl("auto", cells=9, backend="tpu",
                        min_cells=8) == "pallas"
    # on this machine (CPU CI) auto must resolve to the XLA reference
    rng = np.random.default_rng(0)
    xs = [rng.random((5, 2))]
    bgp = fit_gp_batched(xs, [xs[0][:, 0]])
    mu_a, var_a = batched_posterior(bgp, rng.random((4, 2)), impl="auto")
    assert np.all(np.isfinite(np.asarray(mu_a)))


def test_impl_routing_env_threshold_read_at_resolve_time(monkeypatch):
    # the env override must be honoured even when set AFTER import —
    # it used to be frozen into the module constant at import time, so
    # services configured via env after ``import repro`` silently kept
    # the default threshold
    monkeypatch.setenv("REPRO_PALLAS_AUTO_MIN_CELLS", "16")
    assert resolve_impl("auto", cells=16, backend="tpu") == "pallas"
    assert resolve_impl("auto", cells=15, backend="tpu") == "xla"
    monkeypatch.setenv("REPRO_PALLAS_AUTO_MIN_CELLS", str(1 << 30))
    assert resolve_impl("auto", cells=16, backend="tpu") == "xla"
    # an explicit min_cells argument still beats the env var
    monkeypatch.setenv("REPRO_PALLAS_AUTO_MIN_CELLS", "1")
    assert resolve_impl("auto", cells=2, backend="tpu",
                        min_cells=4) == "xla"
    monkeypatch.delenv("REPRO_PALLAS_AUTO_MIN_CELLS")
    assert resolve_impl("auto", cells=1 << 30, backend="tpu") == "pallas"


# -- RGPE weights ------------------------------------------------------------


def _rgpe_setup(seed=4):
    rng = np.random.default_rng(seed)
    xs = rng.random((30, 2))
    related = fit_gp(xs, _surface(xs))
    unrelated = fit_gp(rng.random((12, 2)), rng.normal(size=12))
    x_t = rng.random((8, 2))
    target = fit_gp(x_t, _surface(x_t))
    return related, unrelated, target, rng


def test_batched_weights_match_sequential():
    related, unrelated, target, _ = _rgpe_setup()
    key = jax.random.PRNGKey(0)
    w_seq = np.asarray(compute_weights([related, unrelated], target, key))
    w_bat = np.asarray(compute_weights_batched(
        stack_gps([related, unrelated]), target, key))
    np.testing.assert_allclose(w_bat, w_seq, atol=TOL)


def test_weights_on_simplex_and_target_never_diluted():
    related, unrelated, target, _ = _rgpe_setup(seed=5)
    for key_i in range(3):
        w = np.asarray(compute_weights_batched(
            stack_gps([related, unrelated]), target,
            jax.random.PRNGKey(key_i), n_samples=64))
        assert w.shape == (3,)
        assert np.all(w >= -1e-9)
        np.testing.assert_allclose(w.sum(), 1.0, atol=1e-5)
        # the related model must dominate the pure-noise one
        assert w[0] >= w[1]
    # dilution prevention never drops the target: even vs a perfect base
    # model the target keeps a nonzero share of the argmin ties
    w = np.asarray(compute_weights_batched(
        stack_gps([related]), target, jax.random.PRNGKey(9)))
    assert w[-1] > 0.0


def test_single_observation_falls_back_to_uniform():
    related, unrelated, target, rng = _rgpe_setup(seed=6)
    t1 = fit_gp(np.asarray(target.x)[:1], np.asarray(target.y_raw)[:1])
    bases = stack_gps([related, unrelated])
    w_b = np.asarray(compute_weights_batched(bases, t1,
                                             jax.random.PRNGKey(0)))
    w_s = np.asarray(compute_weights([related, unrelated], t1,
                                     jax.random.PRNGKey(0)))
    np.testing.assert_allclose(w_b, np.full(3, 1.0 / 3.0), atol=1e-7)
    np.testing.assert_allclose(w_s, w_b, atol=1e-7)


def test_batched_ensemble_posterior_matches_sequential():
    related, unrelated, target, rng = _rgpe_setup(seed=7)
    key = jax.random.PRNGKey(2)
    ens = build_ensemble([related, unrelated], target, key)
    bens = BatchedEnsemble(stack_gps([related, unrelated]), target,
                           compute_weights_batched(
                               stack_gps([related, unrelated]), target, key))
    xq = rng.random((40, 2))
    mu, var = ensemble_posterior(ens, xq)
    mu_b, var_b = ensemble_posterior_batched(bens, xq)
    np.testing.assert_allclose(np.asarray(mu_b), np.asarray(mu), atol=TOL)
    np.testing.assert_allclose(np.asarray(var_b), np.asarray(var), atol=TOL)


def test_pack_fit_lanes_standardisation_is_bitwise_per_lane():
    """The one-shot f64-accumulated standardisation in _pack_fit_lanes
    must be BITWISE identical to an explicit per-lane float64 loop
    mirroring its operation order: both the legacy vmapped fit and the
    fused fit leg consume this packing, so any drift here would
    silently fork their parity baselines. Includes a single-observation
    lane and a constant-target lane (the 1e-8 std clamp path)."""
    from repro.core.gp import _pack_fit_lanes
    rng = np.random.default_rng(11)
    counts = (7, 5, 1, 4)
    d, nm = 3, 8
    xs = [rng.random((n, d)) for n in counts]
    ys = [rng.normal(size=n) * 10.0 + 5.0 for n in counts]
    ys[3] = np.full(4, 2.5)                    # constant -> clamped std
    x, ysd, mask, y_mean, y_std = _pack_fit_lanes(
        xs, ys, list(counts), nm)
    for i, n in enumerate(counts):
        row = np.zeros(nm, np.float32)
        row[:n] = np.asarray(ys[i], np.float32)
        mrow = np.zeros(nm, np.float32)
        mrow[:n] = 1.0
        mu = row.sum(dtype=np.float64) / np.float64(n)
        sq = ((row - mu) * mrow) ** 2
        sd = np.maximum(np.sqrt(sq.sum(dtype=np.float64)
                                / np.float64(n)), 1e-8)
        ym = np.float32(mu)
        ysd_i = ((row - ym) / np.float32(sd)) * mrow
        assert y_mean[i] == ym
        assert y_std[i] == np.float32(sd)
        assert np.array_equal(ysd[i], ysd_i)
        assert np.array_equal(mask[i], mrow)
        assert np.array_equal(x[i, :n],
                              np.asarray(xs[i], np.float32))
        assert (x[i, n:] == 0).all() and (ysd[i, n:] == 0).all()
