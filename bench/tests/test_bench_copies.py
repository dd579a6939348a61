"""The benchmark's copies agree with the program as it stands, at tiny
sizes on the CPU: the scout emulator and search space, and the float64
reference's GP fit and posterior, support selection, RGPE weights,
constrained EI and EHVI; and the control's lower-precision products."""
import numpy as np
import pytest

from bench import reference as ref
from bench import scout
from bench.cohort import Cohort


@pytest.fixture(scope="module")
def emus():
    from repro.simdata import make_emulator
    return scout.Emulator(), make_emulator()


def test_space_and_encoding_match():
    from repro.core import scout_search_space
    space = scout_search_space()
    ours = scout.space_configs()
    assert [dict(c) for c in space.configs] == ours
    np.testing.assert_array_equal(
        space.all_encoded(), np.stack([scout.encode(c) for c in ours]))


@pytest.mark.parametrize("w", [0, 7, 17])
def test_emulator_runs_match(emus, w):
    ours, theirs = emus
    wid = ours.workload_ids()[w]
    assert wid == theirs.workload_ids()[w]
    for ci in (0, 33, 68):
        c = ours.configs[ci]
        m1, a1 = ours.run(wid, c, np.random.default_rng(ci))
        m2, a2 = theirs.run(wid, c, np.random.default_rng(ci))
        assert m1 == m2
        np.testing.assert_array_equal(a1, a2)
    assert ours.runtime_target(wid, 50) == theirs.runtime_target(wid, 50)


def _gp_data(seed, n, d=7):
    rng = np.random.default_rng(seed)
    x = rng.random((n, d))
    return x, np.sin(3 * x.sum(1)) + 0.1 * rng.normal(size=n)


def test_fit_and_posterior_match_program():
    from repro.core.gp import fit_gp, gp_posterior
    x, y = _gp_data(0, 12)
    gp = fit_gp(x, y, noise=0.1, steps=120)
    ls, sf = ref.fit_lanes([x], [y], np.zeros((1, 7)), np.zeros(1),
                           steps=120, noise=0.1, lr=0.05)
    got = ref.nlml(x, y, np.asarray(gp.params.log_lengthscales),
                   float(gp.params.log_signal), 0.1)
    assert abs(got - ref.nlml(x, y, ls[0], sf[0], 0.1)) < 1e-3
    xq = np.random.default_rng(1).random((20, 7))
    mu, var = gp_posterior(gp, xq)
    ours = ref.GP64(x, y, np.asarray(gp.params.log_lengthscales),
                    float(gp.params.log_signal), 0.1)
    mu64, var64 = ours.posterior(xq)
    np.testing.assert_allclose(np.asarray(mu), mu64, atol=2e-4)
    np.testing.assert_allclose(np.asarray(var), var64, atol=2e-4)


def test_support_selection_matches_algorithm_1():
    from repro.core.selection import select_similar
    from repro.core.types import RunRecord
    runs = Cohort({"repository": {"collaborators": 1, "runs_each": 6}},
                  3).repository_runs()
    cands = {}
    for anon, c, ms, mt in runs:
        cands.setdefault(anon, []).append(RunRecord(anon, c, mt, ms))
    target = [RunRecord("t", r.config, r.metrics, r.measures)
              for r in cands["anon-4"][:3]]
    want = [w for w, _ in select_similar(target, cands, 3)]
    ours = ref.select_support(
        [(r.config, r.metrics) for r in target],
        {w: [(r.config, r.metrics) for r in rs] for w, rs in cands.items()},
        3)
    assert ours == want


def test_rgpe_weights_match_program():
    import jax
    from repro.core.gp import fit_gp, stack_gps
    from repro.core.rgpe import compute_weights_batched
    bases = [fit_gp(*_gp_data(s, 10), noise=0.1) for s in (1, 2, 3)]
    x, y = _gp_data(4, 6)
    tgt = fit_gp(x, y, noise=0.1)
    key = jax.random.PRNGKey(7)
    w = np.asarray(compute_weights_batched(stack_gps(bases), tgt, key,
                                           n_samples=256))

    def gp64(g):
        return ref.GP64(np.asarray(g.x), np.asarray(g.y_raw),
                        np.asarray(g.params.log_lengthscales),
                        float(g.params.log_signal), 0.1)
    ours = ref.rgpe_weights([gp64(b) for b in bases], gp64(tgt), key, 256)
    np.testing.assert_allclose(w, ours, atol=2 / 256)


def test_constrained_ei_matches_program():
    from repro.core.acquisition import constrained_ei
    rng = np.random.default_rng(5)
    mu, var = rng.normal(size=30), rng.uniform(0.01, 2, 30)
    mc, vc = rng.normal(size=30), rng.uniform(0.01, 2, 30)
    want = np.asarray(constrained_ei(mu, var, -0.3, [(mc, vc, 0.2)]))
    np.testing.assert_allclose(
        want, ref.constrained_ei(mu, var, -0.3, [(mc, vc, 0.2)]),
        rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("n_obj", [2, 3])
def test_ehvi_matches_program_oracle(n_obj):
    from repro.core.acquisition import mc_ehvi_nd
    rng = np.random.default_rng(n_obj)
    observed = rng.random((6, n_obj))
    refp = observed.max(0) * 1.1 + 1e-9
    samples = [rng.random((16, 9)) for _ in range(n_obj)]
    np.testing.assert_allclose(ref.mc_ehvi(samples, observed, refp),
                               mc_ehvi_nd(samples, observed, refp),
                               rtol=1e-9, atol=1e-12)


@pytest.mark.parametrize("passes,worst", [(1, 1e-2), (3, 1e-4)])
def test_lower_precision_products(passes, worst):
    # bfloat16 parts round as JAX rounds them; a product from one pass
    # errs by about 2^-8 of its scale, from three by about 2^-16
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    a, b = rng.normal(size=(16, 7)), rng.normal(size=(7, 24))
    a32 = a.astype(np.float32)
    np.testing.assert_array_equal(
        ref.bf16(a32), np.asarray(jnp.asarray(a32).astype(jnp.bfloat16)
                                  .astype(jnp.float32)))
    err = np.abs(ref.mm(a, b, passes) - a @ b).max()
    scale = np.abs(a).max() * np.abs(b).max() * 7
    assert 0 < err < worst * scale
    np.testing.assert_array_equal(ref.mm(a, b), a @ b)


@pytest.mark.parametrize("passes", [1, 3])
def test_lower_precision_kernel_matches_exact(passes):
    rng = np.random.default_rng(4)
    x = rng.uniform(size=(12, 7))
    ls, sf = rng.normal(scale=0.3, size=7), 0.2
    exact = ref.matern(x, x, ls, sf)
    low = ref.matern(x, x, ls, sf, passes)
    assert low.dtype == np.float32
    np.testing.assert_allclose(low, exact, atol=0.05 if passes == 1
                               else 1e-3)
