"""Gaussian-process regression in pure JAX (Matern-5/2 + ARD).

The building block of both baselines and Karasu: CherryPick's NaiveBO is
exactly this GP + EI; Karasu fits one per workload per objective and
ensembles them with RGPE.

Targets are standardised internally (zero mean / unit variance over the
model's own observations) — the property RGPE relies on: predictions from
different workloads become comparable in *rank* without sharing scales.
Observation noise defaults to sigma^2 = 0.1 on the standardised scale, as
assumed in the paper's evaluation (§IV-B); kernel hyperparameters are fit
by Adam on the exact negative log marginal likelihood.

Hot spot at repository scale: the kernel matrix. ``repro.kernels.matern``
provides the Pallas-tiled pairwise Matern-5/2 kernel; this module calls
through ``matern52`` which dispatches on size/impl.

Two representations live here:

  - ``GP``        — one model, exact shapes. The reference implementation.
  - ``BatchedGP`` — m models stacked into padded ``(m, n_max, d)`` arrays
    with a validity mask, fit and queried through ``vmap`` so that all
    measures of one search, all support models of one ensemble, and all
    tenants of a ``SearchService`` round share a single batched Cholesky
    instead of a Python loop. Padding is exact: padded rows/columns are
    masked out of the kernel and carry unit diagonal entries, so the
    valid block of every factorisation equals the unbatched one.

On top of ``BatchedGP`` sits the posterior **query plan**
(``batched_posterior_multi``): many stacks' grid queries — target GPs,
RGPE support stacks, MOO models, across tenants — fused into one padded
launch per (grid, dim) bucket, with ``impl="auto"`` routing the pairwise
Matern to the Pallas kernel when the fused batch justifies it.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.matern import matern52
from repro.kernels.routing import resolve_impl

JITTER = 1e-6
# GP dots run at full f32: the TPU's default f32 matmul rounds its
# inputs to bf16, too coarse for the posterior's solves and means
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class GPParams:
    log_lengthscales: jnp.ndarray  # (d,)
    log_signal: jnp.ndarray        # ()
    noise: float                   # fixed observation noise variance


@dataclasses.dataclass(frozen=True)
class GP:
    x: jnp.ndarray                 # (n, d) encoded configs
    y_raw: jnp.ndarray             # (n,) original-scale targets
    y: jnp.ndarray                 # (n,) standardised targets
    y_mean: jnp.ndarray
    y_std: jnp.ndarray
    params: GPParams
    chol: jnp.ndarray              # (n, n) cholesky of K + noise I
    alpha: jnp.ndarray             # (n,) K^{-1} y

    @property
    def n(self) -> int:
        return int(self.x.shape[0])


def _kernel(params: GPParams, a: jnp.ndarray, b: jnp.ndarray,
            impl: str = "xla") -> jnp.ndarray:
    ls = jnp.exp(params.log_lengthscales)
    sf = jnp.exp(params.log_signal)
    return sf * matern52(a / ls, b / ls, impl=impl)


def _nlml(params: GPParams, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    n = x.shape[0]
    k = _kernel(params, x, x) + (params.noise + JITTER) * jnp.eye(n)
    chol = jnp.linalg.cholesky(k)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y)
    return (0.5 * jnp.dot(y, alpha, precision=HIGHEST)
            + jnp.sum(jnp.log(jnp.diagonal(chol)))
            + 0.5 * n * jnp.log(2.0 * jnp.pi))


def _adam_nlml(loss, d: int, steps: int, lr: float):
    """Shared Adam-on-NLML driver for both the single and batched fits —
    identical update rule so batched fits reproduce unbatched ones."""
    p0 = {"ls": jnp.zeros((d,)), "sf": jnp.zeros(())}
    grad = jax.grad(loss)
    mu0 = jax.tree.map(jnp.zeros_like, p0)
    nu0 = jax.tree.map(jnp.zeros_like, p0)

    def body(carry, i):
        p, mu, nu = carry
        g = grad(p)
        mu = jax.tree.map(lambda m, gg: 0.9 * m + 0.1 * gg, mu, g)
        nu = jax.tree.map(lambda v, gg: 0.999 * v + 0.001 * gg * gg, nu, g)
        t = i.astype(jnp.float32) + 1.0
        def upd(pp, m, v):
            mh = m / (1 - 0.9 ** t)
            vh = v / (1 - 0.999 ** t)
            return pp - lr * mh / (jnp.sqrt(vh) + 1e-8)
        p = jax.tree.map(upd, p, mu, nu)
        p = {"ls": jnp.clip(p["ls"], -3.0, 3.0),
             "sf": jnp.clip(p["sf"], -3.0, 3.0)}
        return (p, mu, nu), None

    (p, _, _), _ = jax.lax.scan(body, (p0, mu0, nu0), jnp.arange(steps))
    return p


@partial(jax.jit, static_argnames=("steps", "noise"))
def _fit(x, y, key, steps: int = 120, noise: float = 0.1,
         lr: float = 0.05):
    d = x.shape[1]

    def loss(p):
        return _nlml(GPParams(p["ls"], p["sf"], noise), x, y)

    return _adam_nlml(loss, d, steps, lr)


def fit_gp(x: np.ndarray, y: np.ndarray, *, noise: float = 0.1,
           steps: int = 120, key: Optional[jax.Array] = None) -> GP:
    x = jnp.asarray(x, jnp.float32)
    y_raw = jnp.asarray(y, jnp.float32)
    y_mean = jnp.mean(y_raw)
    y_std = jnp.maximum(jnp.std(y_raw), 1e-8)
    ys = (y_raw - y_mean) / y_std
    key = key if key is not None else jax.random.PRNGKey(0)
    p = _fit(x, ys, key, steps=steps, noise=noise)
    params = GPParams(p["ls"], p["sf"], noise)
    n = x.shape[0]
    k = _kernel(params, x, x) + (noise + JITTER) * jnp.eye(n)
    chol = jnp.linalg.cholesky(k)
    alpha = jax.scipy.linalg.cho_solve((chol, True), ys)
    return GP(x, y_raw, ys, y_mean, y_std, params, chol, alpha)


def gp_posterior(gp: GP, xq: jnp.ndarray,
                 impl: str = "xla") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Posterior mean/variance on the standardised scale. xq: (m, d)."""
    xq = jnp.asarray(xq, jnp.float32)
    ks = _kernel(gp.params, xq, gp.x, impl=impl)        # (m, n)
    mu = jnp.dot(ks, gp.alpha, precision=HIGHEST)
    v = jax.scipy.linalg.solve_triangular(gp.chol, ks.T, lower=True)
    kss = jnp.exp(gp.params.log_signal)                  # diag of k(x,x)
    var = jnp.maximum(kss - jnp.sum(v * v, axis=0), 1e-10)
    return mu, var


def gp_posterior_raw(gp: GP, xq) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Posterior on the original target scale."""
    mu, var = gp_posterior(gp, xq)
    return mu * gp.y_std + gp.y_mean, var * gp.y_std ** 2


def gp_sample(gp: GP, xq: jnp.ndarray, key: jax.Array,
              n_samples: int) -> jnp.ndarray:
    """Draw (n_samples, m) from the marginal posterior (independent per
    point, as used by RGPE's ranking-loss sampling)."""
    mu, var = gp_posterior(gp, xq)
    eps = jax.random.normal(key, (n_samples, mu.shape[0]))
    return mu[None] + eps * jnp.sqrt(var)[None]


def gp_loo_samples(gp: GP, key: jax.Array, n_samples: int) -> jnp.ndarray:
    """Leave-one-out posterior samples at the GP's own inputs — used for
    the target model inside RGPE so it does not trivially win on its own
    training points. Closed-form LOO from the full Cholesky."""
    n = gp.n
    kinv = jax.scipy.linalg.cho_solve((gp.chol, True), jnp.eye(n))
    kinv_diag = jnp.diagonal(kinv)
    mu_loo = gp.y - gp.alpha / kinv_diag
    var_loo = jnp.maximum(1.0 / kinv_diag, 1e-10)
    eps = jax.random.normal(key, (n_samples, n))
    return mu_loo[None] + eps * jnp.sqrt(var_loo)[None]


# ---------------------------------------------------------------------------
# BatchedGP: m models in padded (m, n_max, d) arrays, vmapped throughout
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BatchedGP:
    """m stacked GPs. Padded entries are masked out of every kernel and
    carry a unit diagonal, so each model's valid block matches its
    unbatched counterpart exactly."""
    x: jnp.ndarray                 # (m, n_max, d), zero-padded
    y: jnp.ndarray                 # (m, n_max) standardised, zero-padded
    mask: jnp.ndarray              # (m, n_max) 1.0 valid / 0.0 pad
    y_mean: jnp.ndarray            # (m,)
    y_std: jnp.ndarray             # (m,)
    log_lengthscales: jnp.ndarray  # (m, d)
    log_signal: jnp.ndarray        # (m,)
    noise: float
    chol: jnp.ndarray              # (m, n_max, n_max)
    alpha: jnp.ndarray             # (m, n_max)
    counts: jnp.ndarray            # (m,) int32 valid observations

    @property
    def m(self) -> int:
        return int(self.x.shape[0])

    @property
    def n_max(self) -> int:
        return int(self.x.shape[1])

    def extract(self, i: int) -> GP:
        """Materialise model i as an unbatched GP (exact un-padding)."""
        n = int(self.counts[i])
        params = GPParams(self.log_lengthscales[i], self.log_signal[i],
                          self.noise)
        ys = self.y[i, :n]
        return GP(self.x[i, :n], ys * self.y_std[i] + self.y_mean[i], ys,
                  self.y_mean[i], self.y_std[i], params,
                  self.chol[i, :n, :n], self.alpha[i, :n])


def _masked_nlml(params: GPParams, x: jnp.ndarray, y: jnp.ndarray,
                 mask: jnp.ndarray) -> jnp.ndarray:
    """NLML over the valid block only. Padded rows/cols contribute a
    parameter-independent constant, so gradients equal the unmasked
    ``_nlml`` on the valid data."""
    n_max = x.shape[0]
    k = _kernel(params, x, x) * (mask[:, None] * mask[None, :])
    k = k + (params.noise + JITTER) * jnp.eye(n_max) + jnp.diag(1.0 - mask)
    chol = jnp.linalg.cholesky(k)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y)
    n = jnp.sum(mask)
    return (0.5 * jnp.dot(y, alpha, precision=HIGHEST)
            + jnp.sum(jnp.log(jnp.diagonal(chol)) * mask)
            + 0.5 * n * jnp.log(2.0 * jnp.pi))


@partial(jax.jit, static_argnames=("steps", "noise"))
def _fit_batched(x, y, mask, steps: int = 120, noise: float = 0.1,
                 lr: float = 0.05):
    d = x.shape[-1]

    def one(xi, yi, mi):
        def loss(p):
            return _masked_nlml(GPParams(p["ls"], p["sf"], noise),
                                xi, yi, mi)
        return _adam_nlml(loss, d, steps, lr)

    return jax.vmap(one)(x, y, mask)


@partial(jax.jit, static_argnames=("noise",))
def _batched_chol_alpha(log_ls, log_sf, x, y, mask, noise: float):
    def one(ls, sf, xi, yi, mi):
        n_max = xi.shape[0]
        params = GPParams(ls, sf, noise)
        k = _kernel(params, xi, xi) * (mi[:, None] * mi[None, :])
        k = k + (noise + JITTER) * jnp.eye(n_max) + jnp.diag(1.0 - mi)
        chol = jnp.linalg.cholesky(k)
        alpha = jax.scipy.linalg.cho_solve((chol, True), yi)
        return chol, alpha

    return jax.vmap(one)(log_ls, log_sf, x, y, mask)


def _pack_fit_lanes(xs, ys, ns, nm: int):
    """Host-side lane packing + vectorised target standardisation.

    Packs ragged ``(x_i, y_i)`` models into padded ``(m, nm, d)`` /
    ``(m, nm)`` float32 arrays with a validity mask and standardises
    every lane's targets in one shot: per-lane mean/std are accumulated
    in float64 over the masked rows (padding is exact — pad entries are
    zero and excluded by count), then cast to float32 for the same
    ``(y - mu) / sd`` the per-lane path applied. This replaces the old
    per-model ``jnp.mean``/``jnp.std`` loop, which paid m blocking
    device round-trips per fit call; values shift by at most ~1 ulp
    (f64 vs f32 accumulation order), within every consumer's tolerance.
    Shared by ``fit_gp_batched`` and the plan executor's fit leg, so
    both launches see bitwise-identical packing."""
    m = len(xs)
    d = int(np.shape(xs[0])[1])
    x = np.zeros((m, nm, d), np.float32)
    yr = np.zeros((m, nm), np.float32)
    mask = np.zeros((m, nm), np.float32)
    for i, (xi, yi) in enumerate(zip(xs, ys)):
        n = ns[i]
        x[i, :n] = np.asarray(xi, np.float32)
        yr[i, :n] = np.asarray(yi, np.float32)
        mask[i, :n] = 1.0
    cnt = np.asarray(ns, np.float64)
    mu = yr.sum(axis=1, dtype=np.float64) / cnt
    sq = ((yr - mu[:, None]) * mask) ** 2
    sd = np.maximum(np.sqrt(sq.sum(axis=1, dtype=np.float64) / cnt), 1e-8)
    y_mean = mu.astype(np.float32)
    y_std = sd.astype(np.float32)
    ysd = ((yr - y_mean[:, None]) / y_std[:, None]) * mask
    return x, ysd, mask, y_mean, y_std


def fit_gp_batched(xs: Sequence[np.ndarray], ys: Sequence[np.ndarray], *,
                   noise: float = 0.1, steps: int = 120,
                   n_max: Optional[int] = None, round_to: int = 1,
                   m_round_pow2: bool = False, lane_round_to: int = 1,
                   launches=None) -> BatchedGP:
    """Fit m GPs in one vmapped Adam/Cholesky pass.

    ``xs[i]``: (n_i, d), ``ys[i]``: (n_i,). All models must share d (and
    the fixed noise); n_i may differ — shorter models are zero-padded to
    ``n_max``. ``round_to`` rounds the pad length up to a multiple so jit
    shapes stay stable while a search's observation count grows (padding
    never changes results — masked rows carry unit diagonals).

    ``m_round_pow2`` pads the MODEL dimension to the next power of two by
    repeating model 0; models ``>= m`` are throwaway lanes. Callers whose
    cohort size varies step to step (an async ``SearchService``, where
    whichever sessions' profiling runs landed form the batch) use this so
    the vmapped fit compiles once per bucket instead of once per cohort
    size. Real models' results are unaffected: vmap lanes are
    independent.

    ``lane_round_to`` additionally rounds the model dimension up to a
    multiple (applied after the pow2 rounding) so the lane axis divides a
    ``shard_map`` mesh evenly; ``launches`` optionally substitutes a
    ``(fit, chol_alpha)`` pair of launch twins for the default jitted
    ones — ``sharded_fit_launches`` builds the shard-mapped pair."""
    m = len(xs)
    if m == 0 or m != len(ys):
        raise ValueError("fit_gp_batched needs >=1 model and len(xs)==len(ys)")
    if m_round_pow2:
        target = 1 << (m - 1).bit_length()
        xs = list(xs) + [xs[0]] * (target - m)
        ys = list(ys) + [ys[0]] * (target - m)
        m = target
    if lane_round_to > 1 and m % lane_round_to:
        target = ((m + lane_round_to - 1) // lane_round_to) * lane_round_to
        xs = list(xs) + [xs[0]] * (target - m)
        ys = list(ys) + [ys[0]] * (target - m)
        m = target
    d = int(np.shape(xs[0])[1])
    ns = [int(np.shape(y)[0]) for y in ys]
    nm = max(ns) if n_max is None else int(n_max)
    if nm < max(ns):
        raise ValueError(f"n_max={nm} < largest model ({max(ns)})")
    if round_to > 1:
        nm = ((nm + round_to - 1) // round_to) * round_to

    x, ysd, mask, y_mean, y_std = _pack_fit_lanes(xs, ys, ns, nm)

    xj = jnp.asarray(x)
    yj = jnp.asarray(ysd)
    mj = jnp.asarray(mask)
    fit_fn, ca_fn = ((_fit_batched, _batched_chol_alpha)
                     if launches is None else launches)
    p = fit_fn(xj, yj, mj, steps=steps, noise=noise)
    chol, alpha = ca_fn(p["ls"], p["sf"], xj, yj, mj, noise)
    return BatchedGP(xj, yj, mj, jnp.asarray(y_mean), jnp.asarray(y_std),
                     p["ls"], p["sf"], noise, chol, alpha,
                     jnp.asarray(ns, jnp.int32))


# ---------------------------------------------------------------------------
# Shard-mapped fit twins: the vmapped Adam fit + Cholesky refresh split
# over a mesh's data axis (lanes are independent models, so data-parallel
# splitting is exact). Minted once per (mesh, axis) and registered with
# ``launch.compile_stats`` so the compile-once accounting covers them.
# ---------------------------------------------------------------------------

_SHARDED_FIT: dict = {}


def sharded_fit_launches(mesh, axis: str = "data"):
    """``(fit, chol_alpha)`` launch twins of ``_fit_batched`` /
    ``_batched_chol_alpha`` running under ``shard_map`` over ``axis``.

    Per-lane math is untouched — each device fits its slice of the model
    stack with the same vmapped program, so results match the unsharded
    launch up to float roundoff (XLA fuses the per-shard batch size
    differently, nothing more). ``lr`` is lifted to a static argname:
    ``shard_map`` bodies cannot close over tracers, and the fit's
    learning rate is a config constant, never a traced value."""
    key = (mesh, axis)
    hit = _SHARDED_FIT.get(key)
    if hit is not None:
        return hit
    from jax.sharding import PartitionSpec

    from repro.distributed import mesh_axis_size, shard_map
    from repro.launch.compile_stats import register_launch
    spec = PartitionSpec(axis)

    @partial(jax.jit, static_argnames=("steps", "noise", "lr"))
    def fit(x, y, mask, steps: int = 120, noise: float = 0.1,
            lr: float = 0.05):
        body = partial(_fit_batched.__wrapped__, steps=steps, noise=noise,
                       lr=lr)
        return shard_map(body, mesh, in_specs=(spec,) * 3, out_specs=spec,
                         check_vma=False)(x, y, mask)

    @partial(jax.jit, static_argnames=("noise",))
    def chol_alpha(log_ls, log_sf, x, y, mask, noise: float):
        body = partial(_batched_chol_alpha.__wrapped__, noise=noise)
        return shard_map(body, mesh, in_specs=(spec,) * 5, out_specs=spec,
                         check_vma=False)(log_ls, log_sf, x, y, mask)

    size = mesh_axis_size(mesh, axis)
    register_launch(f"fit_sharded_x{size}_{len(_SHARDED_FIT)}", fit)
    register_launch(f"chol_alpha_sharded_x{size}_{len(_SHARDED_FIT)}",
                    chol_alpha)
    pair = (fit, chol_alpha)
    _SHARDED_FIT[key] = pair
    return pair


def stack_gps(gps: Sequence[GP], n_max: Optional[int] = None, *,
              round_to: int = 1) -> BatchedGP:
    """Stack already-fitted GPs into a BatchedGP without refitting — the
    padded Cholesky is assembled block-diagonally from each model's own
    factor, so posteriors are bit-identical to the unbatched ones.
    ``round_to`` rounds the padded length up to a multiple (same
    jit-shape bucketing as ``fit_gp_batched``), so stacks built at
    different data sizes land on shared query-plan pad shapes."""
    if not gps:
        raise ValueError("stack_gps needs >=1 model")
    d = int(gps[0].x.shape[1])
    noise = float(gps[0].params.noise)
    ns = [g.n for g in gps]
    nm = max(ns) if n_max is None else int(n_max)
    if round_to > 1:
        nm = ((nm + round_to - 1) // round_to) * round_to
    m = len(gps)

    x = np.zeros((m, nm, d), np.float32)
    y = np.zeros((m, nm), np.float32)
    mask = np.zeros((m, nm), np.float32)
    chol = np.zeros((m, nm, nm), np.float32)
    alpha = np.zeros((m, nm), np.float32)
    ls = np.zeros((m, d), np.float32)
    sf = np.zeros((m,), np.float32)
    y_mean = np.zeros((m,), np.float32)
    y_std = np.zeros((m,), np.float32)
    pad_diag = float(np.sqrt(1.0 + noise + JITTER))
    for i, g in enumerate(gps):
        n = ns[i]
        x[i, :n] = np.asarray(g.x)
        y[i, :n] = np.asarray(g.y)
        mask[i, :n] = 1.0
        chol[i, :n, :n] = np.asarray(g.chol)
        for j in range(n, nm):
            chol[i, j, j] = pad_diag
        alpha[i, :n] = np.asarray(g.alpha)
        ls[i] = np.asarray(g.params.log_lengthscales)
        sf[i] = np.asarray(g.params.log_signal)
        y_mean[i] = float(g.y_mean)
        y_std[i] = float(g.y_std)
    return BatchedGP(jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask),
                     jnp.asarray(y_mean), jnp.asarray(y_std),
                     jnp.asarray(ls), jnp.asarray(sf), noise,
                     jnp.asarray(chol), jnp.asarray(alpha),
                     jnp.asarray(ns, jnp.int32))


@partial(jax.jit, static_argnames=("impl",))
def _batched_posterior(log_ls, log_sf, x, mask, chol, alpha, xq,
                       impl: str = "xla"):
    def one(ls, sf, xi, mi, ci, ai, xqi):
        params = GPParams(ls, sf, 0.0)
        ks = _kernel(params, xqi, xi, impl=impl) * mi[None, :]  # (q, n_max)
        mu = jnp.dot(ks, ai, precision=HIGHEST)
        v = jax.scipy.linalg.solve_triangular(ci, ks.T, lower=True)
        var = jnp.maximum(jnp.exp(sf) - jnp.sum(v * v, axis=0), 1e-10)
        return mu, var

    return jax.vmap(one)(log_ls, log_sf, x, mask, chol, alpha, xq)


# Donating twin: the plan executor rebuilds the stacked observation-
# cache buffers (x, mask, chol, alpha, grid) every step, so on backends
# where the executor pins donation they are handed back to XLA for the
# solve intermediates. Hyperparameter rows stay un-donated (tiny, and
# shared with the watcher's bucket accounting).
_batched_posterior_donated = jax.jit(
    _batched_posterior.__wrapped__, static_argnames=("impl",),
    donate_argnums=(2, 3, 4, 5, 6))


def batched_posterior(bgp: BatchedGP, xq: jnp.ndarray, *, impl: str = "xla"
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Posterior mean/variance of every model, standardised scale.

    xq: (q, d) shared across models, or (m, q, d) per-model. Returns
    ((m, q), (m, q)). One vmapped triangular solve instead of m calls;
    ``impl`` dispatches the pairwise Matern to Pallas where it wins
    (``"auto"`` resolves on the fused models x grid x obs cell count)."""
    xq = jnp.asarray(xq, jnp.float32)
    if xq.ndim == 2:
        xq = jnp.broadcast_to(xq[None], (bgp.m,) + xq.shape)
    impl = resolve_impl(impl, cells=bgp.m * xq.shape[1] * bgp.n_max)
    return _batched_posterior(bgp.log_lengthscales, bgp.log_signal, bgp.x,
                              bgp.mask, bgp.chol, bgp.alpha, xq, impl=impl)


# ---------------------------------------------------------------------------
# Posterior query plan: MANY stacks' grid posteriors in one padded launch
# ---------------------------------------------------------------------------


def _pad_stack_obs(st: BatchedGP, n_pad: int):
    """Pad one stack's observation axis to ``n_pad``: zero rows masked
    out of the kernel, unit diagonal on the padded Cholesky block — the
    same exactness contract ``fit_gp_batched``/``stack_gps`` already
    guarantee, so fused results match per-stack ones."""
    p = n_pad - st.n_max
    if p == 0:
        return st.x, st.mask, st.chol, st.alpha
    x = jnp.pad(st.x, ((0, 0), (0, p), (0, 0)))
    mask = jnp.pad(st.mask, ((0, 0), (0, p)))
    chol = jnp.pad(st.chol, ((0, 0), (0, p), (0, p)))
    bump = jnp.concatenate([jnp.zeros((st.n_max,), jnp.float32),
                            jnp.ones((p,), jnp.float32)])
    chol = chol + jnp.diag(bump)[None]
    alpha = jnp.pad(st.alpha, ((0, 0), (0, p)))
    return x, mask, chol, alpha


def batched_posterior_multi(
    queries, *,
    impl: str = "auto", round_to: Optional[int] = None,
    m_round_pow2: Optional[bool] = None,
    counters: Optional[dict] = None,
) -> List[Tuple[jnp.ndarray, jnp.ndarray]]:
    """Execute MANY ``(stack, grid)`` posterior queries as ONE padded
    ``_batched_posterior`` launch per (q, d) bucket.

    Thin wrapper over the query-plan layer (``core.plan``): each tuple
    becomes a ``PosteriorQuery`` node and the ``StepPlanner`` /
    ``PlanExecutor`` own all bucketing and padding — target GPs, every
    RGPE ensemble's support stack, and MOO objective/constraint models
    become lanes of the same vmapped triangular solve instead of
    separate Python-loop launches. ``round_to`` / ``m_round_pow2``
    default to the planner's policy (observation axis to multiples of
    8, fused model axis to a power of two).

    Returns one ``(mu, var)`` pair per query, shapes ``(m_i, q)``, in
    input order. ``counters`` (optional dict) is incremented with
    ``launches`` / ``queries`` / ``lanes`` for callers tracking fusion.
    """
    from .plan import (PlanExecutor, PosteriorQuery, StepPlanner,
                       flatten_counters)
    planner = StepPlanner(obs_round_to=round_to, m_round_pow2=m_round_pow2)
    nested: dict = {}
    results = PlanExecutor(impl=impl).execute(
        planner.plan([PosteriorQuery(st, xq) for st, xq in queries]),
        counters=nested)
    flatten_counters(nested, counters, ("posterior",))
    return results


def batched_sample(bgp: BatchedGP, xq: jnp.ndarray, keys: jax.Array,
                   n_samples: int, *, impl: str = "xla") -> jnp.ndarray:
    """(m, n_samples, q) marginal-posterior draws; ``keys`` is one PRNG
    key per model (so draws match per-model ``gp_sample`` exactly)."""
    mu, var = batched_posterior(bgp, xq, impl=impl)
    q = mu.shape[1]
    eps = jax.vmap(lambda k: jax.random.normal(k, (n_samples, q)))(keys)
    return mu[:, None, :] + eps * jnp.sqrt(var)[:, None, :]


# ---------------------------------------------------------------------------
# Sample query plan: MANY stacks' posterior draws in one padded launch
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("impl",))
def _batched_sample_launch(log_ls, log_sf, x, mask, chol, alpha, xq, eps,
                           impl: str = "xla"):
    """Posterior + affine draw combine for all fused lanes in one
    launch. ``eps`` carries the per-lane N(0,1) draws, generated OUTSIDE
    at each query's exact (S, q) shape and zero-padded on the grid axis
    here — so the grid padding that keeps this program's jit shapes
    stable across steps can never perturb a lane's PRNG stream."""
    mu, var = _batched_posterior(log_ls, log_sf, x, mask, chol, alpha, xq,
                                 impl=impl)
    return mu[:, None, :] + eps * jnp.sqrt(var)[:, None, :]


# Donating twin of the sample launch: same buffers as the posterior
# twin plus the per-step eps tensor (drawn fresh each round, never
# session-cached, so always safe to hand back).
_batched_sample_launch_donated = jax.jit(
    _batched_sample_launch.__wrapped__, static_argnames=("impl",),
    donate_argnums=(2, 3, 4, 5, 6, 7))


def batched_sample_multi(
    queries, *,
    impl: str = "auto", round_to: Optional[int] = None,
    q_round_to: Optional[int] = None,
    m_round_pow2: Optional[bool] = None,
    counters: Optional[dict] = None,
) -> List[jnp.ndarray]:
    """Execute MANY ``(stack, grid, keys, n_samples)`` posterior-sample
    draws as ONE padded ``_batched_sample_launch`` per (S, q, d) bucket.

    The sample-side twin of ``batched_posterior_multi`` and likewise a
    thin wrapper over the query-plan layer (each tuple becomes a
    ``SampleQuery`` node; all bucketing/padding policy lives in
    ``core.plan.StepPlanner``). Exact-padding contract: the observation
    axis pads to a ``round_to`` bucket (masked rows, unit Cholesky
    diagonal), the GRID axis to a ``q_round_to`` bucket (edge-repeated
    rows whose draws are sliced off — posterior columns are
    independent, so real columns are untouched), and the fused model
    axis to a power of two by repeating lane 0 (throwaway lanes). Draw
    streams are untouched by fusion OR padding: lane i consumes
    ``normal(keys[i], (S, q))`` at the exact query shape, just as
    ``batched_sample`` does.

    Returns one ``(m_i, n_samples, q)`` array per query, in input order.
    ``counters`` (optional dict) is incremented with ``launches`` /
    ``queries`` / ``lanes`` for callers tracking fusion.
    """
    from .plan import (PlanExecutor, SampleQuery, StepPlanner,
                       flatten_counters)
    planner = StepPlanner(obs_round_to=round_to, q_round_to=q_round_to,
                          m_round_pow2=m_round_pow2)
    nested: dict = {}
    results = PlanExecutor(impl=impl).execute(
        planner.plan([SampleQuery(st, xq, keys, ns)
                      for st, xq, keys, ns in queries]),
        counters=nested)
    flatten_counters(nested, counters, ("sample",))
    return results


@jax.jit
def _batched_loo_launch(chol, alpha, y, eps):
    """Closed-form LOO posterior + draws for stacked targets. chol:
    (J, n_pad, n_pad) block-diagonally padded (unit diagonal on the pad
    block, so the valid block's inverse is exact); alpha/y: (J, n_pad)
    zero-padded; eps: (J, S, n_pad), exact-shape draws zero-padded."""
    n_pad = chol.shape[1]

    def one(ci, ai, yi):
        kinv = jax.scipy.linalg.cho_solve((ci, True), jnp.eye(n_pad))
        kd = jnp.diagonal(kinv)
        return yi - ai / kd, jnp.maximum(1.0 / kd, 1e-10)

    mu, var = jax.vmap(one)(chol, alpha, y)
    return mu[:, None, :] + eps * jnp.sqrt(var)[:, None, :]


# Donating twin: every LOO argument is stacked fresh per scoring round
# (jnp.stack always copies), so all four may be donated.
_batched_loo_launch_donated = jax.jit(
    _batched_loo_launch.__wrapped__, donate_argnums=(0, 1, 2, 3))


def loo_sample_multi(
    queries, *,
    round_to: Optional[int] = None, counters: Optional[dict] = None,
) -> List[jnp.ndarray]:
    """MANY targets' leave-one-out posterior draws (``gp_loo_samples``)
    as ONE ``_batched_loo_launch`` per (S, n) bucket — the last
    per-ensemble draw of an RGPE scoring round joins the sample query
    plan (each ``(target, key, n_samples)`` tuple becomes a
    ``LooSampleQuery`` node; bucketing/padding policy lives in
    ``core.plan.StepPlanner``). The observation axis pads to a
    ``round_to`` bucket (unit Cholesky diagonal, so the valid block's
    LOO moments are exact); eps is drawn OUTSIDE at each target's exact
    (S, n) shape, so streams match the per-target path bit for bit.
    Returns one ``(S, n_i)`` array per query, in input order."""
    from .plan import (LooSampleQuery, PlanExecutor, StepPlanner,
                       flatten_counters)
    planner = StepPlanner(obs_round_to=round_to)
    nested: dict = {}
    results = PlanExecutor().execute(
        planner.plan([LooSampleQuery(gp, key, ns)
                      for gp, key, ns in queries]),
        counters=nested)
    flatten_counters(nested, counters, ("loo",))
    return results
