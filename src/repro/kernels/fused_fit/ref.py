"""Pure-jnp oracle: warm-startable fused GP fit.

One launch fits a whole lane bucket: masked Matern-5/2 kernel +
Cholesky + analytic NLML gradient + Adam update, iterated ``steps``
times from a caller-supplied initial point, then a final factorisation
emitting ``(chol, alpha)`` at the fitted hyperparameters. This is the
XLA twin of the Pallas kernel in ``fused.py`` — same formulas, same
update rule — and the numeric bridge to the legacy autodiff fit
(``core.gp._fit_batched`` + ``_batched_chol_alpha``), which remains
the parity baseline: with a zero initial point the two agree to
<= 1e-4 on every hyperparameter and factor.

The gradient is analytic rather than autodiff so the Pallas kernel can
compute the identical expressions in-core. With

  G = K^{-1} - alpha alpha^T,   K = sf * M(r) * mask_outer + diag

the NLML derivatives are

  d/dlog_sf   = 0.5 * sum(G * K_data)
  d/dlog_ls_k = 2 * diag(Xt^T A Xt)_k - 2 * (Xt^2)^T rowsum(A)
                with A = G * dK/dr2 and Xt = x / ls,

where ``dK/dr2 = -(5/6) sf (1 + sqrt5 * d2/r) exp(-sqrt5 r)`` is the
Matern-5/2 radial derivative (finite at r=0). Masked rows/cols carry
zero mask factors, so padded observations and fully-masked lanes have
exactly zero gradient — params stay at their initial point and the
factorisation degenerates to the unit-diagonal padding contract.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

SQRT5 = 5.0 ** 0.5
JITTER = 1e-6            # matches core.gp.JITTER
R2_SHIFT = 1e-12         # matches kernels.matern sqrt shift
HIGHEST = jax.lax.Precision.HIGHEST


def _masked_kernel_parts(log_ls, log_sf, x, mask, noise):
    """K (full, pad-stabilised), K_data (parameter-dependent block),
    and the radial-derivative matrix P = dK/dr2 — shared between the
    gradient and the final factorisation."""
    n_max = x.shape[0]
    ls = jnp.exp(log_ls)
    sf = jnp.exp(log_sf)
    xt = x / ls
    sq = jnp.sum(xt * xt, axis=1)
    d2 = jnp.maximum(sq[:, None] + sq[None, :]
                     - 2.0 * jnp.dot(xt, xt.T, precision=HIGHEST), 0.0)
    r = jnp.sqrt(d2 + R2_SHIFT)
    e = jnp.exp(-SQRT5 * r)
    mval = (1.0 + SQRT5 * r + 5.0 / 3.0 * d2) * e
    mo = mask[:, None] * mask[None, :]
    kd = sf * mval * mo
    k = kd + (noise + JITTER) * jnp.eye(n_max) + jnp.diag(1.0 - mask)
    # dM/dd2. The (d2 > 0) factor mirrors autodiff through the clamp;
    # the diagonal is excluded EXPLICITLY rather than relying on
    # d2_ii == 0: its analytic contribution is zero (Delta_ii = 0) but
    # when d2_ii rounds to a tiny positive the term1/term2 cancellation
    # in the gradient leaves roundoff residue that Adam's sign
    # normalisation amplifies to O(lr) — an n_obs=1 lane would drift
    # off its warm-start instead of staying put.
    off = ~jnp.eye(n_max, dtype=bool)
    p = jnp.where((d2 > 0.0) & off,
                  -(5.0 / 6.0) * sf * (1.0 + SQRT5 * d2 / r) * e * mo,
                  0.0)
    return k, kd, p, xt


def _masked_nlml_grads(log_ls, log_sf, x, y, mask, noise):
    """Analytic d NLML / d (log_ls, log_sf) over the valid block."""
    n_max = x.shape[0]
    k, kd, p, xt = _masked_kernel_parts(log_ls, log_sf, x, mask, noise)
    chol = jnp.linalg.cholesky(k)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y)
    kinv = jax.scipy.linalg.cho_solve((chol, True), jnp.eye(n_max))
    g = kinv - alpha[:, None] * alpha[None, :]
    g_sf = 0.5 * jnp.sum(g * kd)
    a = g * p
    ra = jnp.sum(a, axis=1)
    term1 = jnp.sum(xt * xt * ra[:, None], axis=0)       # (Xt^2)^T rA
    term2 = jnp.sum(xt * jnp.dot(a, xt, precision=HIGHEST),
                    axis=0)                              # diag(Xt^T A Xt)
    g_ls = 2.0 * term2 - 2.0 * term1
    return g_ls, g_sf


def _fused_fit_one(x, y, mask, init_ls, init_sf, *, steps, noise, lr):
    """One lane: Adam on the analytic NLML gradient from ``init``,
    then the final masked factorisation. The update rule is kept in
    exact lockstep with ``core.gp._adam_nlml``."""
    def body(carry, i):
        ls, sf, m_ls, m_sf, v_ls, v_sf = carry
        g_ls, g_sf = _masked_nlml_grads(ls, sf, x, y, mask, noise)
        m_ls = 0.9 * m_ls + 0.1 * g_ls
        m_sf = 0.9 * m_sf + 0.1 * g_sf
        v_ls = 0.999 * v_ls + 0.001 * g_ls * g_ls
        v_sf = 0.999 * v_sf + 0.001 * g_sf * g_sf
        t = i.astype(jnp.float32) + 1.0
        c1 = 1.0 - 0.9 ** t
        c2 = 1.0 - 0.999 ** t
        ls = ls - lr * (m_ls / c1) / (jnp.sqrt(v_ls / c2) + 1e-8)
        sf = sf - lr * (m_sf / c1) / (jnp.sqrt(v_sf / c2) + 1e-8)
        ls = jnp.clip(ls, -3.0, 3.0)
        sf = jnp.clip(sf, -3.0, 3.0)
        return (ls, sf, m_ls, m_sf, v_ls, v_sf), None

    d = x.shape[-1]
    init = (init_ls, init_sf,
            jnp.zeros((d,)), jnp.zeros(()), jnp.zeros((d,)), jnp.zeros(()))
    (ls, sf, _, _, _, _), _ = jax.lax.scan(body, init, jnp.arange(steps))
    k, _, _, _ = _masked_kernel_parts(ls, sf, x, mask, noise)
    chol = jnp.linalg.cholesky(k)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y)
    return ls, sf, chol, alpha


def fused_fit_ref(x, y, mask, init_ls, init_sf, *,
                  steps: int = 120, noise: float = 0.1, lr: float = 0.05):
    """x: (m, n, d), y/mask: (m, n), init_ls: (m, d), init_sf: (m,)
    -> (log_ls (m, d), log_sf (m,), chol (m, n, n), alpha (m, n))."""
    one = partial(_fused_fit_one, steps=steps, noise=noise, lr=lr)
    return jax.vmap(one)(x, y, mask, init_ls, init_sf)
