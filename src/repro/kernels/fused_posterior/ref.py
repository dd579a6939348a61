"""Pure-XLA oracle: masked Cholesky-solve -> posterior -> EI, one bucket.

The reference twin of the fused Pallas kernel: for every fused model
lane of a (q, d) posterior bucket it reproduces ``core.gp``'s
``_batched_posterior`` math (pairwise Matern-5/2 cross-kernel masked to
the valid observations, one triangular solve against the model's
Cholesky factor) and then applies ``core.acquisition``'s closed-form
minimisation EI against a per-lane incumbent — exactly the chain of
eager launches the fused kernel collapses into one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SQRT5 = 5.0 ** 0.5
HIGHEST = jax.lax.Precision.HIGHEST
VAR_FLOOR = 1e-12        # must match core.acquisition.VAR_FLOOR
INV_SQRT2 = 2.0 ** -0.5
INV_SQRT_2PI = float(1.0 / jnp.sqrt(2.0 * jnp.pi))


def _matern52(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    d2 = (jnp.sum(a * a, 1)[:, None] + jnp.sum(b * b, 1)[None, :]
          - 2.0 * jnp.dot(a, b.T, precision=HIGHEST))
    r = jnp.sqrt(jnp.maximum(d2, 0.0) + 1e-12)
    return (1.0 + SQRT5 * r + 5.0 / 3.0 * d2) * jnp.exp(-SQRT5 * r)


def _ei(mu: jnp.ndarray, var: jnp.ndarray, best) -> jnp.ndarray:
    sigma = jnp.sqrt(jnp.maximum(var, VAR_FLOOR))
    z = (best - mu) / sigma
    big_phi = 0.5 * (1.0 + jax.lax.erf(z * INV_SQRT2))
    small_phi = jnp.exp(-0.5 * z * z) * INV_SQRT_2PI
    return jnp.maximum(sigma * (z * big_phi + small_phi), 0.0)


def fused_posterior_ei_ref(log_ls, log_sf, x, mask, chol, alpha, xq, best):
    """One (q, d) bucket: (mu, var, ei), each (m, q).

    log_ls (m, d), log_sf (m,), x (m, n, d), mask (m, n),
    chol (m, n, n), alpha (m, n), xq (m, q, d), best (m,).
    """
    def one(ls, sf, xi, mi, ci, ai, xqi, bi):
        scale = jnp.exp(ls)
        ks = (jnp.exp(sf) * _matern52(xqi / scale, xi / scale)
              * mi[None, :])                                   # (q, n)
        mu = jnp.dot(ks, ai, precision=HIGHEST)
        v = jax.scipy.linalg.solve_triangular(ci, ks.T, lower=True)
        var = jnp.maximum(jnp.exp(sf) - jnp.sum(v * v, axis=0), 1e-10)
        return mu, var, _ei(mu, var, bi)

    return jax.vmap(one)(log_ls, log_sf, x, mask, chol, alpha, xq, best)
