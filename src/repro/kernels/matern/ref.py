"""Pure-jnp oracle: pairwise Matern-5/2 kernel matrix.

K[i, j] = (1 + sqrt5 r + 5 r^2 / 3) exp(-sqrt5 r),  r = ||a_i - b_j||_2
(inputs are pre-scaled by the ARD lengthscales by the caller).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SQRT5 = 5.0 ** 0.5


def matern52_ref(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    d2 = (jnp.sum(a * a, 1)[:, None] + jnp.sum(b * b, 1)[None, :]
          - 2.0 * jnp.dot(a, b.T, precision=jax.lax.Precision.HIGHEST))
    # epsilon inside the sqrt: keeps the NLML gradient finite at r=0
    r = jnp.sqrt(jnp.maximum(d2, 0.0) + 1e-12)
    return (1.0 + SQRT5 * r + 5.0 / 3.0 * d2) * jnp.exp(-SQRT5 * r)
