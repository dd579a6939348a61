"""Pure-jnp oracle: pairwise Pearson correlation between metric vectors.

R[i, j] = pearsonr(A[i], B[j]) for A (m, d), B (n, d) — the similarity
measure inside Karasu's Algorithm 1 (DIST). A real deployment computes
this over the whole shared repository ("proper indexing and a respective
distance operator", paper §IV-E), which is why it gets a kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def pairwise_pearson_ref(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    ac = a - jnp.mean(a, axis=1, keepdims=True)
    bc = b - jnp.mean(b, axis=1, keepdims=True)
    num = jnp.dot(ac, bc.T, precision=jax.lax.Precision.HIGHEST)
    den = (jnp.sqrt(jnp.sum(ac * ac, axis=1))[:, None]
           * jnp.sqrt(jnp.sum(bc * bc, axis=1))[None, :])
    return num / jnp.maximum(den, 1e-12)
