"""Pallas TPU kernel: blocked pairwise Matern-5/2 kernel matrix.

At repository scale (Karasu fitting thousands of support GPs, each
posterior evaluated over the full candidate set) the kernel matrix is the
GP hot spot. TPU blocking: grid (m_blocks, n_blocks); each program loads
an (bm, d) x (bn, d) tile pair into VMEM, computes squared distances via
one MXU matmul (-2 a.b^T) plus rank-1 row/col norms, and applies the
Matern-5/2 form on the VPU. d is zero-padded to the 128-lane boundary by
the wrapper; bm=bn=256 keeps the tile working set ~0.5 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SQRT5 = 5.0 ** 0.5


def _matern_kernel(a_ref, b_ref, o_ref):
    a = a_ref[...].astype(jnp.float32)      # (bm, d)
    b = b_ref[...].astype(jnp.float32)      # (bn, d)
    ab = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    d2 = (jnp.sum(a * a, 1)[:, None] + jnp.sum(b * b, 1)[None, :]
          - 2.0 * ab)
    d2 = jnp.maximum(d2, 0.0)
    r = jnp.sqrt(d2 + 1e-12)
    o_ref[...] = ((1.0 + SQRT5 * r + 5.0 / 3.0 * d2)
                  * jnp.exp(-SQRT5 * r)).astype(o_ref.dtype)


def matern52_pallas(a: jnp.ndarray, b: jnp.ndarray, *, block: int = 256,
                    interpret: bool = False) -> jnp.ndarray:
    m, d = a.shape
    n, _ = b.shape
    bm = min(block, m)
    bn = min(block, n)
    pm, pn = (-m) % bm, (-n) % bn
    pd = (-d) % 128 if not interpret else 0
    if pm or pd:
        a = jnp.pad(a, ((0, pm), (0, pd)))
    if pn or pd:
        b = jnp.pad(b, ((0, pn), (0, pd)))
    grid = ((m + pm) // bm, (n + pn) // bn)
    out = pl.pallas_call(
        _matern_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, a.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, b.shape[1]), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + pm, n + pn), jnp.float32),
        interpret=interpret,
    )(a, b)
    return out[:m, :n]
