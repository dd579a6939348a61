"""Pallas TPU kernel: blocked pairwise Pearson correlation matrix.

Grid (m_blocks, n_blocks); each program centres its (bm, d) / (bn, d)
tiles in VMEM, computes the cross-products with one MXU matmul and
normalises on the VPU. The metric vectors are short (18 floats in the
paper's setup) so d is padded to the 128 lane boundary with a validity
mask (padded lanes excluded from means/norms).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pearson_kernel(a_ref, b_ref, o_ref, *, d_valid: int):
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    d = a.shape[1]
    mask = (jnp.arange(d) < d_valid).astype(jnp.float32)[None, :]
    inv = 1.0 / d_valid
    am = jnp.sum(a * mask, axis=1, keepdims=True) * inv
    bm = jnp.sum(b * mask, axis=1, keepdims=True) * inv
    ac = (a - am) * mask
    bc = (b - bm) * mask
    num = jax.lax.dot_general(ac, bc, (((1,), (1,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    an = jnp.sqrt(jnp.sum(ac * ac, axis=1))
    bn = jnp.sqrt(jnp.sum(bc * bc, axis=1))
    den = an[:, None] * bn[None, :]
    o_ref[...] = (num / jnp.maximum(den, 1e-12)).astype(o_ref.dtype)
