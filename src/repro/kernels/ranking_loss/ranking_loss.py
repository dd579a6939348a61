"""Pallas TPU kernel: batched pairwise ranking loss.

At repository scale the RGPE weighting evaluates S x (m+1) models x n^2
pairs; this kernel tiles the MC-sample axis into VMEM blocks of bs
samples and evaluates all n^2 comparisons per block on the VPU, one
observation j at a time: column j of the samples, picked by a masked
lane sum, is compared against the whole (bs, n) tile, so every value
stays 2-D (Mosaic cannot relayout a (bs, n) tile into (bs, n, n)).
n is padded to the lane boundary by the wrapper; padded entries sit
outside every row's valid prefix and count no pair.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _count_rows(p, y, valid, n: int):
    """Misranked valid pairs per row of ``p`` (bs, n) against targets
    ``y`` ((1, n) shared or (bs, n) per row); ``valid``: (bs, n)."""
    col = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)

    def body(j, acc):
        pick = col == j
        pj = jnp.sum(jnp.where(pick, p, 0.0), axis=1, keepdims=True)
        yj = jnp.sum(jnp.where(pick[:y.shape[0]], y, 0.0), axis=1,
                     keepdims=True)
        vj = jnp.sum(jnp.where(pick & valid, 1, 0), axis=1,
                     keepdims=True) > 0                     # (bs, 1)
        xor = jnp.logical_xor(pj < p, yj < y) & valid & vj
        return acc + jnp.sum(xor.astype(jnp.int32), axis=1, keepdims=True)

    return jax.lax.fori_loop(0, n, body,
                             jnp.zeros((p.shape[0], 1), jnp.int32))


def _rank_kernel(p_ref, y_ref, o_ref, *, n_valid: int):
    p = p_ref[...].astype(jnp.float32)          # (bs, n)
    y = y_ref[...].astype(jnp.float32)          # (1, n)
    col = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    o_ref[...] = _count_rows(p, y, col < n_valid, p.shape[1])


def _rank_padded_kernel(p_ref, y_ref, nv_ref, o_ref):
    """Ragged twin of ``_rank_kernel``: every row carries its own target
    vector and valid prefix length, so one launch scores a whole batch of
    heterogeneous (tenant, measure) ensembles. Rows whose n_valid is 0
    (padding rows added by the wrapper) count no pair."""
    p = p_ref[...].astype(jnp.float32)          # (bs, n)
    y = y_ref[...].astype(jnp.float32)          # (bs, n)
    nv = nv_ref[...].astype(jnp.int32)          # (bs, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    o_ref[...] = _count_rows(p, y, col < nv, p.shape[1])
