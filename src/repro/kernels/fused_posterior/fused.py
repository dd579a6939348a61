"""Pallas TPU kernel: fused masked Cholesky-solve -> posterior -> EI.

The steady-state hot bucket of the query plan is the (q, d) posterior
launch: every fused model lane needs a pairwise Matern-5/2 cross-kernel
against its observations, a triangular solve against its Cholesky
factor, and (for single-objective tenants) the closed-form EI head. XLA
runs that as separate kernels with the (q, n) cross-kernel and the
(n, q) solve round-tripping through HBM; this kernel keeps the whole
chain of one lane x one query block resident in VMEM.

Grid (m, q_blocks): each program owns one model lane and one block of
``bq`` query points. It computes the masked cross-kernel tile, stored
transposed as (n, bq), on the MXU, then runs an in-kernel forward
substitution over the observation axis (n is the small axis of the
bucket — tens, not thousands — so the O(n^2 bq) row recurrence stays in
VMEM as a loop carry), and finishes with mean, variance, and the EI
head on the VPU. Every per-lane vector and scalar arrives as a
(m, 1, x) array, so each block's last two dims equal the array's, and
every in-kernel value is 2-D. Padded observations arrive masked with
unit Cholesky diagonals (the query plan's exact-padding contract), so
padded rows solve to exactly zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..fused_fit.fused import _col_to_row, _row_to_col

SQRT5 = 5.0 ** 0.5
VAR_FLOOR = 1e-12        # must match core.acquisition.VAR_FLOOR
INV_SQRT2 = 2.0 ** -0.5
INV_SQRT_2PI = 0.3989422804014327

# erf(x) ~= x * P(x^2) / Q(x^2) on [-4, 4] (+-1 beyond, to f32): the
# single-precision rational approximation XLA and Eigen use. Mosaic has
# no erf primitive, so the kernel evaluates it on the VPU.
_ERF_P = (-2.72614225801306e-10, 2.77068142495902e-08,
          -2.10102402082508e-06, -5.69250639462346e-05,
          -7.34990630326855e-04, -2.95459980854025e-03,
          -1.60960333262415e-02)
_ERF_Q = (-1.45660718464996e-05, -2.13374055278905e-04,
          -1.68282697438203e-03, -7.37332916720468e-03,
          -1.42647390514189e-02)


def _horner(coeffs, t):
    acc = jnp.full_like(t, coeffs[0])
    for c in coeffs[1:]:
        acc = acc * t + c
    return acc


def _erf(x):
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    return x * _horner(_ERF_P, x2) / _horner(_ERF_Q, x2)


def _fused_kernel(ls_ref, sf_ref, x_ref, mask_ref, chol_ref, alpha_ref,
                  xq_ref, best_ref, mu_ref, var_ref, ei_ref, *, n: int,
                  bq: int):
    inv_scale = jnp.exp(-ls_ref[0])                # (1, d)
    sf = jnp.exp(sf_ref[0])                        # (1, 1)
    x = x_ref[0] * inv_scale                       # (n, d)
    xq = xq_ref[0] * inv_scale                     # (bq, d)
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    eye_n = row_ids == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    eye_q = (jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 0)
             == jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 1))
    mask = _row_to_col(mask_ref[0], eye_n)         # (n, 1)
    alpha = _row_to_col(alpha_ref[0], eye_n)       # (n, 1)

    # masked Matern-5/2 cross-kernel tile, transposed: ks^T (n, bq),
    # distances via one MXU matmul
    ab = jax.lax.dot_general(x, xq, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    d2 = (jnp.sum(x * x, axis=1, keepdims=True)
          + _col_to_row(jnp.sum(xq * xq, axis=1, keepdims=True), eye_q)
          - 2.0 * ab)
    d2 = jnp.maximum(d2, 0.0)
    r = jnp.sqrt(d2 + 1e-12)
    kst = (sf * (1.0 + SQRT5 * r + 5.0 / 3.0 * d2) * jnp.exp(-SQRT5 * r)
           * mask)                                 # (n, bq)

    mu = jnp.sum(kst * alpha, axis=0, keepdims=True)       # (1, bq)

    # forward substitution v = L^{-1} ks^T, one row per iteration: row
    # j only depends on rows < j, and v starts at zero, so the running
    # product L[j, :] @ v picks up exactly the solved prefix
    chol = chol_ref[0]                             # (n, n)
    iota_col = row_ids[:, :1]                      # (n, 1)
    iota_row = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    rows_q = jax.lax.broadcasted_iota(jnp.int32, (n, bq), 0)

    def body(j, v):
        l_row = jnp.sum(jnp.where(iota_col == j, chol, 0.0), axis=0,
                        keepdims=True)             # L[j, :]
        l_jj = jnp.sum(jnp.where(iota_row == j, l_row, 0.0), axis=1,
                       keepdims=True)              # (1, 1)
        acc = jnp.sum(_row_to_col(l_row, eye_n) * v, axis=0,
                      keepdims=True)               # (1, bq)
        k_row = jnp.sum(jnp.where(rows_q == j, kst, 0.0), axis=0,
                        keepdims=True)
        return jnp.where(rows_q == j, (k_row - acc) / l_jj, v)

    v = jax.lax.fori_loop(0, n, body, jnp.zeros((n, bq), jnp.float32))
    var = jnp.maximum(sf - jnp.sum(v * v, axis=0, keepdims=True), 1e-10)

    # closed-form minimisation EI against the per-lane incumbent
    best = best_ref[0]                             # (1, 1)
    sigma = jnp.sqrt(jnp.maximum(var, VAR_FLOOR))
    z = (best - mu) / sigma
    big_phi = 0.5 * (1.0 + _erf(z * INV_SQRT2))
    small_phi = jnp.exp(-0.5 * z * z) * INV_SQRT_2PI
    ei = jnp.maximum(sigma * (z * big_phi + small_phi), 0.0)

    mu_ref[0] = mu
    var_ref[0] = var
    ei_ref[0] = ei


def fused_posterior_ei_pallas(log_ls, log_sf, x, mask, chol, alpha, xq,
                              best, *, block_q: int = 128,
                              interpret: bool = False):
    """(mu, var, ei) for one padded (q, d) bucket, each (m, q)."""
    m, n, d = x.shape
    q = xq.shape[1]
    bq = min(block_q, q)
    pq = (-q) % bq
    # lane-dim alignment for the compiled TPU kernel only: d (kernel
    # tile), n (cross-kernel columns / solve rows) pad to 128; padded
    # coords ride unit lengthscales, padded observations a zero mask and
    # unit Cholesky diagonal — exact by the plan's padding contract
    pd = (-d) % 128 if not interpret else 0
    pn = (-n) % 128 if not interpret else 0
    if pd:
        log_ls = jnp.pad(log_ls, ((0, 0), (0, pd)))
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pd)))
        xq = jnp.pad(xq, ((0, 0), (0, 0), (0, pd)))
    if pn:
        x = jnp.pad(x, ((0, 0), (0, pn), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, pn)))
        alpha = jnp.pad(alpha, ((0, 0), (0, pn)))
        chol = jnp.pad(chol, ((0, 0), (0, pn), (0, pn)))
        bump = jnp.concatenate([jnp.zeros((n,), jnp.float32),
                                jnp.ones((pn,), jnp.float32)])
        chol = chol + jnp.diag(bump)[None]
    if pq:
        xq = jnp.pad(xq, ((0, 0), (0, pq), (0, 0)), mode="edge")
    n_pad, q_pad, d_pad = n + pn, q + pq, d + pd

    def lane(*shape):
        return pl.BlockSpec((1,) + shape, lambda i, j: (i, 0, 0))

    out_spec = pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, j))
    mu, var, ei = pl.pallas_call(
        functools.partial(_fused_kernel, n=n_pad, bq=bq),
        grid=(m, q_pad // bq),
        in_specs=[
            lane(1, d_pad), lane(1, 1), lane(n_pad, d_pad), lane(1, n_pad),
            lane(n_pad, n_pad), lane(1, n_pad),
            pl.BlockSpec((1, bq, d_pad), lambda i, j: (i, j, 0)),
            lane(1, 1),
        ],
        out_specs=[out_spec, out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((m, 1, q_pad), jnp.float32)] * 3,
        interpret=interpret,
    )(log_ls.reshape(m, 1, d_pad), log_sf.reshape(m, 1, 1), x,
      mask.reshape(m, 1, n_pad), chol, alpha.reshape(m, 1, n_pad), xq,
      jnp.asarray(best, jnp.float32).reshape(m, 1, 1))
    return mu[:, 0, :q], var[:, 0, :q], ei[:, 0, :q]
