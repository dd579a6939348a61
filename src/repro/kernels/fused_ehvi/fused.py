"""Pallas TPU kernel: fused posterior draws -> box-decomposition EHVI.

The MOO counterpart of ``kernels.fused_posterior``: the (n_obj, S, q)
EHVI bucket of the query plan previously ran as an unjitted draw
combine (one affine per lane) plus the vmapped box launch, with the
(L, D, S, q) raw-scale draw tensor round-tripping through HBM between
them. This kernel keeps one lane x one candidate block resident in
VMEM: per box block and per draw it forms the raw-scale draw row and
accumulates the overlap-volume product of the block's boxes with it,
so peak memory is bounded by (bk, bq) and never by front depth or the
draw count. Every value is 2-D: boxes run along sublanes as (bk, 1)
columns, candidates along lanes as (1, bq) rows, and per-lane scalars
arrive as (L, 1, D) rows.

Grid (L, q_pad // bq): each program owns one MOO lane and one block of
``bq`` candidates. The query plan's exact-padding contract does all the
masking for free: padding boxes have lo = hi = +inf (every overlap
clips to zero), padded candidates carry mu = +inf / var = 0 (their
draws land at +inf and gain nothing), padded objective slots are never
read (the dim loop is static over the real objective count), padded
lanes repeat lane 0 and are discarded by the executor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def _ehvi_kernel(los_ref, his_ref, refs_ref, mu_ref, var_ref, ym_ref,
                 ys_ref, eps_ref, out_ref, *,
                 d: int, s: int, bk: int, nb: int):
    refs = refs_ref[0]                                 # (1, D)
    ym = ym_ref[0]
    ys = ys_ref[0]
    mus = [mu_ref[0, dim:dim + 1, :] for dim in range(d)]      # (1, bq)
    sds = [jnp.sqrt(var_ref[0, dim:dim + 1, :]) for dim in range(d)]

    def block(b, acc):
        start = pl.multiple_of(b * bk, bk)
        los = los_ref[0, pl.ds(start, bk), :]          # (bk, D)
        his = his_ref[0, pl.ds(start, bk), :]

        def draws(i, acc):
            vol = None
            for dim in range(d):
                # raw-scale draw row: the exact affine of
                # core.plan._draw_launch, so fusing the draw into the
                # kernel never changes a lane's stream
                e = eps_ref[0, pl.ds(dim * s + i, 1), :]       # (1, bq)
                p = ((mus[dim] + e * sds[dim]) * ys[:, dim:dim + 1]
                     + ym[:, dim:dim + 1])
                hi = jnp.minimum(his[:, dim:dim + 1],
                                 refs[:, dim:dim + 1])         # (bk, 1)
                w = jnp.clip(hi - jnp.maximum(los[:, dim:dim + 1], p),
                             0.0, None)                        # (bk, bq)
                vol = w if vol is None else vol * w
            return acc + jnp.sum(vol, axis=0, keepdims=True)

        # the wrapper sorts each lane's boxes into staircase order
        # (ascending lo[0]), so +inf padding boxes pool at the tail of
        # the axis: a block whose SMALLEST lo[0] is +inf holds only
        # zero-volume boxes and is skipped outright — deep-padded lanes
        # (the fused bucket pads every lane to the deepest front) pay
        # for their own boxes, not the bucket's
        return jax.lax.cond(
            jnp.min(los[:, 0:1]) < jnp.inf,
            lambda a: jax.lax.fori_loop(0, s, draws, a),
            lambda a: a, acc)

    acc = jax.lax.fori_loop(0, nb, block,
                            jnp.zeros(out_ref.shape[1:], jnp.float32))
    out_ref[0] = acc * (1.0 / s)


def fused_ehvi_pallas(los, his, refs, mu, var, y_mean, y_std, eps, *,
                      block_q: int = 128, block_k: int = 128,
                      interpret: bool = False):
    """(L, q) EHVI rows; arguments exactly as ``fused_ehvi_ref``.

    ``block_q`` x ``block_k`` bound the kernel's VMEM high-water mark
    (the volume intermediate is (block_k, block_q) f32)."""
    l, k, d = los.shape
    s = eps.shape[2]
    q = mu.shape[2]
    bq = min(block_q, q)
    pq = (-q) % bq
    # sublane/lane alignment for the compiled TPU kernel only: the
    # objective axis pads to the f32 sublane tile, the box axis to a
    # sublane-aligned block multiple; padded objective slots are never
    # read and padded boxes are +inf (zero volume) by the plan's contract
    d_pad = _round_up(d, 8) if not interpret else d
    bk = (min(block_k, _round_up(k, 8)) if not interpret
          else min(block_k, k))
    pk = (-k) % bk

    # staircase order: each lane's boxes sorted by ascending lo[0]. The
    # box decomposition is disjoint, so any order sums to the same EHVI
    # (up to float summation order); sorting pools the +inf zero-volume
    # padding boxes at the tail, which turns them into whole blocks the
    # kernel's early-exit predicate can skip
    order = jnp.argsort(los[:, :, 0], axis=-1)         # (L, K)
    los = jnp.take_along_axis(los, order[:, :, None], axis=1)
    his = jnp.take_along_axis(his, order[:, :, None], axis=1)
    if pk:
        los = jnp.pad(los, ((0, 0), (0, pk), (0, 0)),
                      constant_values=jnp.inf)
        his = jnp.pad(his, ((0, 0), (0, pk), (0, 0)),
                      constant_values=jnp.inf)
    if d_pad > d:
        los = jnp.pad(los, ((0, 0), (0, 0), (0, d_pad - d)),
                      constant_values=jnp.inf)
        his = jnp.pad(his, ((0, 0), (0, 0), (0, d_pad - d)),
                      constant_values=jnp.inf)
        refs = jnp.pad(refs, ((0, 0), (0, d_pad - d)))
        mu = jnp.pad(mu, ((0, 0), (0, d_pad - d), (0, 0)))
        var = jnp.pad(var, ((0, 0), (0, d_pad - d), (0, 0)))
        y_mean = jnp.pad(y_mean, ((0, 0), (0, d_pad - d)))
        y_std = jnp.pad(y_std, ((0, 0), (0, d_pad - d)))
        eps = jnp.pad(eps, ((0, 0), (0, d_pad - d), (0, 0), (0, 0)))
    if pq:
        mu = jnp.pad(mu, ((0, 0), (0, 0), (0, pq)),
                     constant_values=jnp.inf)
        var = jnp.pad(var, ((0, 0), (0, 0), (0, pq)))
        eps = jnp.pad(eps, ((0, 0), (0, 0), (0, 0), (0, pq)))
    k_pad, q_pad = k + pk, q + pq
    eps2 = eps.reshape(l, d_pad * s, q_pad)

    def lane(*shape):
        return pl.BlockSpec((1,) + shape, lambda i, j: (i, 0, 0))

    def cand(rows):
        return pl.BlockSpec((1, rows, bq), lambda i, j: (i, 0, j))

    out = pl.pallas_call(
        functools.partial(_ehvi_kernel, d=d, s=s, bk=bk, nb=k_pad // bk),
        grid=(l, q_pad // bq),
        in_specs=[lane(k_pad, d_pad), lane(k_pad, d_pad), lane(1, d_pad),
                  cand(d_pad), cand(d_pad), lane(1, d_pad), lane(1, d_pad),
                  cand(d_pad * s)],
        out_specs=cand(1),
        out_shape=jax.ShapeDtypeStruct((l, 1, q_pad), jnp.float32),
        interpret=interpret,
    )(los, his, refs.reshape(l, 1, d_pad), mu, var,
      y_mean.reshape(l, 1, d_pad), y_std.reshape(l, 1, d_pad), eps2)
    return out[:, 0, :q]
