"""Dispatcher for the RGPE ranking loss."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..routing import resolve_impl
from .ref import ranking_loss_padded_ref, ranking_loss_ref
from .ranking_loss import _rank_kernel, _rank_padded_kernel


def _pallas(preds: jnp.ndarray, y: jnp.ndarray, *, block_s: int = 128,
            interpret: bool = False) -> jnp.ndarray:
    s, n = preds.shape
    bs = min(block_s, s)
    ps = (-s) % bs
    pn = (-n) % 128 if not interpret else 0
    if ps or pn:
        preds = jnp.pad(preds, ((0, ps), (0, pn)))
    yp = jnp.pad(y, (0, pn))[None, :] if pn else y[None, :]
    out = pl.pallas_call(
        functools.partial(_rank_kernel, n_valid=n),
        grid=((s + ps) // bs,),
        in_specs=[
            pl.BlockSpec((bs, preds.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((1, yp.shape[1]), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bs, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s + ps, 1), jnp.int32),
        interpret=interpret,
    )(preds, yp)
    return out[:s, 0]


def ranking_loss(preds: jnp.ndarray, y: jnp.ndarray, *, impl: str = "xla"
                 ) -> jnp.ndarray:
    if impl == "auto":
        impl = resolve_impl(impl, cells=preds.shape[0] * preds.shape[1] ** 2)
    if impl == "xla":
        return ranking_loss_ref(preds, y)
    if impl == "pallas":
        return _pallas(preds, y, interpret=False)
    if impl == "pallas_interpret":
        return _pallas(preds, y, interpret=True)
    raise ValueError(f"unknown ranking_loss impl {impl!r}")


def _pallas_padded(preds: jnp.ndarray, ys: jnp.ndarray,
                   n_valid: jnp.ndarray, *, block_s: int = 128,
                   interpret: bool = False) -> jnp.ndarray:
    r, n = preds.shape
    bs = min(block_s, r)
    pr = (-r) % bs
    pn = (-n) % 128 if not interpret else 0
    if pr or pn:
        # padding rows get n_valid = 0 below, so they count zero pairs
        preds = jnp.pad(preds, ((0, pr), (0, pn)))
        ys = jnp.pad(ys, ((0, pr), (0, pn)))
    nv = jnp.pad(jnp.asarray(n_valid, jnp.int32), (0, pr))[:, None]
    out = pl.pallas_call(
        _rank_padded_kernel,
        grid=((r + pr) // bs,),
        in_specs=[
            pl.BlockSpec((bs, preds.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((bs, ys.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((bs, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bs, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r + pr, 1), jnp.int32),
        interpret=interpret,
    )(preds, ys, nv)
    return out[:r, 0]


def ranking_loss_padded(preds: jnp.ndarray, ys: jnp.ndarray,
                        n_valid: jnp.ndarray, *, impl: str = "xla"
                        ) -> jnp.ndarray:
    """Ragged-batch entry point: (R, n_max) samples with per-row targets
    and valid lengths -> (R,) misrank counts. One launch scores every
    (tenant, measure) ensemble of a SearchService step."""
    if impl == "auto":
        impl = resolve_impl(impl, cells=preds.shape[0] * preds.shape[1] ** 2)
    if impl == "xla":
        return ranking_loss_padded_ref(preds, ys, n_valid)
    if impl == "pallas":
        return _pallas_padded(preds, ys, n_valid, interpret=False)
    if impl == "pallas_interpret":
        return _pallas_padded(preds, ys, n_valid, interpret=True)
    raise ValueError(f"unknown ranking_loss impl {impl!r}")


@functools.partial(jax.jit, static_argnames=("impl",))
def _ranking_loss_launch(preds, ys, n_valid, impl: str = "xla"):
    """The jitted (tracked) entry for the padded ranking loss — part of
    the compile-once launch vocabulary (``launch.compile_stats``).
    Callers pad the row axis to the planner's lane policy and the
    sample axis to the observation policy before dispatch, so the shape
    set is closed by the cohort bounds."""
    return ranking_loss_padded(preds, ys, n_valid, impl=impl)


_ranking_loss_launch_donated = jax.jit(
    _ranking_loss_launch.__wrapped__, static_argnames=("impl",),
    donate_argnums=(2,))


def ranking_loss_launch_fn(donate=None):
    """Donating twin on TPU by default. Only ``n_valid`` is donated:
    it matches the (R,) int32 output buffer exactly, while the float32
    sample matrices can never be reused for an int32 result (donating
    them would only trigger unusable-donation warnings). The counts
    are a fresh per-step stack, rebuilt before each scoring round, so
    the donation is unconditionally alias-safe."""
    if donate is None:
        donate = jax.default_backend() == "tpu"
    return _ranking_loss_launch_donated if donate else _ranking_loss_launch
