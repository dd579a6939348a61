"""Dispatcher for pairwise Pearson correlation."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..routing import resolve_impl
from .ref import pairwise_pearson_ref
from .pairwise_pearson import _pearson_kernel


def _pallas(a, b, *, block: int = 256, interpret: bool = False):
    m, d = a.shape
    n, _ = b.shape
    bm, bn = min(block, m), min(block, n)
    pm, pn = (-m) % bm, (-n) % bn
    pd = (-d) % 128 if not interpret else 0
    if pm or pd:
        a = jnp.pad(a, ((0, pm), (0, pd)))
    if pn or pd:
        b = jnp.pad(b, ((0, pn), (0, pd)))
    out = pl.pallas_call(
        functools.partial(_pearson_kernel, d_valid=d),
        grid=((m + pm) // bm, (n + pn) // bn),
        in_specs=[
            pl.BlockSpec((bm, a.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, b.shape[1]), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + pm, n + pn), jnp.float32),
        interpret=interpret,
    )(a, b)
    return out[:m, :n]


def pairwise_pearson(a: jnp.ndarray, b: jnp.ndarray, *, impl: str = "xla"
                     ) -> jnp.ndarray:
    if impl == "auto":
        impl = resolve_impl(impl, cells=a.shape[0] * b.shape[0])
    if impl == "xla":
        return pairwise_pearson_ref(a, b)
    if impl == "pallas":
        return _pallas(a, b, interpret=False)
    if impl == "pallas_interpret":
        return _pallas(a, b, interpret=True)
    raise ValueError(f"unknown pairwise_pearson impl {impl!r}")


@functools.partial(jax.jit, static_argnames=("impl",))
def _pearson_launch(a, b, impl: str = "xla"):
    """The jitted (tracked) entry for pairwise Pearson — part of the
    compile-once launch vocabulary (``launch.compile_stats``). A
    ``SearchService`` step scores every karasu tenant's target runs
    against the candidate index in one such launch; callers pass a
    concrete ``impl`` and pad both row axes (``core.plan.round_rows``,
    ``CAND_ROUND_TO``), so the shape set is closed by the cohort
    bounds. All-zero pad rows correlate 0 with everything."""
    return pairwise_pearson(a, b, impl=impl)
