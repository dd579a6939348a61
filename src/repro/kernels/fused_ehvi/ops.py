"""Dispatcher for the fused posterior-draw + EHVI bucket kernel.

``fused_ehvi`` takes the padded lanes of one (n_obj, S, q) EHVI bucket
(the exact arrays ``core.plan.PlanExecutor`` assembles when constructed
with ``fused_ehvi=True``) and returns the (L, q) acquisition rows.
``impl`` follows the package convention: ``"xla"`` is the reference
chain, ``"pallas"`` / ``"pallas_interpret"`` the fused kernel, and
``"auto"`` routes through ``kernels.routing.resolve_impl`` on the
launch's work volume (lanes x samples x candidates x boxes — the EHVI
reduction's cost scales with all four, unlike the posterior kernel's
output-cell count).

``_fused_ehvi_launch`` is the jitted entry the plan executor calls;
``_fused_ehvi_launch_donated`` donates every argument — all eight are
rebuilt by the executor each step (stacked box decompositions, gathered
posterior rows, fresh draws), so nothing aliases a session-cached
buffer and XLA may reuse their HBM for the volume intermediates. Which
entry runs is pinned ONCE by the executor (``fused_ehvi_launch_fn``'s
``donate`` argument), so ``SearchService.precompile`` warms exactly the
entry serving dispatches.
"""
from __future__ import annotations

from functools import partial

import jax

from ..routing import resolve_impl
from .fused import fused_ehvi_pallas
from .ref import fused_ehvi_ref


def fused_ehvi(los, his, refs, mu, var, y_mean, y_std, eps, *,
               impl: str = "xla"):
    if impl == "auto":
        impl = resolve_impl(impl, cells=(los.shape[0] * eps.shape[2]
                                         * mu.shape[2] * los.shape[1]))
    if impl == "xla":
        return fused_ehvi_ref(los, his, refs, mu, var, y_mean, y_std, eps)
    if impl == "pallas":
        return fused_ehvi_pallas(los, his, refs, mu, var, y_mean, y_std,
                                 eps, interpret=False)
    if impl == "pallas_interpret":
        return fused_ehvi_pallas(los, his, refs, mu, var, y_mean, y_std,
                                 eps, interpret=True)
    raise ValueError(f"unknown fused_ehvi impl {impl!r}")


@partial(jax.jit, static_argnames=("impl",))
def _fused_ehvi_launch(los, his, refs, mu, var, y_mean, y_std, eps,
                       impl: str = "xla"):
    return fused_ehvi(los, his, refs, mu, var, y_mean, y_std, eps,
                      impl=impl)


_fused_ehvi_launch_donated = jax.jit(
    _fused_ehvi_launch.__wrapped__, static_argnames=("impl",),
    donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7))


def fused_ehvi_launch_fn(donate=None):
    """The jitted launch entry: donating when ``donate`` (default: on a
    TPU backend), plain otherwise. Callers resolve the choice once and
    hold onto it — the plan executor pins it at construction so its
    precompile and its serving dispatch can never disagree."""
    if donate is None:
        donate = jax.default_backend() == "tpu"
    return _fused_ehvi_launch_donated if donate else _fused_ehvi_launch


def ref_twin():
    """The pure-XLA reference body standing in for the Pallas kernel in
    jaxpr-level analysis (``repro.analysis``): same signature, same
    masked-dataflow contract, traceable without a Pallas lowering."""
    return fused_ehvi_ref
