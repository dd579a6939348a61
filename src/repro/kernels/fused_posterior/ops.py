"""Dispatcher for the fused posterior+EI bucket kernel.

``fused_posterior_ei`` takes the padded lanes of one (q, d) posterior
bucket (the exact arrays ``core.plan.PlanExecutor`` assembles) and
returns ``(mu, var, ei)``, each (m, q). ``impl`` follows the package
convention: ``"xla"`` is the vmapped reference chain, ``"pallas"`` /
``"pallas_interpret"`` the fused kernel, and ``"auto"`` routes through
``kernels.routing.resolve_impl`` on the bucket's output cell count.

``_fused_posterior_launch`` is the jitted entry the plan executor calls
— one compile per bucket shape, so it belongs to the precompilable
launch vocabulary tracked by ``launch.compile_stats``. On TPU the
executor uses ``_fused_posterior_launch_donated`` instead: the stacked
observation-cache buffers (x, mask, chol, alpha, grid, eps-free lanes)
are rebuilt from the sessions' stacks every step, so the launch
donates them and XLA reuses their HBM for the solve intermediates.
CPU/GPU skip donation — those backends cannot alias them and would warn
on every launch.
"""
from __future__ import annotations

from functools import partial

import jax

from ..routing import resolve_impl
from .fused import fused_posterior_ei_pallas
from .ref import fused_posterior_ei_ref


def fused_posterior_ei(log_ls, log_sf, x, mask, chol, alpha, xq, best, *,
                       impl: str = "xla"):
    if impl == "auto":
        impl = resolve_impl(impl,
                            cells=x.shape[0] * xq.shape[1] * x.shape[1])
    if impl == "xla":
        return fused_posterior_ei_ref(log_ls, log_sf, x, mask, chol,
                                      alpha, xq, best)
    if impl == "pallas":
        return fused_posterior_ei_pallas(log_ls, log_sf, x, mask, chol,
                                         alpha, xq, best, interpret=False)
    if impl == "pallas_interpret":
        return fused_posterior_ei_pallas(log_ls, log_sf, x, mask, chol,
                                         alpha, xq, best, interpret=True)
    raise ValueError(f"unknown fused_posterior impl {impl!r}")


@partial(jax.jit, static_argnames=("impl",))
def _fused_posterior_launch(log_ls, log_sf, x, mask, chol, alpha, xq,
                            best, impl: str = "xla"):
    return fused_posterior_ei(log_ls, log_sf, x, mask, chol, alpha, xq,
                              best, impl=impl)


_fused_posterior_launch_donated = jax.jit(
    _fused_posterior_launch.__wrapped__, static_argnames=("impl",),
    donate_argnums=(2, 3, 4, 5, 6))


def fused_launch_fn(donate=None):
    """The jitted launch entry: donating when ``donate`` (default: on a
    TPU backend), plain otherwise. Callers resolve the choice once and
    hold onto it — the plan executor pins it at construction so its
    precompile and its serving dispatch can never disagree on which
    entry's jit cache gets warmed."""
    if donate is None:
        donate = jax.default_backend() == "tpu"
    return (_fused_posterior_launch_donated if donate
            else _fused_posterior_launch)


def ref_twin():
    """The pure-XLA reference body standing in for the Pallas kernel in
    jaxpr-level analysis (``repro.analysis``): same signature, same
    masked-dataflow contract, traceable without a Pallas lowering."""
    return fused_posterior_ei_ref
