"""Dispatcher for the fused warm-startable fit bucket kernel.

``fused_fit`` takes the padded lanes of one fit bucket (the exact
arrays ``core.plan.PlanExecutor._exec_fit`` packs) plus per-lane
warm-start hyperparameters and returns ``(log_ls, log_sf, chol,
alpha)`` — everything a ``BatchedGP`` needs beyond the inputs
themselves, in ONE launch per optimizer block instead of the legacy
fit + chol_alpha pair. ``impl`` follows the package convention:
``"xla"`` is the analytic vmapped reference, ``"pallas"`` /
``"pallas_interpret"`` the fused kernel, and ``"auto"`` routes through
``kernels.routing.resolve_impl`` on the per-step kernel-matrix cell
count (callers under a mesh pass their per-shard view via
``resolve_impl(..., shards=)`` before binding ``impl`` statically).

``steps`` is a STATIC schedule length — the warm (short refine) and
cold (full) rungs are distinct entries of the closed launch
vocabulary, enumerated and precompiled like every other bucket shape.

``_fused_fit_launch`` is the jitted entry the plan executor calls. On
TPU it uses ``_fused_fit_launch_donated`` instead: only the per-lane
warm-start rows (``init_ls``, ``init_sf``) are donated — they are
rebuilt from the host-side warm cache every step — while x/y/mask must
stay live because the executor hands them to the ``BatchedGP`` the
posterior legs query afterwards.
"""
from __future__ import annotations

from functools import partial

import jax

from ..routing import resolve_impl
from .fused import fused_fit_pallas
from .ref import fused_fit_ref


def fused_fit(x, y, mask, init_ls, init_sf, *, steps: int = 120,
              noise: float = 0.1, lr: float = 0.05, impl: str = "xla"):
    if impl == "auto":
        impl = resolve_impl(
            impl, cells=x.shape[0] * x.shape[1] * x.shape[1] * steps)
    if impl == "xla":
        return fused_fit_ref(x, y, mask, init_ls, init_sf,
                             steps=steps, noise=noise, lr=lr)
    if impl == "pallas":
        return fused_fit_pallas(x, y, mask, init_ls, init_sf,
                                steps=steps, noise=noise, lr=lr,
                                interpret=False)
    if impl == "pallas_interpret":
        return fused_fit_pallas(x, y, mask, init_ls, init_sf,
                                steps=steps, noise=noise, lr=lr,
                                interpret=True)
    raise ValueError(f"unknown fused_fit impl {impl!r}")


@partial(jax.jit, static_argnames=("steps", "noise", "lr", "impl"))
def _fused_fit_launch(x, y, mask, init_ls, init_sf, steps: int = 120,
                      noise: float = 0.1, lr: float = 0.05,
                      impl: str = "xla"):
    return fused_fit(x, y, mask, init_ls, init_sf, steps=steps,
                     noise=noise, lr=lr, impl=impl)


_fused_fit_launch_donated = jax.jit(
    _fused_fit_launch.__wrapped__,
    static_argnames=("steps", "noise", "lr", "impl"),
    donate_argnums=(3, 4))


def fused_fit_launch_fn(donate=None):
    """The jitted launch entry: donating when ``donate`` (default: on a
    TPU backend), plain otherwise. The plan executor pins the choice at
    construction so precompile and serving warm one jit cache."""
    if donate is None:
        donate = jax.default_backend() == "tpu"
    return _fused_fit_launch_donated if donate else _fused_fit_launch


def ref_twin():
    """The pure-XLA reference body standing in for the Pallas kernel in
    jaxpr-level analysis (``repro.analysis``): same signature, same
    masked-dataflow contract, traceable without a Pallas lowering."""
    return fused_fit_ref
