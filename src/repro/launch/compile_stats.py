"""Jit-cache compile accounting for the query-plan launch vocabulary.

The compile-once steady state is a claim about a FINITE set of jitted
launch functions: the fused fit, the per-kind plan launches (each with
its buffer-donating twin), the fused posterior / fused EHVI kernels,
the support-model fit, and the select phase's Pearson and key launches. This module registers exactly that set and
counts their compiles via jit-cache sizes, so a service can assert
"zero recompiles after precompile" instead of hoping for it. The
support fit is unpadded and outside the precompiled vocabulary: it
compiles once per support history length, and a serving step that
fits a support model at a new length counts that miss.

Counting by cache-size delta (rather than a global XLA compile hook) is
deliberate: a step also runs eager ops at genuinely varying shapes —
the remaining-candidate gathers that shrink every iteration, the
unjitted draw combine — whose op-by-op compiles are unavoidable,
cheap, and NOT part of the plan's launch vocabulary. A global counter
could never reach zero; the tracked set can, and a miss in it is
always a real hole in the precompiled bucket vocabulary.

``CompileWatcher`` snapshots the tracked cache sizes and reports the
delta; ``SearchService`` wraps each ``step`` in one to expose
``plan_compile_misses``, and ``precompile`` uses another to report how
many compiles warming the vocabulary actually cost.
"""
from __future__ import annotations

import os
from typing import Dict

import jax

# <checkout>/src/repro/launch/compile_stats.py -> <checkout>
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is
    the directory and nothing here overrides it; otherwise the cache is
    ``<checkout>/.jax_cache`` — one fixed path, since the path is part
    of what a later run must find again. The precompiled bucket
    vocabulary is hundreds of sub-second programs, so the minimum
    compile time for an entry is lifted to zero."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path

# shard-mapped launch twins register here as they are constructed: the
# static vocabulary below is closed, but the sharded twins are minted
# per (mesh, kind, donate) by ``core.plan``/``core.gp`` factories, and
# the steady-state claim must cover them too. Registration is idempotent
# for the SAME function object; re-registering a name with a different
# fn is rejected — the replaced twin's cache entries would vanish from
# the accounting, silently masking real misses (the total can even go
# DOWN). A twin registered mid-step is picked up by watchers constructed
# before it (``CompileWatcher`` re-resolves the tracked set at delta
# time), so its first compiles count as misses — which is exactly
# right, they ARE serving-time compiles.
_DYNAMIC: Dict[str, object] = {}

_STATIC_NAMES = frozenset({
    "fit", "chol_alpha", "posterior", "posterior_donated", "sample",
    "sample_donated", "loo", "loo_donated", "ehvi", "ehvi_donated",
    "fused_posterior", "fused_posterior_donated", "fused_ehvi",
    "fused_ehvi_donated", "fused_fit", "fused_fit_donated",
    "ranking_loss", "ranking_loss_donated", "support_fit", "pearson",
    "derive_keys"})


def register_launch(name: str, fn) -> None:
    """Track a dynamically-minted jitted launch (a sharded twin) in the
    compile-once accounting alongside the static vocabulary.
    Idempotent per (name, fn); a name collision with a DIFFERENT
    function raises — it would corrupt the miss accounting."""
    if name in _STATIC_NAMES:
        raise ValueError(
            f"launch name {name!r} shadows the static vocabulary")
    prev = _DYNAMIC.get(name)
    if prev is not None and prev is not fn:
        raise ValueError(
            f"launch {name!r} is already registered with a different "
            f"function; re-registration would drop its "
            f"{_cache_size(prev)} counted cache entries and corrupt "
            f"the compile-miss accounting — pick a unique name")
    _DYNAMIC[name] = fn


def tracked_launches() -> Dict[str, object]:
    """name -> jitted launch fn, lazily imported (this module must stay
    importable before the heavy model modules are)."""
    from repro.core import acquisition, bo, gp
    from repro.kernels.fused_ehvi import ops as fused_ehvi_ops
    from repro.kernels.fused_fit import ops as fused_fit_ops
    from repro.kernels.fused_posterior import ops as fused_ops
    from repro.kernels.pairwise_pearson import ops as pearson_ops
    from repro.kernels.ranking_loss import ops as ranking_ops

    return {
        **_DYNAMIC,
        "fit": gp._fit_batched,
        "chol_alpha": gp._batched_chol_alpha,
        "posterior": gp._batched_posterior,
        "posterior_donated": gp._batched_posterior_donated,
        "sample": gp._batched_sample_launch,
        "sample_donated": gp._batched_sample_launch_donated,
        "loo": gp._batched_loo_launch,
        "loo_donated": gp._batched_loo_launch_donated,
        "ehvi": acquisition._ehvi_box_launch,
        "ehvi_donated": acquisition._ehvi_box_launch_donated,
        "fused_posterior": fused_ops._fused_posterior_launch,
        "fused_posterior_donated": fused_ops._fused_posterior_launch_donated,
        "fused_ehvi": fused_ehvi_ops._fused_ehvi_launch,
        "fused_ehvi_donated": fused_ehvi_ops._fused_ehvi_launch_donated,
        "fused_fit": fused_fit_ops._fused_fit_launch,
        "fused_fit_donated": fused_fit_ops._fused_fit_launch_donated,
        "ranking_loss": ranking_ops._ranking_loss_launch,
        "ranking_loss_donated": ranking_ops._ranking_loss_launch_donated,
        # the support-model fit ``SupportModelStore`` reaches through
        # ``fit_gp``: unpadded, so each new history length compiles
        "support_fit": gp._fit,
        # the select phase's two step-wide launches: Algorithm 1 for
        # every karasu tenant, and the step's RGPE keys
        "pearson": pearson_ops._pearson_launch,
        "derive_keys": bo._derive_keys_launch,
    }


def _cache_size(fn) -> int:
    size = getattr(fn, "_cache_size", None)
    if not callable(size):
        raise TypeError(
            f"tracked launch {fn!r} has no jit cache to count: its "
            f"compiles would read as zero plan_compile_misses")
    return int(size())


def cache_sizes() -> Dict[str, int]:
    """Per-launch jit-cache entry counts (one entry per compiled
    shape/static-arg combination)."""
    return {name: _cache_size(fn)
            for name, fn in tracked_launches().items()}


def total_cache_size() -> int:
    return sum(cache_sizes().values())


class CompileWatcher:
    """Delta counter over the tracked launch caches: ``misses()`` is
    how many tracked launches compiled since construction (or the last
    ``reset``). Entries are never evicted within a process, so the
    delta is exactly the number of new (shape, static-args) programs.

    The snapshot is PER NAME, and the tracked set is re-resolved at
    delta time: a sharded twin registered mid-step (after this watcher
    was constructed) is attributed in full — its baseline defaults to
    zero — and a launch absent from the delta-time set cannot offset
    other launches' misses the way a single total would."""

    def __init__(self):
        self._base = cache_sizes()

    def delta(self) -> Dict[str, int]:
        """name -> programs that tracked launch compiled since the
        snapshot, for the launches that compiled any."""
        out = {}
        for name, size in cache_sizes().items():
            grew = size - self._base.get(name, 0)
            if grew > 0:
                out[name] = grew
        return out

    def misses(self) -> int:
        return sum(self.delta().values())

    def reset(self) -> None:
        self._base = cache_sizes()
