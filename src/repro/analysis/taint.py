"""Differential taint propagation over a launch body's jaxpr.

The padding contracts this repo lives by ("masked rows cannot change
valid outputs", "throwaway lanes are throwaway") are DATAFLOW claims,
and a jaxpr is the exact dataflow graph the compiler sees. This module
evaluates a launch body's jaxpr equation by equation (mirroring
``jax.core.eval_jaxpr``) carrying a boolean taint mask per value, and
propagates taint DIFFERENTIALLY: for every equation with tainted
inputs, the primitive is re-executed with the tainted positions bumped
(floats by a large delta, bools flipped, ints incremented) and an
output position is tainted iff any bump changes it bitwise.

Differential propagation is what makes mask discipline legible without
a sanitizer whitelist: ``k * mask`` with ``mask == 0`` is bitwise
invariant under any bump of ``k``'s masked entries, so multiplicative
masking, ``where`` selects and structural zeros all sanitize
automatically — while a DROPPED mask shows up as a bitwise diff in the
valid region with no false positives (no dependence means identical
outputs). Two deltas of different sign/magnitude guard against a bump
landing on a fixed point of the op (e.g. clipping).

Higher-order equations (pjit, scan, cond, ...) are probed atomically
through their ``bind``: taint granularity inside them is lost but
soundness of the in/out dependence test is not.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import numpy as np

from jax.extend import core as jax_core

# Two probes per tainted equation: large positive and a sign-flipped,
# non-power-of-two magnitude — a value whose effect survives rounding
# and is unlikely to sit on a fixed point of both probes at once.
DELTAS = (1e3, -37.0)


def _bump(val, taint: np.ndarray, delta: float):
    """Return ``val`` with tainted positions perturbed."""
    v = np.asarray(val)
    t = np.asarray(taint, bool)
    if not t.any():
        return val
    if np.issubdtype(v.dtype, np.floating):
        return jax.numpy.asarray(np.where(t, v + np.asarray(delta, v.dtype),
                                          v))
    if np.issubdtype(v.dtype, np.bool_):
        return jax.numpy.asarray(np.where(t, ~v, v))
    return jax.numpy.asarray(np.where(t, v + 1, v))


def _diff(a, b) -> np.ndarray:
    """Bitwise difference mask; NaN == NaN (a bump that turns one NaN
    into another NaN carries no information)."""
    a = np.asarray(a)
    b = np.asarray(b)
    if np.issubdtype(a.dtype, np.floating):
        both_nan = np.isnan(a) & np.isnan(b)
        return (a != b) & ~both_nan
    return a != b


@dataclasses.dataclass
class TaintResult:
    out_vals: List[Any]
    out_taints: List[np.ndarray]          # aligned with flat outputs
    # per flat output: the producing-eqn chain (primitive names, source
    # to sink) along which taint reached it; [] when untainted
    out_paths: List[List[str]]


def taint_trace(fn: Callable, args: Sequence, taints: Sequence,
                *, deltas: Tuple[float, ...] = DELTAS) -> TaintResult:
    """Trace ``fn(*args)`` to a jaxpr and propagate ``taints`` (one
    boolean mask per argument, True = tainted source) to the flat
    outputs."""
    closed = jax.make_jaxpr(fn)(*args)
    jaxpr, consts = closed.jaxpr, closed.consts

    env: Dict[Any, Tuple[Any, np.ndarray]] = {}
    producer: Dict[Any, int] = {}          # outvar -> eqn index

    def read(v):
        if isinstance(v, jax_core.Literal):
            return v.val, np.zeros(np.shape(v.val), bool)
        return env[v]

    for v, c in zip(jaxpr.constvars, consts):
        env[v] = (c, np.zeros(np.shape(c), bool))
    flat_taints = [np.broadcast_to(np.asarray(t, bool), np.shape(a))
                   for a, t in zip(args, taints)]
    for v, a, t in zip(jaxpr.invars, args, flat_taints):
        env[v] = (jax.numpy.asarray(a), t)

    eqn_names: List[str] = []
    # eqn index -> indices of eqns (or -1 for an argument source) whose
    # tainted outputs fed its tainted inputs
    tainted_feeders: Dict[int, List[int]] = {}

    for i, eqn in enumerate(jaxpr.eqns):
        eqn_names.append(eqn.primitive.name)
        subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
        pairs = [read(v) for v in eqn.invars]
        vals = [p[0] for p in pairs]
        in_taints = [p[1] for p in pairs]
        ans = eqn.primitive.bind(*subfuns, *vals, **bind_params)
        outs = ans if eqn.primitive.multiple_results else [ans]
        out_taints = [np.zeros(np.shape(o), bool) for o in outs]
        if any(t.any() for t in in_taints):
            for delta in deltas:
                bumped = [_bump(v, t, delta)
                          for v, t in zip(vals, in_taints)]
                ans_b = eqn.primitive.bind(*subfuns, *bumped,
                                           **bind_params)
                outs_b = (ans_b if eqn.primitive.multiple_results
                          else [ans_b])
                for j, (o, ob) in enumerate(zip(outs, outs_b)):
                    out_taints[j] = out_taints[j] | _diff(o, ob)
            if any(t.any() for t in out_taints):
                feeders = []
                for v, t in zip(eqn.invars, in_taints):
                    if not isinstance(v, jax_core.Literal) and t.any():
                        feeders.append(producer.get(v, -1))
                tainted_feeders[i] = feeders
        for v, o, t in zip(eqn.outvars, outs, out_taints):
            env[v] = (o, t)
            producer[v] = i

    def chain(idx: int, depth: int = 0) -> List[str]:
        if idx < 0 or depth > 64 or idx not in tainted_feeders:
            return [eqn_names[idx]] if idx >= 0 else []
        feeders = tainted_feeders[idx]
        head = chain(feeders[0], depth + 1) if feeders else []
        return head + [eqn_names[idx]]

    out_vals, out_taints, out_paths = [], [], []
    for v in jaxpr.outvars:
        val, t = read(v)
        out_vals.append(val)
        out_taints.append(t)
        out_paths.append(chain(producer[v]) if (t.any() and v in producer)
                         else [])
    return TaintResult(out_vals, out_taints, out_paths)
