"""Named host spans of the serving path, on the profiler's clock.

``with span("select"):`` does three things:

  - it enters a ``jax.profiler.TraceAnnotation`` named ``karasu.select``,
    so a profiler trace holds the program's own phases in the same
    ``.xplane.pb`` as the device ops, on the same clock;
  - it adds the span's SELF time — its duration less the time its child
    spans cover — to the active sink as ``span_s.<name>``;
  - it keeps a thread-local stack of open spans, which is what makes
    self time computable and what the compile counter charges.

A service entry point opens a ROOT span with ``root(name, sink)``: the
sink (``SearchService.stats``) becomes active on the thread for the
root's extent. ``SearchService.step`` opens ``karasu.step`` as a
``StepTraceAnnotation`` carrying the service's step count, so every
span of one step shares that identifier. With no active sink a span
still annotates but counts nothing, so library callers (``run_search``,
the plan wrappers) work unchanged.

Compile counter: one process-wide ``jax.monitoring`` listener charges
every program build (``/jax/core/compile/backend_compile_duration``,
a compile or a persistent-cache load) to the innermost open span on the
building thread, as ``compiles.<name>`` in the active sink — after a
cohort or shape change this says which phase builds programs.

Span names are bare (``select``, ``pack``); the trace carries them
under the ``karasu.`` prefix. Spans mark phases and plan buckets, never
tenants or lanes, so a step carries a bounded few dozen.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Optional

import jax

PREFIX = "karasu."
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_local = threading.local()


class _Frame:
    __slots__ = ("name", "child_s")

    def __init__(self, name: str):
        self.name = name
        self.child_s = 0.0       # seconds covered by this span's children


def _stack() -> List[_Frame]:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def active_sink() -> Optional[Dict[str, float]]:
    """The sink spans on this thread count into, or None."""
    return getattr(_local, "sink", None)


def _add(sink: Optional[Dict[str, float]], key: str, value) -> None:
    if sink is not None:
        sink[key] = sink.get(key, 0) + value


@contextlib.contextmanager
def _timed(name: str, annotation):
    stack = _stack()
    frame = _Frame(name)
    stack.append(frame)
    t0 = time.perf_counter()
    try:
        with annotation:
            yield
    finally:
        dur = time.perf_counter() - t0
        stack.pop()
        if stack:
            stack[-1].child_s += dur
        _add(active_sink(), "span_s." + name, dur - frame.child_s)


def span(name: str, **args):
    """A child span: ``karasu.<name>`` in the trace (``args`` become the
    annotation's arguments), self seconds into the active sink."""
    return _timed(name, jax.profiler.TraceAnnotation(PREFIX + name, **args))


@contextlib.contextmanager
def root(name: str, sink: Dict[str, float],
         step_num: Optional[int] = None):
    """A service entry point's span: ``sink`` is active on this thread
    for its extent (the previous sink comes back after). With
    ``step_num`` it is a ``StepTraceAnnotation``, so the profiler groups
    the step's spans under that step."""
    if step_num is None:
        annotation = jax.profiler.TraceAnnotation(PREFIX + name)
    else:
        annotation = jax.profiler.StepTraceAnnotation(PREFIX + name,
                                                      step_num=step_num)
    prev = active_sink()
    _local.sink = sink
    try:
        with _timed(name, annotation):
            yield
    finally:
        _local.sink = prev


def _on_event(event: str, duration: float, **kw) -> None:
    if event != COMPILE_EVENT:
        return
    stack = _stack()
    if stack:
        _add(active_sink(), "compiles." + stack[-1].name, 1)


jax.monitoring.register_event_duration_secs_listener(_on_event)
