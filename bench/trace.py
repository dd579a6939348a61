"""Reduction of a profiler trace to device busy time, per-op device time,
per-module device time, and idle gaps labelled by the host span the
benchmark was in.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes. A chip's
plane is named ``/device:TPU:<n>``; on it, the ``XLA Ops`` line holds one
event per device operation (named by its HLO instruction, kept here up
to the '=') and the ``XLA Modules`` line one per launched program. Host
spans are the benchmark's own ``TraceAnnotation``s, found by name on the
host planes.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[int, int]          # (start ns, end ns)
CHIP = re.compile(r"^/device:TPU:\d+$")
NEAR = 512


@dataclasses.dataclass
class Trace:
    ops: List[Tuple[str, int, int]]       # (name, start, end), all devices
    modules: List[Tuple[str, int, int]]
    spans: List[Tuple[str, int, int]]     # the benchmark's host spans
    devices: int


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, span_names: Iterable[str]) -> Trace:
    """Read one ``.xplane.pb``; ``span_names`` are the host annotation
    names (or prefixes ending in '.') to keep."""
    from jax.profiler import ProfileData
    names = tuple(span_names)

    def is_span(n: str) -> bool:
        return any(n == s or (s.endswith(".") and n.startswith(s))
                   for s in names)

    data = ProfileData.from_file(path)
    ops, modules, spans, devices = [], [], [], 0
    for plane in data.planes:
        if CHIP.match(plane.name):
            devices += 1
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    name = ev.name.split(" = ")[0].lstrip("%")
                    dest.append((name, s, s + int(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if is_span(ev.name):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
    return Trace(ops, modules, sorted(spans, key=lambda t: t[1]), devices)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(tr: Trace, lo: int, hi: int) -> float:
    """Device-busy time in [lo, hi), averaged over the devices."""
    total = sum(e - s for s, e in
                union(clip(((s, e) for _, s, e in tr.ops), lo, hi)))
    return total / max(tr.devices, 1)


def window(tr: Trace, span: str = "step") -> Interval:
    """The traced window: from the first to the end of the last ``span``."""
    steps = [(s, e) for n, s, e in tr.spans if n == span]
    if not steps:
        raise ValueError(f"no {span!r} spans in the trace")
    return steps[0][0], max(e for _, e in steps)


def op_times(tr: Trace, lo: int, hi: int) -> Dict[str, float]:
    """Seconds of device time per operation name in [lo, hi)."""
    out: Dict[str, float] = {}
    for name, s, e in tr.ops:
        for cs, ce in clip([(s, e)], lo, hi):
            out[name] = out.get(name, 0.0) + (ce - cs) * 1e-9
    return out


def module_seconds(tr: Trace, patterns: Sequence[str], lo: int,
                   hi: int) -> Tuple[float, int]:
    """(device seconds, events) of the modules whose name contains any of
    ``patterns``, in [lo, hi)."""
    secs, count = 0.0, 0
    for name, s, e in tr.modules:
        if any(p in name for p in patterns):
            for cs, ce in clip([(s, e)], lo, hi):
                secs += (ce - cs) * 1e-9
                count += 1
    return secs, count


def idle_gaps(tr: Trace, lo: int, hi: int) -> Dict[str, float]:
    """Seconds in [lo, hi) with no device operation running, by the
    innermost benchmark span the host was in ("outside" if none)."""
    busy = union(clip(((s, e) for _, s, e in tr.ops), lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    starts = [s for _, s, _ in tr.spans]
    out: Dict[str, float] = {}
    for gs, ge in gaps:
        # the spans open during the gap: a span starts at most NEAR spans
        # before the last one that starts inside it (spans nest, a step
        # holding a few dozen)
        k = bisect.bisect_left(starts, ge)
        near = [sp for sp in tr.spans[max(0, k - NEAR):k] if sp[2] > gs]
        # cut the gap at every span boundary inside it and give each
        # piece to the innermost span covering it
        cuts = sorted({gs, ge} | {x for _, s, e in near
                                  for x in (s, e) if gs < x < ge})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            inner = [(e - s, n) for n, s, e in near if s <= mid < e]
            label = min(inner)[1] if inner else "outside"
            out[label] = out.get(label, 0.0) + (b - a) * 1e-9
    return out


def top(d: Dict[str, float], k: int = 10) -> List[List]:
    return [[n, v] for n, v in sorted(d.items(), key=lambda t: -t[1])[:k]]
