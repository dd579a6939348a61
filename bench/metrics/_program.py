"""The program's own spans and counters (``repro.launch.spans``).

A span's self seconds are ``stats["span_s.<name>"]`` in the service; the
window's delta of every stats key is ``record.stats_delta``. In a traced
run the spans are also ``karasu.<name>`` annotations on the host planes
of the trace, on the device ops' clock; they are loaded here once per
trace file. A program without these spans (a checkout older than them)
gives ``None`` everywhere, never an error.
"""
import functools

ROOT = "karasu.step"


def span_ms_per_step(ctx, names):
    """Self ms per window step of the spans ``names``, summed."""
    delta, steps = ctx.record.stats_delta, ctx.record.steps
    keys = [f"span_s.{n}" for n in names]
    if not steps or not any(k in delta for k in keys):
        return None
    return sum(delta.get(k, 0.0) for k in keys) * 1e3 / steps


@functools.lru_cache(maxsize=1)
def _load(path):
    from bench import trace as tr
    return tr.load(path, ("karasu.",))


def program_trace(ctx):
    """The traced run's device ops with the program's spans."""
    from bench import trace as tr
    return _load(tr.find_xplane(ctx.record.trace_dir))


def idle_by_span(tr, lo, hi):
    """Device-idle seconds inside ``karasu.step`` spans in [lo, hi), by
    the innermost program span open at the time (``karasu.step`` itself
    where no child of it is open); ``None`` without such spans."""
    from bench import trace as tr_
    if not any(n == ROOT for n, _, _ in tr.spans):
        return None
    return {n: s for n, s in tr_.idle_gaps(tr, lo, hi).items()
            if n.startswith("karasu.")}


def unattributed_pct(gaps):
    """Share of the idle time inside steps with no child of the step
    open (%)."""
    total = sum(gaps.values()) if gaps else 0.0
    return 100.0 * gaps.get(ROOT, 0.0) / total if total > 0 else None
