"""Host self ms per step handing the plan executor's results back: the
``unpack`` spans (per-query slicing) and ``scatter`` spans (owner
callbacks) of ``PlanExecutor.execute``."""
from bench.metrics._program import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ("unpack", "scatter"))
