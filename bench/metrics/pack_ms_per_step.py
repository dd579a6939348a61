"""Host self ms per step packing the plan executor's bucket inputs (the
``pack`` spans of ``PlanExecutor.execute``)."""
from bench.metrics._program import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ("pack",))
