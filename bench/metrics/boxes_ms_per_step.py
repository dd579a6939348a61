"""Host self ms per step of the EHVI box decompositions inside
``StepPlanner.plan`` (the program's ``span_s.ehvi.boxes``)."""
from bench.metrics._program import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ("ehvi.boxes",))
