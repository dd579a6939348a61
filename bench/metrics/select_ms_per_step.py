"""Host self ms per step of the ``select`` phase of ``SearchService.step``
(the program's ``span_s.select``)."""
from bench.metrics._program import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ("select",))
