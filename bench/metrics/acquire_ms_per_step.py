"""Host self ms per step of the ``acquire`` phase of ``SearchService.step``
(the program's ``span_s.acquire``)."""
from bench.metrics._program import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ("acquire",))
