"""Host self ms per step of assembling the fit round and its target
stacks: the ``fit.collect``, ``fit.cache`` and ``regroup`` phases of
``SearchService.step``."""
from bench.metrics._program import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ("fit.collect", "fit.cache", "regroup"))
