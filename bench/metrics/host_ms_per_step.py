"""Host self time per step of ``SearchService.step``: the ``step`` span
less the ``plan`` and ``execute.<kind>`` spans inside it (ms)."""
from bench.metrics._spans import per_step_ms


def read(ctx):
    whole = per_step_ms(ctx, lambda n: n == "step")
    if whole is None:
        return None
    return whole - per_step_ms(
        ctx, lambda n: n == "plan" or n.startswith("execute."))
