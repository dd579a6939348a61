"""Host ms per step inside ``StepPlanner.plan`` (the ``plan`` span)."""
from bench.metrics._spans import per_step_ms


def read(ctx):
    return per_step_ms(ctx, lambda n: n == "plan")
