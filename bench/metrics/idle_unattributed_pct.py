"""Share of the device-idle time inside the program's ``karasu.step``
spans, in the traced window, during which no child span of the step is
open (%): how much of the step's idle time the program's spans leave
without a name."""
from bench.metrics._program import (idle_by_span, program_trace,
                                    unattributed_pct)


def read(ctx):
    lo, hi = ctx.window_ns()
    return unattributed_pct(idle_by_span(program_trace(ctx), lo, hi))
