"""Share of the traced window in which no operation ran on the device:
1 minus the union of the device-op intervals over the window (%)."""


def read(ctx):
    from bench import trace as tr
    lo, hi = ctx.window_ns()
    return 100.0 * (1.0 - tr.busy_ns(ctx.trace, lo, hi) / (hi - lo))
