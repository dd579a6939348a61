"""Programs the process built inside the window, compiled or loaded from
the persistent cache, counted by a ``jax.monitoring`` listener (so
support-model fits and small eager programs count too)."""


def read(ctx):
    return float(len(ctx.record.window_compiles))
