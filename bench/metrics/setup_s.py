"""Seconds from the start of the process to the opening of the window:
imports, device start-up, data generation, compilation or loading from
the compile cache, and the warm-up steps."""


def read(ctx):
    return ctx.record.setup_s
