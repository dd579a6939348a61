"""Tenant decisions made in the window over the window's seconds (a
tenant that finishes counts as decided)."""


def read(ctx):
    r = ctx.record
    return r.made_in_window / r.window_s
