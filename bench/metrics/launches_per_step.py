"""Plan launches per step: the window's delta of the service's
``plan_batches`` counter over its steps."""


def read(ctx):
    r = ctx.record
    return r.stats_delta["plan_batches"] / r.steps if r.steps else None
