"""Host-clock spans of the traced run, per step."""


def per_step_ms(ctx, names):
    r = ctx.record
    steps = [(s, e) for n, s, e in r.spans if n == "step"]
    if not steps:
        return None
    inner = sum(e - s for n, s, e in r.spans if names(n))
    return inner * 1e3 / len(steps)

