"""How full the EHVI launches' box axis ran in the window (%): the
lanes' own non-dominated boxes over the boxes they were padded to, from
the service's ``ehvi_boxes_live`` and ``ehvi_boxes_padded`` counters.
``None`` where the program has no such counters or ran no EHVI launch."""


def read(ctx):
    delta = ctx.record.stats_delta
    padded = delta.get("ehvi_boxes_padded", 0)
    if not padded:
        return None
    return 100.0 * delta["ehvi_boxes_live"] / padded
