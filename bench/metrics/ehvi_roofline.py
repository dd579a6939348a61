"""The EHVI leg's share of its roofline (%): the box-decomposition EHVI
launches' least time on the chip over their device time in the trace."""
from bench.metrics._roofline import share


def read(ctx):
    return share(ctx, "ehvi")
