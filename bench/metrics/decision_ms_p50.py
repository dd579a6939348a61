"""The 50th percentile of decision latency over all decisions answering
outcomes due in the window (ms)."""
from bench.metrics._latency import percentile_ms


def read(ctx):
    return percentile_ms(ctx, 50)
