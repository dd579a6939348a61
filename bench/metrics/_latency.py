"""Decision latency: from the moment the answered outcome was due to land
to the decision, for every outcome due in the window."""
import numpy as np


def percentile_ms(ctx, q):
    lat = [(d.t - d.due) * 1e3 for d in ctx.record.decisions]
    return float(np.percentile(lat, q)) if lat else None
