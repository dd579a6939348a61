"""The 95th percentile of step wall time in the traced run, each step
ending in a wait for the device to finish its work (ms)."""
import numpy as np


def read(ctx):
    walls = ctx.record.step_walls
    return float(np.percentile(walls, 95)) * 1e3 if walls else None
