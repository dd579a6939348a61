"""Operations and bytes of the plan legs that report a roofline share,
counted from each live lane's real sizes (never from the pads), and the
share itself: the least time the chip could take, the larger of
operations over peak FLOP/s and bytes over peak HBM bytes/s, over the
leg's device time in the trace.

An EHVI lane of ``s`` draws of ``q`` candidates in ``n_obj`` objectives
against ``k`` boxes of the front's non-dominated region needs, per
(draw, candidate, box), per objective a min, a max, a subtraction and a
clip (4 operations) and a product (1), then a mean over draws; its bytes
are the draws, the boxes and the reference point in, one value per
candidate out, in float32.
"""
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def ehvi_flops(n_obj: int, s: int, q: int, k: int) -> float:
    return s * q * k * 5.0 * n_obj + s * q


def ehvi_bytes(n_obj: int, s: int, q: int, k: int) -> float:
    return 4.0 * (s * q * n_obj + 2 * k * n_obj + n_obj + q)


LEGS = {"ehvi": (ehvi_flops, ehvi_bytes)}


def share(ctx, leg: str):
    """Percent of the roofline the leg reached in the traced window, or
    None where the window ran none of its work."""
    from bench import trace as tr
    lanes = [lane for plan in ctx.record.plan_work
             for bucket in plan[leg] for lane in bucket]
    if not lanes:
        return None
    flops_fn, bytes_fn = LEGS[leg]
    flops = sum(flops_fn(*lane) for lane in lanes)
    nbytes = sum(bytes_fn(*lane) for lane in lanes)
    with open(os.path.join(HERE, "kernels.json")) as f:
        patterns = json.load(f)[leg]
    lo, hi = ctx.window_ns()
    secs, events = tr.module_seconds(ctx.trace, patterns, lo, hi)
    if not events:
        raise ValueError(f"the {leg} leg ran {len(lanes)} lanes but the "
                         f"name table {patterns} matches no device module")
    peak = ctx.peaks
    t_min = max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * t_min / secs
