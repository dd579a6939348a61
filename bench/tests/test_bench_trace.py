"""The trace reduction and the roofline arithmetic, on a small trace
recorded on a TPU v5e (``mini_trace.xplane.pb``: three ``step`` spans,
each with a ``plan`` span, an ``execute.fit`` span around a program named
``_fused_fit_launch`` and an ``execute.ehvi`` span around one named
``_ehvi_box_eval``) and on hand-made intervals."""
import os

import pytest

from bench import trace as tr
from bench.metrics import _roofline as rl

HERE = os.path.dirname(os.path.abspath(__file__))
MINI = os.path.join(HERE, "mini_trace.xplane.pb")
SPANS = ("step", "plan", "execute.")


def test_union_and_clip():
    assert tr.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3), (5, 10)]
    assert tr.clip([(0, 4), (6, 12), (20, 30)], 2, 10) == [(2, 4), (6, 10)]


def _hand_trace():
    # device busy 10-20 and 40-50 of a 0-100 window; the host is in
    # "plan" 20-40 (inside "step" 0-100) and outside any span after 100
    return tr.Trace(ops=[("fusion", 10, 20), ("dot", 40, 50)],
                    modules=[("jit__fused_fit_launch", 10, 20),
                             ("jit__ehvi_box_eval", 40, 50)],
                    spans=[("step", 0, 100), ("plan", 20, 40)], devices=1)


def test_busy_idle_and_gaps_by_span():
    t = _hand_trace()
    lo, hi = tr.window(t)
    assert (lo, hi) == (0, 100)
    assert tr.busy_ns(t, lo, hi) == 20
    gaps = tr.idle_gaps(t, lo, hi)
    assert gaps["plan"] == pytest.approx(20e-9)
    assert gaps["step"] == pytest.approx(60e-9)
    assert sum(gaps.values()) == pytest.approx(80e-9)
    assert tr.op_times(t, lo, hi) == {"fusion": 10e-9, "dot": 10e-9}
    assert tr.module_seconds(t, ["_fused_fit_launch"], lo, hi) == (10e-9, 1)


@pytest.fixture(scope="module")
def mini():
    return tr.load(MINI, SPANS)


def test_recorded_trace_reduces(mini):
    assert mini.devices == 1
    names = [n for n, _, _ in mini.spans]
    assert names.count("step") == 3 and names.count("execute.fit") == 3
    lo, hi = tr.window(mini)
    busy = tr.busy_ns(mini, lo, hi)
    assert 0 < busy < hi - lo
    gaps = tr.idle_gaps(mini, lo, hi)
    # the host sleeps in "plan" and between the executes, inside "step"
    assert gaps["plan"] > 1e-3 and gaps["step"] > 2e-3
    assert sum(gaps.values()) == pytest.approx((hi - lo - busy) * 1e-9)
    ops = tr.op_times(mini, lo, hi)
    assert sum(ops.values()) >= busy * 1e-9 * 0.999


def test_recorded_trace_name_table_finds_both_legs(mini):
    # the EHVI leg is found by its table entry; the fit leg has none (its
    # served launch is a jitted lambda), though its modules are in the
    # trace under their own name
    import json
    table = json.load(open(os.path.join(os.path.dirname(HERE), "metrics",
                                        "kernels.json")))
    lo, hi = tr.window(mini)
    secs, events = tr.module_seconds(mini, table["ehvi"], lo, hi)
    assert events == 3 and secs > 0
    assert "fit" not in table
    assert tr.module_seconds(mini, ["_fused_fit_launch"], lo, hi)[1] == 3


def test_ehvi_work():
    assert rl.ehvi_flops(2, 64, 50, 9) == 64 * 50 * 9 * 10 + 64 * 50
    assert rl.ehvi_bytes(2, 64, 50, 9) == 4 * (64 * 50 * 2 + 36 + 2 + 50)
