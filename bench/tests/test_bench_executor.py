"""The traffic generator on a fake clock: the closed loop releases every
run at launch; the open loop releases one in-flight run per Poisson
event, the same ones for the same seed, each due when its event came."""
import numpy as np
import pytest

from bench.executor import WallClockExecutor


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class Job:
    def __init__(self, rid, ci):
        self.rid, self.ci, self.config, self.tag = rid, ci, {}, "bo"


def _fn(config):
    return {"cost": 1.0}, np.zeros((6, 3))


def _executor(seed=0):
    clock = Clock()
    return WallClockExecutor(np.random.default_rng(seed), clock=clock), clock


def test_closed_loop_releases_at_launch():
    ex, clock = _executor()
    clock.t = 3.0
    ex.submit(Job(7, 1), _fn)
    assert ex.pending() == 1
    out = ex.poll()
    assert [o.job.rid for o in out] == [7] and ex.released[7] == [3.0]


def _landings(seed, traffic, until=50.0):
    ex, clock = _executor(seed)
    ex.set_traffic(traffic)
    for rid in range(8):
        ex.submit(Job(rid, rid), _fn)
    order = []
    while clock.t < until:
        clock.t += 0.25
        for o in ex.poll():
            order.append((o.job.rid, ex.released[o.job.rid][-1]))
            ex.submit(Job(o.job.rid, o.job.ci + 1), _fn)   # decide at once
    return order


def test_poisson_events_repeat_for_a_seed():
    traffic = {"release": "poisson", "rate_per_s": 2.0}
    a, b = _landings(5, traffic), _landings(5, traffic)
    assert a == b
    assert len(a) == pytest.approx(100, rel=0.35)
    assert a != _landings(6, traffic)


def test_landings_are_due_when_their_event_came():
    # due times rise, and each lies before the poll that saw it
    traffic = {"release": "poisson", "rate_per_s": 4.0}
    dues = [due for _, due in _landings(1, traffic, until=10.0)]
    assert dues == sorted(dues) and 0.0 < dues[0] and dues[-1] <= 10.0
    gaps = np.diff(dues)
    assert np.mean(gaps) == pytest.approx(0.25, rel=0.35)


def test_collect_waits_for_the_next_landing():
    ex, clock = _executor()
    ex.set_traffic({"release": "poisson", "rate_per_s": 1000.0})
    ex.submit(Job(1, 0), _fn)
    # on a frozen clock the due time never comes: collect gives up at
    # its timeout instead of spinning
    assert ex.collect(timeout=0.0) == []
