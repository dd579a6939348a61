"""The one traffic generator: a wall-clock profiling executor.

It stands behind the program's executor protocol (``submit``, ``pending``,
``poll``, ``collect``, ``drain``, ``shutdown``). The emulated profiling
run executes inline at ``submit``; its outcome becomes visible to the
service at its due time, which the traffic mix sets:

- ``"release": "on_launch"`` — a closed loop with zero think time: every
  outcome is due the moment its run is launched.
- ``"release": "poisson"`` — an open loop of landing events at
  ``rate_per_s``; each event releases one in-flight run, chosen
  uniformly at random, whatever the service is doing.

The executor logs, for the harness, when each run was launched and when
each outcome was due, so a decision's latency is measured from the due
time of the outcome it answers.
"""
from __future__ import annotations

import collections
import time
from typing import Dict, List, Optional, Tuple

import numpy as np


def _run(job, fn):
    from repro.serve.profile_executor import ProfileOutcome
    try:
        measures, metrics = fn(job.config)
        return ProfileOutcome(job, measures, metrics)
    except Exception as e:                      # noqa: BLE001 — relayed
        return ProfileOutcome(job, error=e)


class WallClockExecutor:
    def __init__(self, rng: np.random.Generator, clock=time.perf_counter):
        self.clock = clock
        self.rng = rng
        self.traffic: Dict = {"release": "on_launch"}
        self._inflight: List[Tuple[float, object]] = []   # (launch t, out)
        self._landed: List[object] = []
        self._next_event: Optional[float] = None
        # the harness's record: (t, rid, tag, configuration) per launch,
        # and the due time of every released outcome, per tenant in order
        self.launches: List[Tuple[float, int, str, int]] = []
        self.released: Dict[int, List[float]] = collections.defaultdict(
            list)

    def set_traffic(self, traffic: Dict) -> None:
        """Switch the release rule from now on (set-up staggers the cohort
        on the closed loop, then hands over to the cell's mix)."""
        self.traffic = traffic
        if traffic["release"] == "poisson":
            self._next_event = self.clock() + self._draw_gap()
        elif traffic["release"] != "on_launch":
            raise ValueError(f"unknown release {traffic['release']!r}")

    # -- the Poisson clock ---------------------------------------------------
    def _draw_gap(self) -> float:
        return float(self.rng.exponential(1.0 / self.traffic["rate_per_s"]))

    def _advance(self, now: float) -> None:
        if self.traffic["release"] == "on_launch":
            return
        while self._next_event is not None and self._next_event <= now:
            t = self._next_event
            if self._inflight:
                i = int(self.rng.integers(len(self._inflight)))
                _, out = self._inflight.pop(i)
                self.released[out.job.rid].append(t)
                self._landed.append(out)
            self._next_event = t + self._draw_gap()

    # -- the executor protocol -----------------------------------------------
    def submit(self, job, fn) -> None:
        now = self.clock()
        self.launches.append((now, job.rid, job.tag, int(job.ci)))
        out = _run(job, fn)
        if self.traffic["release"] == "on_launch":
            self.released[job.rid].append(now)
            self._landed.append(out)
        else:
            self._inflight.append((now, out))

    def pending(self) -> int:
        return len(self._inflight) + len(self._landed)

    def poll(self):
        self._advance(self.clock())
        out, self._landed = self._landed, []
        return out

    def collect(self, timeout: Optional[float] = None, min_results: int = 1):
        deadline = None if timeout is None else self.clock() + timeout
        want = min(min_results, self.pending())
        self._advance(self.clock())
        while len(self._landed) < want:
            wake = self._next_event
            if deadline is not None:
                wake = deadline if wake is None else min(wake, deadline)
            if wake is None:
                break
            time.sleep(max(0.0, wake - self.clock()))
            self._advance(self.clock())
            if deadline is not None and self.clock() >= deadline:
                break
        out, self._landed = self._landed, []
        return out

    def drain(self, timeout: Optional[float] = None):
        return self.collect(timeout, min_results=self.pending())

    def shutdown(self) -> None:
        self._inflight.clear()
        self._landed.clear()
