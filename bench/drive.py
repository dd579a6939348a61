"""One run of one cell: set-up, the measured window, and the drain.

The service is built as a deployment builds it —
``SearchService(repository, slots=<cohort>)`` with every default — and
driven through ``submit`` / ``step`` / ``collect`` behind the wall-clock
executor. The whole cohort is admitted at once and takes its first
(cold-rung) step on the closed loop; then the cell's traffic runs.

Set-up rehearses: the same cohort from the same seed runs first on a
service of its own, past the point the window will reach, and is
dropped; the window then runs on a fresh service whose every launch has
the shapes the rehearsal built, so nothing compiles inside it. A tenant
that finishes is replaced at once by a new one, so the cohort stays
full.

A decision is the service launching a tenant's next profiling run, or
finishing the tenant. Its latency runs from the moment the outcome it
answers was due to land.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import os
import shutil
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench.cohort import Cohort, Tenant, subseed
from bench.executor import WallClockExecutor

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# tracing, lowering and compiling: the time a new program costs
BUILD_EVENTS = (COMPILE_EVENT, "/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration")
DRAIN_LIMIT_S = 60.0
REHEARSAL = 1.3      # the rehearsal's compile-free run, in windows


@dataclasses.dataclass
class Decision:
    rid: int
    due: float                  # when the answered outcome was due
    t: float                    # when the decision was made
    n_obs: int                  # observations it was made on
    kind: str                   # "launch" | "finish"
    ci: Optional[int] = None    # the configuration launched


@dataclasses.dataclass
class RunRecord:
    """What a run measured, and what the check needs afterwards."""
    setup_s: float
    window_s: float
    t_open: float
    t_close: float
    decisions: List[Decision]   # answers to the window's outcomes
    made_in_window: int         # decisions made inside the window
    unanswered: int             # the window's outcomes never answered
    attempted: int              # outcomes due before the close and not
    #                             answered before the opening
    steps: int
    stats_delta: Dict[str, float]
    step_walls: List[float]     # window steps, each ending in a device wait
    #                             in the traced run
    window_compiles: List[str]
    tenants: Dict[int, Tenant]
    sessions: Dict[int, Dict]   # rid -> {"obs": [...], "fit": {...}}
    spans: List[Tuple[str, float, float]]
    plan_work: List[Dict]
    trace_dir: Optional[str]
    memory_peak_bytes: int


class _Spans:
    """Host spans of the traced run: each is written into the profiler's
    trace as a ``TraceAnnotation`` and kept here with its host clock."""

    def __init__(self):
        self.spans: List[Tuple[str, float, float]] = []
        self.on = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        if not self.on:
            yield
            return
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.append((name, t0, time.perf_counter()))


def _instrument(svc, spans: _Spans, plan_work: List[Dict]) -> None:
    """Wrap the planner and executor the service built, on the instance:
    a ``plan`` span, and an ``execute.<first bucket kind>`` span that also
    records each launch's live work for the roofline readers."""
    plan, execute = svc.planner.plan, svc.plan_executor.execute

    def planned(queries):
        with spans("plan"):
            return plan(queries)

    def executed(p, **kw):
        kind = p.buckets[0].kind if p.buckets else "empty"
        if spans.on:
            plan_work.append(_work_of(p))
        with spans(f"execute.{kind}"):
            return execute(p, **kw)

    svc.planner.plan = planned
    svc.plan_executor.execute = executed


def _work_of(plan) -> Dict:
    """The live work of a plan's EHVI launches, counted from the queries
    themselves (never from the pads)."""
    ehvi = []
    for b in plan.buckets:
        qs = [plan.queries[i] for i in b.indices]
        if b.kind == "ehvi":
            lanes = []
            for i, q in zip(b.indices, qs):
                los, _ = plan.prep[i]
                n_obj = b.key[0]
                s = b.key[1]
                q_live = (int(np.shape(q.samples[0])[1])
                          if q.samples is not None else len(q.mu[0]))
                lanes.append((n_obj, s, q_live, int(los.shape[0])))
            ehvi.append(lanes)
    return {"ehvi": ehvi}


def _wait_device() -> None:
    """Wait until the device has run everything enqueued so far: a chip
    runs its programs in order, so a trivial one enqueued last finishes
    last."""
    import jax
    import jax.numpy as jnp
    jax.block_until_ready(jnp.zeros(()) + 1.0)


class _Deployment:
    """One service with its cohort and its profiling executor."""

    def __init__(self, config: Dict, seed: int, service_cls, space):
        from repro.serve.search_service import SearchService
        self.cohort = Cohort(config, seed)
        self.space = space
        self.ex = WallClockExecutor(np.random.default_rng(subseed(seed, 4)))
        self.svc = (service_cls or SearchService)(
            self.cohort.build_repository(), slots=self.cohort.slots(),
            executor=self.ex)
        self.tenants: Dict[int, Tenant] = {}
        self.finished: List[Tuple[float, int]] = []
        self.results: Dict[int, List] = {}
        self.step_log: List[Tuple[float, float]] = []

    def admit(self, count: int) -> None:
        for _ in range(count):
            tenant = self.cohort.next_tenant()
            rid = self.svc.submit(self.cohort.request(tenant, self.space))
            self.tenants[rid] = tenant

    def step(self, spans, wait: bool) -> float:
        t0 = time.perf_counter()
        with spans("step"):
            self.svc.step()
        if wait:
            _wait_device()
        t1 = time.perf_counter()
        self.step_log.append((t0, t1))
        done = self.svc.collect()
        for c in done:
            self.finished.append((t1, c.rid))
            self.results[c.rid] = c.result.observations
        self.admit(len(done))
        return t1 - t0


class Driver:
    def __init__(self, config: Dict, traffic: Dict, seed: int, *,
                 trace: bool = False, trace_root: Optional[str] = None,
                 service_cls=None, t_start: Optional[float] = None,
                 rehearse: bool = True):
        from repro.core import scout_search_space
        self.t_start = time.perf_counter() if t_start is None else t_start
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.trace, self.trace_root = trace, trace_root
        self.service_cls = service_cls
        self.rehearse = rehearse     # off only where the process has
        #                              built the window's programs before
        self.space = scout_search_space()
        self.spans = _Spans()
        self.plan_work: List[Dict] = []
        self.compiles: List[Tuple[float, str, float]] = []
        self.build_s = 0.0

    # -- the run ------------------------------------------------------------------
    def run(self, seconds: float) -> RunRecord:
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_compile)
        try:
            return self._run(seconds)
        finally:
            jax.monitoring.unregister_event_duration_listener(
                self._on_compile)

    def _on_compile(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles.append((time.perf_counter(),
                                  str(kw.get("fun_name", "?")), duration))
        if event in BUILD_EVENTS:
            self.build_s += duration

    def _start(self) -> _Deployment:
        """A fresh service; the whole cohort is admitted at once and takes
        its first (cold-rung) step on the closed loop."""
        d = _Deployment(self.config, self.seed, self.service_cls, self.space)
        d.admit(d.cohort.slots())
        d.step(self.spans, False)
        d.ex.set_traffic(self.traffic)
        return d

    def _rehearse(self, seconds: float) -> None:
        """Set-up: the same cohort on a first service, run until it has
        done, at its compile-free pace, more than the window will, so
        that every program the window uses is built; then dropped."""
        d = self._start()
        done = 0.0
        while True:
            b0 = self.build_s
            pace = d.step(self.spans, self.trace) - (self.build_s - b0)
            done += pace
            if done >= REHEARSAL * seconds + 2 * pace:
                break
        d.ex.shutdown()

    def _run(self, seconds: float) -> RunRecord:
        import jax
        if self.rehearse:
            self._rehearse(seconds)
        self.d = d = self._start()
        if self.trace:
            _instrument(d.svc, self.spans, self.plan_work)
        _wait_device()
        stats0 = dict(d.svc.stats)

        trace_dir = None
        if self.trace:
            trace_dir = os.path.join(self.trace_root, f"trace-{os.getpid()}")
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            self.spans.on = True
        t_open = time.perf_counter()
        setup_s = t_open - self.t_start
        first_step = len(d.step_log)
        while time.perf_counter() - t_open < seconds:
            d.step(self.spans, self.trace)
        t_close = time.perf_counter()
        window_steps = d.step_log[first_step:]
        fits_at_close = self._fits()
        if self.trace:
            self.spans.on = False
            _wait_device()
            jax.profiler.stop_trace()
        # the drain's minute runs from here: writing the trace is the
        # benchmark's own pause, not the service's
        t_drain = time.perf_counter()
        stats_delta = {k: v - stats0.get(k, 0)
                       for k, v in d.svc.stats.items()}
        memory_peak = _memory_peak()

        def owed():
            # outcomes due before the close and not answered before the
            # opening: the window's work, a backlog from set-up included
            return [(rid, due, n, a) for rid, due, n, a in self._answers()
                    if due < t_close and (a is None or a[0] >= t_open)]
        # the drain: every such outcome is answered, or a minute passes
        while (any(a is None for *_, a in owed())
               and time.perf_counter() - t_drain < DRAIN_LIMIT_S):
            d.step(self.spans, False)
        answers = owed()
        decisions = [Decision(rid, due, a[0], n, a[1], a[2])
                     for rid, due, n, a in answers if a is not None]
        made = sum(1 for t, _, _ in self._all_decision_times()
                   if t_open <= t < t_close)
        window_compiles = [name for t, name, _ in self.compiles
                           if t_open <= t < t_close]
        return RunRecord(
            setup_s=setup_s, window_s=t_close - t_open, t_open=t_open,
            t_close=t_close, decisions=decisions, made_in_window=made,
            unanswered=len(answers) - len(decisions),
            attempted=len(answers),
            steps=len(window_steps), stats_delta=stats_delta,
            step_walls=[b - a for a, b in window_steps],
            window_compiles=window_compiles, tenants=dict(d.tenants),
            sessions=self._snapshot_sessions(fits_at_close),
            spans=list(self.spans.spans), plan_work=self.plan_work,
            trace_dir=trace_dir, memory_peak_bytes=memory_peak)

    # -- bookkeeping --------------------------------------------------------------
    def _all_decision_times(self):
        """(t, rid, kind) of every decision: each launch after a tenant's
        first (its initial run) and each finish."""
        seen = set()
        for t, rid, _tag, _ci in self.d.ex.launches:
            if rid in seen:
                yield t, rid, "launch"
            seen.add(rid)
        for t, rid in self.d.finished:
            yield t, rid, "finish"

    def _answers(self):
        """(rid, due, observations, answer or None) for every released
        outcome. A tenant has one run in flight at a time, so its k-th
        released outcome, its (k+1)-th observation, is answered by its
        (k+1)-th launch or, after its last, by its finish; an answer is
        (t, kind, configuration launched)."""
        launches = collections.defaultdict(list)
        for t, rid, _tag, ci in self.d.ex.launches:
            launches[rid].append((t, "launch", ci))
        finishes = {rid: t for t, rid in self.d.finished}
        for rid, dues in self.d.ex.released.items():
            answers = launches[rid][1:]
            if rid in finishes:
                answers.append((finishes[rid], "finish", None))
            for k, due in enumerate(dues):
                yield (rid, due, k + 1,
                       answers[k] if k < len(answers) else None)

    def _fits(self) -> Dict[int, Dict]:
        """rid -> the hyperparameters of each active tenant's last fit, as
        the program holds them: measure -> (observations, log
        lengthscales, log signal)."""
        return {rid: {m: (int(v[0]), np.asarray(v[1], np.float64),
                          float(v[2])) for m, v in s.fit_cache.items()}
                for rid, s in self.d.svc.active.items()}

    def _snapshot_sessions(self, fits_at_close: Dict[int, Dict]
                           ) -> Dict[int, Dict]:
        """Each tenant's observations and its last fits: those the program
        holds once the drain is over or, for a tenant that finished in
        the drain, those it held when the window closed."""
        out = {rid: {"obs": obs, "fit": fits_at_close.get(rid, {})}
               for rid, obs in self.d.results.items()}
        fits = self._fits()
        for rid, s in self.d.svc.active.items():
            out[rid] = {"obs": list(s.observations), "fit": fits[rid]}
        for v in out.values():
            v["obs"] = [(dict(o.config), dict(o.measures),
                         np.asarray(o.metrics, np.float64))
                        for o in v["obs"]]
        return out


def _memory_peak() -> int:
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
