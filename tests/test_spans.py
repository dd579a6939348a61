"""The span recorder (``repro.launch.spans``) and the spans of
``SearchService.step``: nesting and self time, the thread-local stack,
no counting without a sink, compiles charged to the innermost span, and
a tiny cohort whose steps' children of ``karasu.step`` are exactly the
documented phases, cover the step, and reach the profiler's trace."""
import dataclasses
import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import (BOConfig, Constraint, Objective, Repository,
                        scout_search_space)
from repro.launch import spans
from repro.launch.spans import root, span
from repro.serve.search_service import SearchRequest, SearchService
from repro.simdata import make_emulator

# the planner's and executor's spans, children of the step wherever a
# round runs directly under it
PLAN_SPANS = ("plan", "pack", "launch", "unpack", "scatter")


def test_self_time_excludes_children():
    sink = {}
    with root("r", sink):
        time.sleep(0.02)
        with span("a"):
            time.sleep(0.03)
            with span("b"):
                time.sleep(0.04)
        with span("a"):              # a name seen twice accumulates
            time.sleep(0.01)
    assert set(sink) == {"span_s.r", "span_s.a", "span_s.b"}
    # each reads its own sleeps; with a child's it would read 0.04 more
    assert 0.02 <= sink["span_s.r"] < 0.055
    assert 0.04 <= sink["span_s.a"] < 0.075
    assert 0.04 <= sink["span_s.b"] < 0.075
    assert spans.active_sink() is None


def test_no_sink_counts_nothing():
    with span("alone"):
        with span("inner"):
            pass
    sink = {}
    with root("r", sink):
        pass
    # a span outside any root left nothing behind on the thread
    assert set(sink) == {"span_s.r"}


def test_roots_nest_and_restore_the_outer_sink():
    outer, inner = {}, {}
    with root("outer", outer):
        with root("inner", inner):
            with span("x"):
                time.sleep(0.01)
        with span("y"):
            pass
    assert set(inner) == {"span_s.inner", "span_s.x"}
    assert set(outer) == {"span_s.outer", "span_s.y"}
    # the inner root is a child of the outer one
    assert outer["span_s.outer"] < 0.01


def test_stack_is_thread_local():
    """A span open on another thread is neither this thread's child nor
    counted into this thread's sink."""
    main, other = {}, {}
    started = threading.Event()

    def work():
        with root("worker", other):
            with span("w"):
                started.set()
                time.sleep(0.05)

    with root("r", main):
        with span("a"):
            t = threading.Thread(target=work)
            t.start()
            started.wait()
            t.join()
    assert set(main) == {"span_s.r", "span_s.a"}
    assert main["span_s.a"] >= 0.04          # not reduced by "w"
    assert set(other) == {"span_s.worker", "span_s.w"}


def test_compile_charged_to_innermost_span():
    sink = {}
    fresh = jax.jit(lambda x: x * 3.0 + 1.0)
    a, b = np.ones((7, 3), np.float32), np.ones((5,), np.float32)
    with root("r", sink):
        with span("phase"):
            with span("build"):
                fresh(a)
            fresh(a)                         # cached: builds nothing
        jax.jit(lambda x: x - 2.0)(b)
    assert sink["compiles.build"] == 1
    assert "compiles.phase" not in sink
    assert sink["compiles.r"] == 1
    # with no sink on the thread the listener charges nothing
    jax.jit(lambda x: x / 5.0)(b)
    assert sink["compiles.r"] == 1


# -- the spans of SearchService.step -----------------------------------------

EMU = make_emulator()
WID = EMU.workload_ids()[6]


def _cohort(moo=True):
    space = scout_search_space()
    space = dataclasses.replace(space, name="scout-10",
                                configs=space.configs[:10])
    repo = Repository()
    rng = np.random.default_rng(3)
    for u in range(2):
        for ci in rng.choice(len(space), 7, replace=False):
            repo.add_run(EMU.make_record(f"anon-{u}", WID,
                                         space.configs[ci], rng))
    svc = SearchService(repo, slots=2)
    cfg = BOConfig(n_init=2, max_iters=6, rgpe_samples=16)
    cons = [Constraint("runtime", EMU.runtime_target(WID, 50))]
    runner = lambda c: EMU.run(WID, c, rng=None)      # noqa: E731
    svc.submit(SearchRequest(space, runner, Objective("cost"), cons,
                             method="karasu", bo_config=cfg, seed=1))
    if moo:
        svc.submit(SearchRequest(space, runner, None, cons,
                                 method="karasu", bo_config=cfg, seed=2,
                                 objectives=[Objective("cost"),
                                             Objective("energy")], n_mc=8))
    return svc


def _tree(events):
    """(name, start, end, step_num) -> {index of a karasu.step event:
    [names of its children]}: an event's parent is the shortest other
    event that contains it."""
    out = {}
    for i, (n, s, e, _) in enumerate(events):
        parents = [(pe - ps, j) for j, (_, ps, pe, _) in enumerate(events)
                   if j != i and ps <= s and e <= pe
                   and (pe - ps, j) != (e - s, i)]
        if n == "karasu.step":
            out.setdefault(i, [])
        elif parents:
            j = min(parents)[1]
            if events[j][0] == "karasu.step":
                out.setdefault(j, []).append(n)
    return out


@pytest.fixture(scope="module")
def traced_steps(tmp_path_factory):
    from jax.profiler import ProfileData
    svc = _cohort()
    svc.step()                  # the first step builds most programs
    first = {k: v for k, v in svc.stats.items() if k.startswith("compiles.")}
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    deltas = []
    jax.profiler.start_trace(trace_dir)
    try:
        for _ in range(2):
            before = dict(svc.stats)
            svc.step()
            deltas.append({k: v - before.get(k, 0)
                           for k, v in svc.stats.items()
                           if k.startswith("span_s.")})
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(spans.PREFIX):
                    stats = dict(ev.stats)
                    s = int(ev.start_ns)
                    events.append((ev.name, s, s + int(ev.duration_ns),
                                   stats.get("step_num")))
    return first, deltas, events


def test_step_children_are_the_documented_phases(traced_steps):
    _, _, events = traced_steps
    tree = _tree(events)
    steps = sorted(events[i][3] for i in tree)
    assert steps == [2, 3]
    want = {spans.PREFIX + n for n in
            set(SearchService.STEP_PHASES + PLAN_SPANS) - {"profile_wait"}}
    for i, children in tree.items():
        assert set(children) == want, events[i]
    # no program span takes a name the benchmark's own spans use
    for n, *_ in events:
        bare = n[len(spans.PREFIX):]
        assert bare not in ("step", "plan") or n in (
            "karasu.step", "karasu.plan")
        assert not n.startswith(("step", "plan", "execute."))
    # phases and buckets, never tenants: a bounded count per step
    per_step = len(events) / len(tree)
    assert 20 <= per_step <= 80


def test_step_self_times_cover_the_step(traced_steps):
    _, deltas, events = traced_steps
    roots = sorted((e[3], e[2] - e[1]) for e in events
                   if e[0] == "karasu.step")
    for d, (_, dur_ns) in zip(deltas, roots):
        total = sum(d.values())
        children = total - d["span_s.step"]
        # the children's self times add up to the root's duration
        assert children >= 0.98 * total, d
        assert total == pytest.approx(dur_ns * 1e-9, rel=0.02)


def test_compiles_are_charged_to_step_phases(traced_steps):
    """The cohort's first step builds its programs inside the phases
    (the jitted launches under ``launch``, the eager glue elsewhere)."""
    first, _, _ = traced_steps
    assert first.get("compiles.launch", 0) > 0
    phases = set(SearchService.STEP_PHASES + PLAN_SPANS) | {"step"}
    assert {k[len("compiles."):] for k in first} <= phases


# -- the EHVI box decomposition: its span and its counters --------------------

def test_box_span_only_in_multi_objective_steps(traced_steps):
    _, deltas, events = traced_steps
    # the cohort's two-objective tenant decides on every traced step
    assert all(d.get("span_s.ehvi.boxes", 0) > 0 for d in deltas)
    plans = [(s, e) for n, s, e, _ in events if n == "karasu.plan"]
    boxes = [(s, e) for n, s, e, _ in events if n == "karasu.ehvi.boxes"]
    assert boxes and all(any(ps <= s and e <= pe for ps, pe in plans)
                         for s, e in boxes)

    svc = _cohort(moo=False)
    for _ in range(3):
        svc.step()
    assert svc.stats["iterations"] > 0
    assert "span_s.ehvi.boxes" not in svc.stats
    assert svc.stats["ehvi_boxes_live"] == svc.stats["ehvi_boxes_padded"] == 0


def test_box_counters_sum_live_and_padded_boxes():
    """A two-lane EHVI bucket, fronts of 2 and 5 mutually non-dominated
    points: staircases of 3 and 6 boxes, both lanes padded to 8."""
    from repro.core.plan import EhviQuery, PlanExecutor, StepPlanner
    rng = np.random.default_rng(4)
    fronts = [np.array([[1.0, 4.0], [3.0, 2.0]]),
              np.array([[1.0, 9.0], [2.0, 7.0], [3.0, 5.0], [4.0, 3.0],
                        [5.0, 1.0]])]
    queries = [EhviQuery(tuple(rng.normal(2.0, 1.0, (4, 6))
                               for _ in range(2)), f,
                         f.max(axis=0) * 1.1 + 1e-9) for f in fronts]
    plan = StepPlanner().plan(queries)
    assert [b.pads["k_pad"] for b in plan.buckets] == [8]
    nested = {}
    PlanExecutor().execute(plan, counters=nested)
    assert nested["ehvi"]["boxes_live"] == 3 + 6
    assert nested["ehvi"]["boxes_padded"] == 2 * 8

    svc = SearchService(Repository(), slots=2)
    svc._count_plan(nested)
    svc._count_plan(nested)
    assert svc.stats["ehvi_boxes_live"] == 18
    assert svc.stats["ehvi_boxes_padded"] == 32
    assert svc.stats["ehvi_boxes_live"] <= svc.stats["ehvi_boxes_padded"]
