"""Rule ``vocab-closure``: live signatures stay inside the enumerated
launch vocabulary, and launch arguments carry strong types.

The compile-once steady state rests on ``enumerate_buckets(limits)``
covering every ``launch_signature`` a live cohort within ``limits`` can
emit — dynamically asserted as ``plan_compile_misses == 0``; checked
STATICALLY here by walking the planner's reachable bucket states (every
exact observation count, lane count, sample/objective knob, candidate
count and front size inside the limits, through the REAL
``StepPlanner.plan`` so the ``_pads_*`` policy itself is exercised) and
testing each emitted signature for membership in the enumerated set —
under every mesh lane-lifting divisor in play (``lane_shards`` 1/2/4).

The second check guards the jit-cache axis the signature tuple cannot
see: weak-typed launch arguments. A Python scalar traced into a launch
gets a weak dtype; if it ever varies, each value mints a NEW cache
entry with an identical signature — the vocabulary fractures invisibly.
Every launch argument in the analysis fixtures must trace strong;
waivers (the fit's constant ``lr``) carry a justification in
``findings.SUPPRESSIONS``.
"""
from __future__ import annotations

from types import SimpleNamespace
from typing import Callable, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .findings import Finding

# the representative serving envelope the lint CLI proves closure for:
# wide enough to exercise every rounding regime (multi-bucket obs axis,
# pow2+shard lane ladder, both EHVI box regimes for small fronts)
def lint_limits():
    from repro.core.plan import CohortLimits
    return CohortLimits(d=4, q_grid=20, max_obs=24, max_lanes=8,
                        n_samples=(32,), n_mc=(16,),
                        n_objectives=(2, 3), max_ehvi_boxes=64)


def _stack(m: int, n: int, d: int):
    """A shape-only stand-in for ``BatchedGP``: the planner reads just
    ``.x`` (shapes), ``.m`` and ``.n_max``."""
    return SimpleNamespace(x=np.zeros((m, n, d), np.float32), m=m,
                           n_max=n)


def signature_universe(planner, limits) -> set:
    return {planner.launch_signature(b)
            for b in planner.enumerate_buckets(limits)}


def _ehvi_fronts(limits, rng) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Observed fronts of 0..3 points per objective count — box counts
    from 1 (empty front) up through multi-box staircases, all inside
    ``max_ehvi_boxes`` for the envelopes this rule runs at."""
    fronts = []
    for n_obj in limits.n_objectives:
        ref = np.full((n_obj,), 3.0)
        for pts in range(4):
            fronts.append((rng.normal(0.0, 1.0, (pts, n_obj)), ref))
    return fronts


def iter_live_plans(planner, limits) -> Iterable:
    """Exhaustively yield planned cohort steps over the reachable
    exact-shape states: every observation count and lane count for
    posterior/sample/loo buckets, every remaining-candidate count a
    session holding at most ``max_obs`` observations can score and every
    front in ``_ehvi_fronts`` for EHVI buckets (lane counts thinned to
    {1, 2, max} there — the lane axis rounds identically across
    kinds)."""
    from repro.core.plan import (EhviQuery, FitQuery, LooSampleQuery,
                                 PosteriorQuery, SampleQuery)
    rng = np.random.default_rng(7)
    d, qg = limits.d, limits.q_grid
    grid = np.zeros((qg, d), np.float32)
    lane_counts = range(1, limits.max_lanes + 1)
    thin_lanes = sorted({1, min(2, limits.max_lanes), limits.max_lanes})
    for n in range(1, limits.max_obs + 1):
        for lanes in lane_counts:
            yield planner.plan(
                [PosteriorQuery(_stack(1, n, d), grid)] * lanes)
        # one multi-model stack occupying all lanes at once
        yield planner.plan(
            [PosteriorQuery(_stack(limits.max_lanes, n, d), grid)])
        for s in limits.n_samples:
            own = np.zeros((n, d), np.float32)   # RGPE: own inputs
            for lanes in thin_lanes:
                yield planner.plan(
                    [SampleQuery(_stack(1, n, d), own, None, s)]
                    * lanes)
                yield planner.plan(
                    [LooSampleQuery(SimpleNamespace(n=n), None, s)]
                    * lanes)
        # fit buckets: both steps rungs (warm refine + cold full) at
        # every noise level. The live rungs come from LIMITS directly —
        # mirroring the service, whose warm cache decides a query's
        # steps — NOT from the planner's fit_step_rungs policy, so a
        # planner that drops a rung from its enumeration surfaces here
        # as an unenumerated live signature
        live_rungs = sorted(
            {int(limits.fit_steps)}
            | ({int(limits.fit_warm_steps)}
               if limits.fit_warm_steps else set()))
        for steps in live_rungs:
            for noise in limits.noises:
                for lanes in thin_lanes:
                    yield planner.plan(
                        [FitQuery(np.zeros((n, d), np.float32),
                                  np.zeros((n,), np.float32),
                                  noise, steps)] * lanes)
    fronts = _ehvi_fronts(limits, rng)
    for n_obj in limits.n_objectives:
        for s in limits.n_mc:
            for q in range(max(1, qg - limits.max_obs), qg + 1):
                row = np.zeros((q,), np.float32)
                for observed, ref in fronts:
                    if observed.shape[-1] != n_obj:
                        continue
                    for lanes in thin_lanes:
                        yield planner.plan([EhviQuery(
                            samples=None, observed=observed, ref=ref,
                            mu=(row,) * n_obj, var=(row,) * n_obj,
                            y_mean=(0.0,) * n_obj,
                            y_std=(1.0,) * n_obj,
                            keys=(None,) * n_obj, n_mc=s)] * lanes)


def check_closure(
    limits=None,
    planner_factory: Optional[Callable[[int], object]] = None,
    shard_sizes: Sequence[int] = (1, 2, 4),
) -> List[Finding]:
    """Every live signature must be enumerated, per mesh divisor."""
    from repro.core.plan import StepPlanner
    limits = lint_limits() if limits is None else limits
    if planner_factory is None:
        planner_factory = lambda s: StepPlanner(lane_shards=s)
    out: List[Finding] = []
    seen_bad = set()
    for shards in shard_sizes:
        planner = planner_factory(shards)
        universe = signature_universe(planner, limits)
        for plan in iter_live_plans(planner, limits):
            for bucket in plan.buckets:
                if bucket.kind == "draw":   # unjitted: no vocabulary
                    continue
                sig = planner.launch_signature(bucket)
                if sig not in universe and sig not in seen_bad:
                    seen_bad.add(sig)
                    out.append(Finding(
                        "vocab-closure", "error", bucket.kind,
                        repr(sig),
                        f"live cohort emits a signature outside "
                        f"enumerate_buckets (lane_shards={shards}): "
                        f"a serving step would compile mid-flight"))
    return out


def check_weak_types(specs=None) -> List[Finding]:
    """Every traced launch argument in the analysis fixtures must be
    strong-typed; weak scalars fracture the jit cache invisibly."""
    import jax

    from .padding_taint import launch_specs
    specs = launch_specs() if specs is None else specs
    out: List[Finding] = []
    for spec in specs:
        closed = jax.make_jaxpr(spec.fn)(*spec.args)
        names = (spec.arg_names if len(spec.arg_names)
                 == len(closed.jaxpr.invars)
                 else [f"arg{i}" for i in
                       range(len(closed.jaxpr.invars))])
        for name, var in zip(names, closed.jaxpr.invars):
            if getattr(var.aval, "weak_type", False):
                out.append(Finding(
                    "vocab-closure", "error", spec.name, name,
                    f"launch argument {name!r} traces weak-typed (a "
                    f"Python scalar reached the launch): every "
                    f"distinct value would mint its own jit-cache "
                    f"entry under one signature"))
    return out


def check_vocab_closure() -> List[Finding]:
    return check_closure() + check_weak_types()
