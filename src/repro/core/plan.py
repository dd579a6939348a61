"""The declarative query-plan layer: collect -> plan -> execute -> scatter.

PRs 1-4 fused every posterior, sample draw, and EHVI evaluation of a
multi-tenant service step into padded batched launches, but the "plan"
was implicit: the bucketing and padding policy (observation axis rounded
to multiples of 8, fused model axis to a power of two, (q, d) / (S, q,
d) bucket keys) was restated in ``core/gp.py``, ``core/acquisition.py``
and ``serve/search_service.py``. This module makes the plan an explicit,
testable IR:

  - **Query nodes** — one dataclass per kind of launch a scheduling
    round needs, each carrying an opaque ``owner`` tag for scatter:

    ==================== ================================== =============
    node                 one logical request                bucket key
    ==================== ================================== =============
    ``PosteriorQuery``   grid posterior of a BatchedGP      (q, d)
    ``SampleQuery``      marginal posterior draws of a      (S, q, d)
                         BatchedGP at a grid
    ``LooSampleQuery``   closed-form leave-one-out draws    (S, n)
                         of a single target GP
    ``PosteriorDrawQuery`` affine draws from precomputed    (S, q)
                         posterior rows (MOO EHVI sampling)
    ``EhviQuery``        MC-EHVI of raw-scale draws against (n_obj, S, q)
                         a session's front (any n_obj >= 2)
    ``FitQuery``         warm-startable GP fit of one       (d, steps,
                         model's observations               noise)
    ==================== ================================== =============

  - ``StepPlanner`` — owns ALL bucketing/padding policy in one place.
    ``plan(queries)`` groups queries into ``Bucket``\\ s (one fused
    launch each) and records every pad decision on the bucket, so tests
    can assert the exact launch shapes a query set produces without
    running anything.

  - ``PlanExecutor`` — runs one launch per bucket (the jitted kernels
    live with their model math in ``core/gp.py`` /
    ``core/acquisition.py``) and scatters results back to owners:
    results come back in query order, and any query whose ``owner`` is
    callable has it invoked with the result.

``SearchService.step`` collects query nodes from every ready session,
plans, executes, and scatters; ``run_search`` / ``run_search_moo`` /
``KarasuContext.score_ensembles`` route through the same planner, and
the historical entry points (``batched_posterior_multi``,
``batched_sample_multi``, ``loo_sample_multi``, ``mc_ehvi_multi``) are
thin wrappers over it — so the serving path and the driver path share
one plan implementation, and new workload kinds (e.g. the n>=3-objective
EHVI) are plan-node additions instead of another fused-step rewrite.

Exact-padding contract (inherited from the fused launches this layer
absorbs): padded observations are masked out of the kernel and carry
unit Cholesky diagonals, padded grid points are edge-repeats or +inf
points whose rows are sliced off, padded model lanes repeat lane 0 and
are thrown away, padded EHVI boxes have lo = hi = +inf (zero volume) —
fusing or padding a query NEVER changes its result beyond float
roundoff, and PRNG draws always happen at each query's exact shape
before any padding, so draw streams are plan-invariant.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.distributed import auto_axes, mesh_axis_size, shard_map
from repro.kernels.routing import resolve_impl
from repro.launch.spans import span

from .acquisition import (EHVI_BOX_CHUNK, _ehvi_box_launch,
                          _ehvi_box_launch_donated, expected_improvement,
                          nondominated_boxes, pareto_front)
from .gp import (GP, BatchedGP, _batched_loo_launch,
                 _batched_loo_launch_donated, _batched_posterior,
                 _batched_posterior_donated, _batched_sample_launch,
                 _batched_sample_launch_donated, _pack_fit_lanes,
                 _pad_stack_obs, fit_gp_batched, sharded_fit_launches)

# -- the one home of the shape policy ---------------------------------------
OBS_ROUND_TO = 8        # observation axis pads to multiples of this
GRID_ROUND_TO = 8       # sample/EHVI candidate axis pads to multiples
M_ROUND_POW2 = True     # fused model/lane axis pads to a power of two
# the select phase's step-wide launches (the Pearson rows of every karasu
# tenant's target runs, the step's RGPE keys) pad their row axis to a
# power of two of at least ROW_PAD_MIN; the candidate index pads its runs
# to multiples of CAND_ROUND_TO, so a few published runs keep its shape
ROW_PAD_MIN = 8
CAND_ROUND_TO = 128


# ---------------------------------------------------------------------------
# Query nodes
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PosteriorQuery:
    """Posterior mean/variance of one ``BatchedGP`` stack on a grid.
    ``grid``: (q, d) shared across the stack's models or (m, q, d)
    per-model. Result: ``(mu, var)``, each (m, q) — or ``(mu, var, ei)``
    when ``best`` (the standardised-scale incumbent for the closed-form
    minimisation-EI head) is set, letting the fused bucket kernel finish
    the acquisition in the same launch."""
    stack: BatchedGP
    grid: Any
    owner: Any = None
    best: Any = None


@dataclasses.dataclass(frozen=True)
class SampleQuery:
    """Marginal-posterior draws of one stack at a grid: ``keys`` is one
    PRNG key per model. Result: (m, n_samples, q)."""
    stack: BatchedGP
    grid: Any
    keys: Any
    n_samples: int
    owner: Any = None


@dataclasses.dataclass(frozen=True)
class LooSampleQuery:
    """Closed-form leave-one-out posterior draws of a single target GP
    at its own inputs (RGPE's target honesty device). Result: (S, n)."""
    gp: GP
    key: Any
    n_samples: int
    owner: Any = None


@dataclasses.dataclass(frozen=True)
class PosteriorDrawQuery:
    """Raw-scale affine draws from precomputed posterior rows — the MOO
    EHVI sampling leg, where the grid posterior already ran and only
    ``mu + eps * sqrt(var)`` (rescaled) remains. ``mu``/``var``: (q,)
    standardised rows at the remaining candidates. Result: (n_mc, q)."""
    mu: Any
    var: Any
    y_mean: Any
    y_std: Any
    key: Any
    n_mc: int
    owner: Any = None


@dataclasses.dataclass(frozen=True)
class EhviQuery:
    """MC expected hypervolume improvement against a session's observed
    front, in one of two equivalent forms sharing a bucket:

    **Sample form** (``samples`` set): one (S, q) raw-scale draw array
    per objective (any count >= 2) — the draws already ran (e.g. as a
    ``PosteriorDrawQuery`` round).

    **Posterior form** (``samples=None``): the draw is deferred into the
    EHVI launch itself. ``mu``/``var``: one (q,) standardised posterior
    row per objective; ``y_mean``/``y_std``: per-objective scalars;
    ``keys``: one PRNG key per objective; ``n_mc``: draw count. The
    launch consumes ``normal(keys[i], (n_mc, q))`` and the exact
    ``(mu + eps * sqrt(var)) * y_std + y_mean`` affine of
    ``_draw_launch``, so both forms produce bit-identical streams — the
    fused executor skips the separate draw round (and its (S, q) HBM
    round-trip per objective) without perturbing results.

    ``observed``: (n, n_obj); ``ref``: (n_obj,). Result: (q,) numpy."""
    samples: Optional[Tuple[Any, ...]]
    observed: Any
    ref: Any
    owner: Any = None
    mu: Optional[Tuple[Any, ...]] = None
    var: Optional[Tuple[Any, ...]] = None
    y_mean: Optional[Tuple[float, ...]] = None
    y_std: Optional[Tuple[float, ...]] = None
    keys: Optional[Tuple[Any, ...]] = None
    n_mc: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class FitQuery:
    """Fit one GP model's hyperparameters from its raw observations —
    the fit leg as a first-class plan node. ``x``: (n, d) raw inputs,
    ``y``: (n,) raw objective values (standardisation happens at
    packing, exactly as in ``fit_gp_batched``). ``steps`` is the Adam
    schedule length and part of the bucket key: warm lanes carry their
    previous hyperparameters in ``init_ls``/``init_sf`` and ask for the
    short refine rung (``CohortLimits.fit_warm_steps``), cold lanes
    leave them ``None`` (zero init) on the full rung
    (``CohortLimits.fit_steps``). Result: ``(stack, lane)`` — the
    bucket's fitted ``BatchedGP`` plus this query's lane index in it
    (``stack.extract(lane)`` recovers the unbatched model)."""
    x: Any
    y: Any
    noise: float
    steps: int
    init_ls: Any = None
    init_sf: Any = None
    owner: Any = None


# ---------------------------------------------------------------------------
# The plan IR
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One fused launch: the queries at ``indices`` share ``key`` and
    execute together under the pad decisions in ``pads`` (every padded
    axis length the launch will use, for golden-shape tests)."""
    kind: str
    key: Tuple
    indices: Tuple[int, ...]
    pads: Dict[str, int]


@dataclasses.dataclass(frozen=True)
class CohortLimits:
    """Bounds that CLOSE a service's bucket vocabulary, so the full set
    of launch shapes it can ever ask for is enumerable up front
    (``StepPlanner.enumerate_buckets``) and precompilable at startup
    (``SearchService.precompile``).

    ``d``/``q_grid`` come from the search space (encoded dimension and
    candidate count); ``max_obs`` bounds any single model's observation
    count (for targets: initial runs + max_iters; support models are
    bounded by the repository's deepest (workload, measure) history);
    ``max_lanes`` bounds how many model lanes one fused launch can carry
    (targets and support stacks summed across the cohort). The optional
    tuples pin the discrete knob values in play — RGPE sample counts,
    MOO Monte-Carlo draw counts, objective counts — and ``noises`` the
    fixed noise levels the (jit-static) fit launches will see.
    ``max_ehvi_boxes`` bounds the box-decomposition size of any front
    (2-objective fronts decompose into at most ``front+1`` staircase
    boxes; n>=3 fronts grow faster and dominate the vocabulary)."""
    d: int
    q_grid: int
    max_obs: int
    max_lanes: int = 1
    n_samples: Tuple[int, ...] = ()
    n_mc: Tuple[int, ...] = ()
    n_objectives: Tuple[int, ...] = ()
    max_ehvi_boxes: int = 1
    noises: Tuple[float, ...] = (0.1,)
    fit_steps: int = 120
    fit_warm_steps: int = 16


@dataclasses.dataclass
class StepPlan:
    """The planned step: ``queries`` in emission order, ``buckets`` one
    per fused launch, ``prep`` per-query planner precomputation (the
    EHVI box decompositions). ``stats()`` reports the fusion shape."""
    queries: List[Any]
    buckets: List[Bucket]
    prep: Dict[int, Any] = dataclasses.field(default_factory=dict)

    def stats(self) -> Dict[str, int]:
        return {"batches": len(self.buckets), "queries": len(self.queries)}


def _round_up(n: int, mult: int) -> int:
    return n if mult <= 1 else ((n + mult - 1) // mult) * mult


def _pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


def round_rows(n: int) -> int:
    """Row pad of a select-phase launch carrying ``n`` live rows."""
    return max(ROW_PAD_MIN, _pow2(n))


def row_pads(max_rows: int) -> List[int]:
    """Every row pad ``round_rows`` gives for 1..``max_rows`` rows: the
    closed vocabulary ``SearchService.precompile`` warms."""
    out, p = [], ROW_PAD_MIN
    while p <= round_rows(max_rows):
        out.append(p)
        p <<= 1
    return out


class StepPlanner:
    """Owns ALL bucketing/padding policy: which queries fuse (the bucket
    keys) and what every launch's padded shapes are. The historical
    contracts — observation axis to multiples of ``obs_round_to``,
    sample/EHVI candidate axis to ``q_round_to``, fused model/lane axis
    to a power of two, EHVI box count to a power of two — live here and
    nowhere else."""

    def __init__(self, *, obs_round_to: Optional[int] = None,
                 q_round_to: Optional[int] = None,
                 m_round_pow2: Optional[bool] = None,
                 mesh=None, data_axis: str = "data",
                 lane_shards: Optional[int] = None):
        self.obs_round_to = (OBS_ROUND_TO if obs_round_to is None
                             else obs_round_to)
        self.q_round_to = (GRID_ROUND_TO if q_round_to is None
                           else q_round_to)
        self.m_round_pow2 = (M_ROUND_POW2 if m_round_pow2 is None
                             else m_round_pow2)
        # data-parallel execution: with a mesh installed, every fused
        # lane axis additionally rounds up to a multiple of the mesh's
        # data-axis size, so shard_map splits each launch evenly across
        # devices. ``lane_shards`` overrides the divisor directly (for
        # policy tests on single-device hosts).
        self.mesh = auto_axes(mesh)
        self.data_axis = data_axis
        self.lane_shards = (mesh_axis_size(mesh, data_axis)
                            if lane_shards is None else int(lane_shards))

    # -- shared shape policy -------------------------------------------------
    def round_obs(self, n: int) -> int:
        return _round_up(n, self.obs_round_to)

    def round_grid(self, q: int) -> int:
        return _round_up(q, self.q_round_to)

    def round_models(self, m: int) -> int:
        m = _pow2(m) if self.m_round_pow2 else m
        return _round_up(m, self.lane_shards)

    def round_boxes(self, k: int) -> int:
        """Box-axis pad of a live ``k``-box front: small fronts pad to
        a power of two; past one launch block the axis pads to a chunk
        multiple instead (the launch scans fixed-size blocks there,
        bounding peak memory). The policy twin of ``_box_pads`` — the
        static closure analysis holds the two together."""
        return (_pow2(k) if k <= EHVI_BOX_CHUNK
                else _round_up(k, EHVI_BOX_CHUNK))

    def fit_targets(self, xs, ys, *, noise: float, steps: int = 120,
                    m_round_pow2: Optional[bool] = None) -> BatchedGP:
        """Fit a cohort of target GPs under the planner's jit-shape
        policy (the fused-fit twin of ``plan``: same observation-axis
        bucketing, same model-axis rule). ``m_round_pow2=False`` opts a
        fixed-size cohort (e.g. single-tenant ``run_search``) out of the
        power-of-two lane padding that only pays off when cohort size
        varies step to step. With a mesh installed the fit runs through
        the shard-mapped launch twins (lane axis split over the data
        axis), and the lane count rounds to a shard multiple either
        way."""
        launches = (sharded_fit_launches(self.mesh, self.data_axis)
                    if self.mesh is not None and self.lane_shards > 1
                    else None)
        return fit_gp_batched(
            xs, ys, noise=noise, steps=steps, round_to=self.obs_round_to,
            m_round_pow2=(self.m_round_pow2 if m_round_pow2 is None
                          else m_round_pow2),
            lane_round_to=self.lane_shards, launches=launches)

    # -- bucketing -----------------------------------------------------------
    def bucket_key(self, query) -> Tuple[str, Tuple]:
        """(kind, key): queries fuse into one launch iff both match.
        Shapes are read via ``np.shape`` — no materialisation, so
        device-resident grids/rows never sync to host just to plan."""
        if isinstance(query, PosteriorQuery):
            return "posterior", (int(np.shape(query.grid)[-2]),
                                 int(query.stack.x.shape[-1]))
        if isinstance(query, SampleQuery):
            return "sample", (int(query.n_samples),
                              int(np.shape(query.grid)[-2]),
                              int(query.stack.x.shape[-1]))
        if isinstance(query, LooSampleQuery):
            return "loo", (int(query.n_samples), query.gp.n)
        if isinstance(query, PosteriorDrawQuery):
            return "draw", (int(query.n_mc),
                            int(np.shape(query.mu)[0]))
        if isinstance(query, EhviQuery):
            if query.samples is None:   # posterior form: draw deferred
                return "ehvi", (len(query.mu), int(query.n_mc),
                                int(np.shape(query.mu[0])[0]))
            s_shape = np.shape(query.samples[0])
            return "ehvi", (len(query.samples), int(s_shape[0]),
                            int(s_shape[1]))
        if isinstance(query, FitQuery):
            # steps and noise are jit-static on the fit launch, so warm
            # and cold lanes land in DIFFERENT buckets by construction
            return "fit", (int(np.shape(query.x)[1]), int(query.steps),
                           float(query.noise))
        raise TypeError(f"not a query node: {query!r}")

    def plan(self, queries: Sequence) -> StepPlan:
        """Group queries into one ``Bucket`` per fused launch and fix
        every pad decision. No launches execute here; the one
        non-trivial planning cost is the EHVI box decomposition
        (``_pads_ehvi`` must know each front's box count to fix
        ``k_pad``), which is computed once per query on the host and
        carried to the executor via ``StepPlan.prep``."""
        with span("plan"):
            groups: Dict[Tuple[str, Tuple], List[int]] = {}
            for i, query in enumerate(queries):
                groups.setdefault(self.bucket_key(query), []).append(i)
            prep: Dict[int, Any] = {}
            buckets = []
            for (kind, key), idxs in groups.items():
                pads = getattr(self, f"_pads_{kind}")(
                    key, [queries[i] for i in idxs], idxs, prep)
                buckets.append(Bucket(kind, key, tuple(idxs), pads))
            return StepPlan(list(queries), buckets, prep)

    def _pads_posterior(self, key, queries, idxs, prep) -> Dict[str, int]:
        lanes = sum(q.stack.m for q in queries)
        return {"n_pad": self.round_obs(max(q.stack.n_max for q in queries)),
                "m_pad": self.round_models(lanes), "lanes": lanes}

    def _pads_sample(self, key, queries, idxs, prep) -> Dict[str, int]:
        lanes = sum(q.stack.m for q in queries)
        return {"n_pad": self.round_obs(max(q.stack.n_max for q in queries)),
                "q_pad": self.round_grid(key[1]),
                "m_pad": self.round_models(lanes), "lanes": lanes}

    def _pads_loo(self, key, queries, idxs, prep) -> Dict[str, int]:
        # the lane axis pads to a power of two like every other fused
        # launch: without it the LOO launch recompiles per cohort size
        # and the bucket vocabulary is open-ended
        lanes = len(queries)
        return {"n_pad": self.round_obs(key[1]),
                "l_pad": self.round_models(lanes), "lanes": lanes}

    def _pads_draw(self, key, queries, idxs, prep) -> Dict[str, int]:
        # deliberately exact: the draw combine is not jitted (q shrinks
        # every iteration and the arithmetic is trivially cheap), so
        # padding would buy nothing and only perturb memory traffic
        return {"lanes": len(queries)}

    def _pads_ehvi(self, key, queries, idxs, prep) -> Dict[str, int]:
        n_obj = key[0]
        k_max = 1
        with span("ehvi.boxes"):
            for i, query in zip(idxs, queries):
                observed = np.asarray(query.observed, np.float64)
                if observed.size and (observed.ndim != 2
                                      or observed.shape[1] != n_obj):
                    raise ValueError(
                        f"EhviQuery observed has shape {observed.shape} "
                        f"but carries {n_obj} objective sample arrays")
                los, his = nondominated_boxes(
                    pareto_front(observed.reshape(-1, n_obj)),
                    np.asarray(query.ref, np.float64))
                prep[i] = (los, his)
                k_max = max(k_max, los.shape[0])
        return {"k_pad": self.round_boxes(k_max),
                "q_pad": self.round_grid(key[2]),
                "l_pad": self.round_models(len(queries)),
                "lanes": len(queries)}

    def _pads_fit(self, key, queries, idxs, prep) -> Dict[str, int]:
        lanes = len(queries)
        n_max = max(int(np.shape(q.y)[0]) for q in queries)
        return {"n_pad": self.round_obs(n_max),
                "m_pad": self.round_models(lanes), "lanes": lanes}

    # -- the closed bucket vocabulary ----------------------------------------
    def _obs_pads(self, max_obs: int) -> List[int]:
        step = max(1, self.obs_round_to)
        return list(range(step, self.round_obs(max_obs) + 1, step))

    def _grid_pads(self, max_q: int) -> List[int]:
        step = max(1, self.q_round_to)
        return list(range(step, self.round_grid(max_q) + 1, step))

    def ehvi_grid_pads(self, limits: CohortLimits) -> List[int]:
        """The EHVI candidate-axis vocabulary: a session scores its
        remaining candidates, ``q_grid`` minus the configurations it has
        profiled, and it never holds more than ``max_obs`` observations —
        so only the grid buckets of ``[q_grid - max_obs, q_grid]``
        occur."""
        lo = max(1, limits.q_grid - limits.max_obs)
        return sorted({self.round_grid(q)
                       for q in range(lo, limits.q_grid + 1)})

    def _lane_pads(self, max_lanes: int) -> List[int]:
        # every fixed point of round_models up to the bound: the pow2
        # ladder, each rung lifted to a shard multiple when a mesh is
        # installed (so the enumerated vocabulary IS the sharded one)
        return sorted({self.round_models(m)
                       for m in range(1, max_lanes + 1)})

    def _box_pads(self, max_boxes: int) -> List[int]:
        out, p = [], 1
        while p < min(_pow2(max_boxes), EHVI_BOX_CHUNK):
            out.append(p)
            p <<= 1
        out.append(p)
        k = 2 * EHVI_BOX_CHUNK
        while k <= _round_up(max_boxes, EHVI_BOX_CHUNK):
            out.append(k)
            k += EHVI_BOX_CHUNK
        return out

    def fit_step_rungs(self, limits: CohortLimits) -> List[int]:
        """The fit leg's schedule-length vocabulary: the warm (short
        refine) rung and the cold (full) rung — deduplicated, since a
        service may disable warm starts by equating the two. A mutant
        that drops the warm rung here opens a vocabulary hole the
        closure analysis must catch (``repro.analysis.mutants``)."""
        rungs = {int(limits.fit_steps)}
        if limits.fit_warm_steps:
            rungs.add(int(limits.fit_warm_steps))
        return sorted(rungs)

    def enumerate_buckets(self, limits: CohortLimits,
                          ehvi_lanes: Optional[int] = None
                          ) -> List[Bucket]:
        """Walk the CLOSED launch-shape vocabulary a cohort bounded by
        ``limits`` can produce — one ``Bucket`` (empty ``indices``) per
        distinct jitted launch shape, keys stated at their padded values
        (every padded value is its own fixed point under the rounding
        policy, so a dummy query AT the key shape lands exactly on the
        enumerated launch). ``draw`` buckets are deliberately absent:
        the draw combine is not jitted, so it has no compile vocabulary.

        Per kind: posterior launches vary (n_pad, m_pad) at the fixed
        (q_grid, d); sample launches add the grid axis (RGPE scores at
        the target's own inputs, so q ranges over the observation
        buckets) and the sample count; LOO launches vary (n_pad, l_pad)
        per sample count; EHVI launches vary the candidate bucket (the
        remaining-candidate set shrinks every iteration), the box-axis
        pad, and the MOO lane pad — one lane per multi-objective session
        of a step, so up to ``ehvi_lanes`` (default ``max_lanes``) — per
        (n_obj, n_mc); fit launches vary (n_pad, m_pad) per (noise,
        steps-rung) — the warm and cold schedule lengths from
        ``fit_step_rungs``."""
        out: List[Bucket] = []
        obs = self._obs_pads(limits.max_obs)
        lanes = self._lane_pads(limits.max_lanes)
        for n_pad in obs:
            for m_pad in lanes:
                out.append(Bucket("posterior", (limits.q_grid, limits.d),
                                  (), {"n_pad": n_pad, "m_pad": m_pad,
                                       "lanes": m_pad}))
        for s in limits.n_samples:
            for q_pad in self._grid_pads(limits.max_obs):
                for n_pad in obs:
                    for m_pad in lanes:
                        out.append(Bucket(
                            "sample", (s, q_pad, limits.d), (),
                            {"n_pad": n_pad, "q_pad": q_pad,
                             "m_pad": m_pad, "lanes": m_pad}))
            for n_pad in obs:
                for l_pad in lanes:
                    out.append(Bucket("loo", (s, n_pad), (),
                                      {"n_pad": n_pad, "l_pad": l_pad,
                                       "lanes": l_pad}))
        moo_lanes = self._lane_pads(ehvi_lanes or limits.max_lanes)
        for n_obj in limits.n_objectives:
            for s in limits.n_mc:
                for q_pad in self.ehvi_grid_pads(limits):
                    for k_pad in self._box_pads(limits.max_ehvi_boxes):
                        for l_pad in moo_lanes:
                            out.append(Bucket(
                                "ehvi", (n_obj, s, q_pad), (),
                                {"k_pad": k_pad, "q_pad": q_pad,
                                 "l_pad": l_pad, "lanes": l_pad}))
        for noise in limits.noises:
            for steps in self.fit_step_rungs(limits):
                for n_pad in obs:
                    for m_pad in lanes:
                        out.append(Bucket(
                            "fit", (limits.d, steps, float(noise)), (),
                            {"n_pad": n_pad, "m_pad": m_pad,
                             "lanes": m_pad}))
        return out

    def launch_signature(self, bucket: Bucket) -> Tuple:
        """The jit-cache identity of a bucket's launch: kind plus every
        axis length the compiled program sees (exact key dims that the
        executor pads away are normalised to their padded value, so a
        live bucket compares equal to its enumerated twin). Under a
        mesh the shard count joins the signature — the shard-mapped
        twin of a shape is a DIFFERENT compiled program than the
        single-device one, and the precompiled vocabulary must say
        which family it warmed."""
        k, key, p = bucket.kind, bucket.key, bucket.pads
        if k == "posterior":
            sig = ("posterior", key[0], key[1], p["n_pad"], p["m_pad"])
        elif k == "sample":
            sig = ("sample", key[0], p["q_pad"], key[2],
                   p["n_pad"], p["m_pad"])
        elif k == "loo":
            sig = ("loo", key[0], p["n_pad"], p["l_pad"])
        elif k == "draw":   # unjitted: exact shapes, no compile identity
            sig = ("draw", key[0], key[1], p["lanes"])
        elif k == "ehvi":
            sig = ("ehvi", key[0], key[1], p["q_pad"], p["k_pad"],
                   p["l_pad"])
        elif k == "fit":
            # the schedule length is a jit-static rung of the closed
            # vocabulary, not an axis — named so golden-signature tests
            # can't confuse it with the obs pad
            sig = ("fit", key[0], p["n_pad"], p["m_pad"],
                   ("steps", key[1]), ("noise", key[2]))
        else:
            raise ValueError(f"unknown bucket kind {k!r}")
        if self.lane_shards > 1 and k != "draw":
            sig = sig + (("shards", self.lane_shards),)
        return sig


# ---------------------------------------------------------------------------
# Execution: one launch per bucket, scatter to owners
# ---------------------------------------------------------------------------


def _count(counters: Optional[dict], kind: str, queries: int,
           lanes: int, **extra: int) -> None:
    if counters is None:
        return
    c = counters.setdefault(kind, {})
    for k, v in dict(launches=1, queries=queries, lanes=lanes,
                     **extra).items():
        c[k] = c.get(k, 0) + v


def _box_counts(bucket: Bucket, plan: StepPlan) -> Dict[str, int]:
    """An EHVI launch's boxes over its live lanes: each lane's own box
    count, and the box axis it was padded to."""
    return {"boxes_live": sum(int(plan.prep[i][0].shape[0])
                              for i in bucket.indices),
            "boxes_padded": bucket.pads["k_pad"] * len(bucket.indices)}


def flatten_counters(nested: dict, counters: Optional[dict],
                     kinds: Sequence[str]) -> None:
    """Merge ``execute``'s per-kind counters into the historical flat
    ``launches``/``queries``/``lanes`` dict (EHVI adds ``boxes_live`` /
    ``boxes_padded``) the single-kind wrappers
    (``batched_posterior_multi`` & co.) expose."""
    if counters is None:
        return
    for kind in kinds:
        for k, v in nested.get(kind, {}).items():
            counters[k] = counters.get(k, 0) + v


# -- shard-mapped launch twins ----------------------------------------------
# One jitted twin per (mesh, kind, donate): the base (unjitted) bucket
# launch body runs under shard_map with every argument — and every
# output — split on its leading lane axis over the mesh's data axis.
# Lane axes are multiples of the shard count by planner policy
# (``StepPlanner.round_models``), so shapes always divide evenly. Each
# twin is registered with ``launch.compile_stats`` at construction, so
# the compile-once accounting (``plan_compile_misses``) covers the
# sharded vocabulary exactly like the single-device one.
_SHARDED_LAUNCHES: Dict[Tuple, Any] = {}


def _shard_base(kind: str):
    """(base fn, takes-static-impl, donate_argnums) for one launch kind.
    Bases are the UNJITTED bodies — the sharded twin re-jits them under
    its own shard_map wrapper (donating the same per-step-rebuilt
    buffers as the single-device donating twins)."""
    if kind == "posterior":
        return _batched_posterior.__wrapped__, True, (2, 3, 4, 5, 6)
    if kind == "sample":
        return _batched_sample_launch.__wrapped__, True, (2, 3, 4, 5, 6, 7)
    if kind == "loo":
        return _batched_loo_launch.__wrapped__, False, (0, 1, 2, 3)
    if kind == "ehvi":
        from .acquisition import _ehvi_box_eval
        return _ehvi_box_eval, False, (0, 1, 2, 3)
    if kind == "fused_posterior":
        from repro.kernels.fused_posterior.ops import fused_posterior_ei
        return fused_posterior_ei, True, (2, 3, 4, 5, 6)
    if kind == "fused_ehvi":
        from repro.kernels.fused_ehvi.ops import fused_ehvi
        return fused_ehvi, True, (0, 1, 2, 3, 4, 5, 6, 7)
    if kind == "fused_fit":
        from repro.kernels.fused_fit.ops import fused_fit
        return fused_fit, True, (3, 4)
    raise ValueError(f"no sharded twin for launch kind {kind!r}")


def sharded_fused_fit_launch(mesh, axis: str, donate: bool):
    """Shard-mapped twin of the fused fit launch. The generic wrapper
    below only threads ``impl`` statically, but the fit leg's schedule
    length and noise are jit-static rungs of the vocabulary too — so it
    gets its own wrapper binding all three before shard_map. One jitted
    entry covers every (steps, noise) rung (jit caches per static), and
    only the per-step-rebuilt warm-start rows are donated."""
    cache_key = (mesh, axis, "fused_fit", donate)
    hit = _SHARDED_LAUNCHES.get(cache_key)
    if hit is not None:
        return hit
    from repro.kernels.fused_fit.ops import fused_fit
    from repro.launch.compile_stats import register_launch
    spec = PartitionSpec(axis)

    def run(x, y, mask, init_ls, init_sf, *, steps: int = 120,
            noise: float = 0.1, lr: float = 0.05, impl: str = "xla"):
        body = functools.partial(fused_fit, steps=steps, noise=noise,
                                 lr=lr, impl=impl)
        return shard_map(body, mesh, in_specs=(spec,) * 5,
                         out_specs=spec, check_vma=False)(
            x, y, mask, init_ls, init_sf)

    kw: Dict[str, Any] = {"static_argnames": ("steps", "noise", "lr",
                                              "impl")}
    if donate:
        kw["donate_argnums"] = (3, 4)
    launch = jax.jit(run, **kw)
    register_launch(
        f"fused_fit_sharded{'_donated' if donate else ''}"
        f"_x{mesh_axis_size(mesh, axis)}_{len(_SHARDED_LAUNCHES)}",
        launch)
    sharding = NamedSharding(mesh, spec)

    def placed(*args, **kwargs):
        return launch(*(jax.device_put(a, sharding) for a in args),
                      **kwargs)

    _SHARDED_LAUNCHES[cache_key] = placed
    return placed


def sharded_bucket_launch(mesh, axis: str, kind: str, donate: bool):
    """The jitted shard-mapped twin of one bucket launch kind, cached
    per (mesh, axis, kind, donate) so repeated steps re-enter one jit
    cache (and ``CompileWatcher`` sees one stable tracked entry)."""
    if kind == "fused_fit":   # extra statics: steps/noise/lr rungs
        return sharded_fused_fit_launch(mesh, axis, donate)
    cache_key = (mesh, axis, kind, donate)
    hit = _SHARDED_LAUNCHES.get(cache_key)
    if hit is not None:
        return hit
    from repro.launch.compile_stats import register_launch
    base, has_impl, donate_nums = _shard_base(kind)
    spec = PartitionSpec(axis)

    if has_impl:
        def run(*args, impl: str = "xla"):
            body = functools.partial(base, impl=impl)
            return shard_map(body, mesh, in_specs=(spec,) * len(args),
                             out_specs=spec, check_vma=False)(*args)
        kw: Dict[str, Any] = {"static_argnames": ("impl",)}
    else:
        def run(*args):
            return shard_map(base, mesh, in_specs=(spec,) * len(args),
                             out_specs=spec, check_vma=False)(*args)
        kw = {}
    if donate:
        kw["donate_argnums"] = donate_nums
    launch = jax.jit(run, **kw)
    register_launch(
        f"{kind}_sharded{'_donated' if donate else ''}"
        f"_x{mesh_axis_size(mesh, axis)}_{len(_SHARDED_LAUNCHES)}",
        launch)
    sharding = NamedSharding(mesh, spec)

    def placed(*args, **kwargs):
        # one argument placement for every caller: a step's bucket args
        # mix host-built stacks (uncommitted) with outputs of earlier
        # sharded launches (committed to the mesh), and precompile's
        # dummies are all uncommitted — jit caches per argument
        # sharding, so without normalisation a "warmed" shape compiles
        # AGAIN the first time it arrives mesh-committed mid-serve.
        # device_put is a no-op for arrays already carrying this
        # sharding, so the steady state pays nothing.
        return launch(*(jax.device_put(a, sharding) for a in args),
                      **kwargs)

    _SHARDED_LAUNCHES[cache_key] = placed
    return placed


def _draw_launch(keys, mu, var, y_std, y_mean, n_mc: int):
    """All draw lanes of one bucket in one stacked batch. Per-lane eps
    is ``normal(key, (n_mc, q))`` — the identical stream the per-session
    loop consumes, so fusion never changes draws."""
    q = mu.shape[1]
    eps = jax.vmap(lambda k: jax.random.normal(k, (n_mc, q)))(keys)
    sm = mu[:, None, :] + eps * jnp.sqrt(var)[:, None, :]
    return sm * y_std[:, None, None] + y_mean[:, None, None]


def _materialise_ehvi_draws(query, s: int, q: int):
    """Raw-scale draws of a posterior-form ``EhviQuery`` on the vmapped
    (non-fused) path: one ``_draw_launch`` over the query's objectives,
    consuming the same per-objective keys the fused kernel would — so
    the two executors' EHVI rows agree to float roundoff."""
    keys = jnp.stack([jnp.asarray(k) for k in query.keys])
    parts = [jnp.stack([jnp.asarray(a, jnp.float32) for a in t])
             for t in (query.mu, query.var)]
    scal = [jnp.asarray(np.asarray(t, np.float32)) for t in
            (query.y_std, query.y_mean)]
    draws = _draw_launch(keys, parts[0], parts[1], scal[0], scal[1],
                         n_mc=s)
    return [draws[d] for d in range(draws.shape[0])]


class PlanExecutor:
    """Executes a ``StepPlan``: one fused launch per bucket, results
    returned in query order. Scatter: any query whose ``owner`` is
    callable has ``owner(result)`` invoked (in query order, so owners
    that overlay earlier owners' state — e.g. RGPE mixes over target
    posteriors — see a deterministic sequence). ``counters`` (optional
    dict) collects ``{kind: {launches, queries, lanes}}``.

    ``fused_posterior=True`` dispatches posterior buckets to the fused
    ``kernels.fused_posterior`` launch (masked Cholesky-solve ->
    posterior -> EI in one kernel) instead of the vmapped-XLA
    ``_batched_posterior`` chain; ``fused_ehvi=True`` likewise
    dispatches EHVI buckets to ``kernels.fused_ehvi`` (per-lane draw
    affine + box reduction in one kernel) instead of the vmapped
    ``_ehvi_box_launch``. The defaults stay the vmapped paths, which
    double as the fused kernels' parity baselines. Results are
    identical up to float roundoff either way; queries carrying
    ``best`` additionally get the EI row.

    ``donate`` picks the donating jitted twins for every bucket launch
    (fused or vmapped): the per-step-rebuilt buffers — stacked
    observation caches, padded grids, box decompositions, draws — are
    handed back to XLA for the launch intermediates. It is resolved
    ONCE at construction (default: donate on a TPU backend), so
    ``SearchService.precompile`` warms exactly the jit entry serving
    dispatches — the two can never disagree via a per-call backend
    probe. Single-query buckets guard against aliasing: with no
    lane-padding to force a copy, the "stacked" buffers can BE a
    session's cached stack arrays, which donation would delete.

    ``mesh`` turns on data-parallel execution: every jitted bucket
    launch is replaced by its shard-mapped twin splitting the lane axis
    over the mesh's ``data_axis`` (lanes are independent models, so
    per-lane results match the single-device path up to float roundoff
    — XLA fuses the per-shard batch size differently, nothing more; the
    DISCRETE trajectory, which configs a search selects, is unchanged).
    The paired ``StepPlanner(mesh=...)`` rounds lane pads to shard
    multiples so shapes always divide; ``resolve_impl`` sees the
    per-shard cell volume, so ``"auto"`` routes each DEVICE's slice.
    The unjitted ``draw`` combine stays unsharded — exact shapes, no
    compile identity, trivial arithmetic."""

    def __init__(self, *, impl: str = "auto",
                 fused_posterior: bool = False,
                 fused_ehvi: bool = False,
                 donate: Optional[bool] = None,
                 mesh=None, data_axis: str = "data"):
        self.impl = impl
        self.fused_posterior = fused_posterior
        self.fused_ehvi = fused_ehvi
        self.donate = (jax.default_backend() == "tpu" if donate is None
                       else bool(donate))
        self.mesh = auto_axes(mesh)
        self.data_axis = data_axis
        self.lane_shards = mesh_axis_size(mesh, data_axis)

    def _launch(self, kind: str, plain, donated):
        """The launch for one bucket kind under this executor's config:
        the shard-mapped twin when a mesh is installed, else the donating
        or plain single-device jit."""
        if self.mesh is not None and self.lane_shards > 1:
            return sharded_bucket_launch(self.mesh, self.data_axis, kind,
                                         self.donate)
        return donated if self.donate else plain

    def bucket_impl(self, bucket: Bucket, impl: Optional[str] = None
                    ) -> str:
        """The kernel impl one bucket's launch runs: ``impl`` (default:
        the executor's) with ``"auto"`` resolved on the launch's cell
        count per shard. Kinds without a Pallas twin on this executor
        (LOO, draws, the vmapped EHVI chain) run XLA."""
        impl = self.impl if impl is None else impl
        kind, key, p = bucket.kind, bucket.key, bucket.pads
        if kind == "posterior":
            cells = p["m_pad"] * key[0] * p["n_pad"]
        elif kind == "sample":
            cells = p["m_pad"] * p["q_pad"] * p["n_pad"]
        elif kind == "fit":
            cells = p["m_pad"] * p["n_pad"] * p["n_pad"] * key[1]
        elif kind == "ehvi" and self.fused_ehvi:
            cells = p["l_pad"] * key[1] * p["q_pad"] * p["k_pad"]
        else:
            return "xla"
        return resolve_impl(impl, cells=cells, shards=self.lane_shards)

    def execute(self, plan: StepPlan, *, counters: Optional[dict] = None,
                impl: Optional[str] = None) -> List[Any]:
        impl = self.impl if impl is None else impl
        results: List[Any] = [None] * len(plan.queries)
        for bucket in plan.buckets:
            queries = [plan.queries[i] for i in bucket.indices]
            # each kind's launch splits into pack / launch / unpack spans
            out = getattr(self, f"_exec_{bucket.kind}")(
                bucket, queries, plan, impl)
            for i, r in zip(bucket.indices, out):
                results[i] = r
            _count(counters, bucket.kind, len(queries),
                   bucket.pads.get("m_pad",
                                   bucket.pads.get("l_pad",
                                                   bucket.pads["lanes"])),
                   **(_box_counts(bucket, plan) if bucket.kind == "ehvi"
                      else {}))
        with span("scatter"):
            for query, result in zip(plan.queries, results):
                if callable(query.owner):
                    query.owner(result)
        return results

    # -- per-kind launches ---------------------------------------------------
    @staticmethod
    def _stack_parts(queries, n_pad: int, q: int, d: int,
                     q_pad: Optional[int] = None):
        """Assemble the padded (ls, sf, x, mask, chol, alpha, xq) lanes
        shared by the posterior and sample launches."""
        xs, masks, chols, alphas, lss, sfs, xqs = [], [], [], [], [], [], []
        for query in queries:
            st = query.stack
            x, mask, chol, alpha = _pad_stack_obs(st, n_pad)
            xs.append(x)
            masks.append(mask)
            chols.append(chol)
            alphas.append(alpha)
            lss.append(st.log_lengthscales)
            sfs.append(st.log_signal)
            xq = jnp.asarray(query.grid, jnp.float32)
            if xq.ndim == 2:
                xq = jnp.broadcast_to(xq[None], (st.m, q, d))
            if q_pad is not None and q_pad > q:
                xq = jnp.pad(xq, ((0, 0), (0, q_pad - q), (0, 0)),
                             mode="edge")
            xqs.append(xq)
        return [jnp.concatenate(a) for a in
                (lss, sfs, xs, masks, chols, alphas, xqs)]

    @staticmethod
    def _pad_lanes(parts, m_pad: int):
        m_total = int(parts[0].shape[0])
        if m_pad > m_total:
            parts = [jnp.concatenate(
                [a, jnp.broadcast_to(a[:1],
                                     (m_pad - m_total,) + a.shape[1:])])
                for a in parts]
        return parts

    def _fresh_parts(self, queries, parts):
        """Aliasing guard for donated launches: a single-query bucket's
        "stacked" parts come out of ``jnp.concatenate([x])`` /
        ``jnp.asarray``, which RETURN the input when shapes already
        match — i.e. the session's cached stack buffers themselves.
        Donating those would delete live cache state, so copy them
        first. Multi-query buckets always concatenate (a real copy)."""
        if self.donate and len(queries) == 1:
            parts = [jnp.array(p, copy=True) for p in parts]
        return parts

    def _exec_posterior(self, bucket, queries, plan, impl):
        q, d = bucket.key
        n_pad, m_pad = bucket.pads["n_pad"], bucket.pads["m_pad"]
        with span("pack", kind="posterior"):
            parts = self._fresh_parts(
                queries, self._stack_parts(queries, n_pad, q, d))
            r_impl = self.bucket_impl(bucket, impl)
            if self.fused_posterior:
                from repro.kernels.fused_posterior import fused_launch_fn
                # per-lane incumbents; lanes without an EI head get 0.0
                # (the EI row is computed either way — shape stability —
                # and simply not returned for those queries)
                best = jnp.concatenate([
                    jnp.full((query.stack.m,),
                             0.0 if query.best is None
                             else float(query.best),
                             jnp.float32) for query in queries])
                parts = self._pad_lanes(parts + [best], m_pad)
                launch = self._launch("fused_posterior",
                                      fused_launch_fn(donate=False),
                                      fused_launch_fn(donate=True))
            else:
                parts = self._pad_lanes(parts, m_pad)
                launch = self._launch("posterior", _batched_posterior,
                                      _batched_posterior_donated)
        with span("launch", kind="posterior"):
            res = launch(*parts, impl=r_impl)
        mu, var = res[:2]
        ei = res[2] if self.fused_posterior else None
        with span("unpack", kind="posterior"):
            out, off = [], 0
            for query in queries:
                rows = slice(off, off + query.stack.m)
                if query.best is None:
                    out.append((mu[rows], var[rows]))
                elif ei is not None:
                    out.append((mu[rows], var[rows], ei[rows]))
                else:
                    out.append((mu[rows], var[rows], expected_improvement(
                        mu[rows], var[rows], float(query.best))))
                off += query.stack.m
        return out

    def _exec_sample(self, bucket, queries, plan, impl):
        n_samples, q, d = bucket.key
        n_pad, q_pad, m_pad = (bucket.pads["n_pad"], bucket.pads["q_pad"],
                               bucket.pads["m_pad"])
        with span("pack", kind="sample"):
            parts = self._fresh_parts(
                queries,
                self._stack_parts(queries, n_pad, q, d, q_pad=q_pad))
            keys_cat = jnp.concatenate(
                [jnp.asarray(query.keys) for query in queries])
            # exact-shape draws (one dispatch for the bucket), THEN pad:
            # the grid padding that keeps jit shapes stable across steps
            # must never perturb a lane's PRNG stream
            eps = jax.vmap(
                lambda k: jax.random.normal(k, (n_samples, q)))(keys_cat)
            if q_pad > q:
                eps = jnp.pad(eps, ((0, 0), (0, 0), (0, q_pad - q)))
            parts = self._pad_lanes(parts + [eps], m_pad)
            r_impl = self.bucket_impl(bucket, impl)
            launch = self._launch("sample", _batched_sample_launch,
                                  _batched_sample_launch_donated)
        with span("launch", kind="sample"):
            s = launch(*parts, impl=r_impl)
        with span("unpack", kind="sample"):
            out, off = [], 0
            for query in queries:
                out.append(s[off:off + query.stack.m, :, :q])
                off += query.stack.m
        return out

    def _exec_loo(self, bucket, queries, plan, impl):
        n_samples, n = bucket.key
        n_pad = bucket.pads["n_pad"]
        p = n_pad - n
        with span("pack", kind="loo"):
            chols, alphas, ys = [], [], []
            for query in queries:
                gp = query.gp
                chol = jnp.pad(gp.chol, ((0, p), (0, p)))
                if p:
                    bump = jnp.concatenate([jnp.zeros((n,), jnp.float32),
                                            jnp.ones((p,), jnp.float32)])
                    chol = chol + jnp.diag(bump)
                chols.append(chol)
                alphas.append(jnp.pad(gp.alpha, (0, p)))
                ys.append(jnp.pad(gp.y, (0, p)))
            keys = jnp.stack([jnp.asarray(query.key) for query in queries])
            eps = jax.vmap(
                lambda k: jax.random.normal(k, (n_samples, n)))(keys)
            if p:
                eps = jnp.pad(eps, ((0, 0), (0, 0), (0, p)))
            parts = self._pad_lanes(
                [jnp.stack(chols), jnp.stack(alphas), jnp.stack(ys), eps],
                bucket.pads["l_pad"])
            # every LOO part is stacked fresh above (jnp.stack always
            # copies), so donation needs no single-query guard here
            launch = self._launch("loo", _batched_loo_launch,
                                  _batched_loo_launch_donated)
        with span("launch", kind="loo"):
            s = launch(*parts)
        with span("unpack", kind="loo"):
            return [s[j, :, :n] for j in range(len(queries))]

    def _exec_draw(self, bucket, queries, plan, impl):
        n_mc, _q = bucket.key
        with span("pack", kind="draw"):
            parts = [jnp.stack([jnp.asarray(getattr(query, f))
                                for query in queries])
                     for f in ("key", "mu", "var", "y_std", "y_mean")]
        with span("launch", kind="draw"):
            draws = _draw_launch(*parts, n_mc=n_mc)
        with span("unpack", kind="draw"):
            return [draws[j] for j in range(len(queries))]

    def _exec_ehvi(self, bucket, queries, plan, impl):
        if self.fused_ehvi:
            return self._exec_ehvi_fused(bucket, queries, plan, impl)
        _n_obj, s, q = bucket.key
        q_pad, l_pad = bucket.pads["q_pad"], bucket.pads["l_pad"]
        with span("pack", kind="ehvi"):
            los, his, refs = self._ehvi_fronts(bucket, queries, plan)
            ps = []
            for query in queries:
                samples = (query.samples if query.samples is not None
                           else _materialise_ehvi_draws(query, s, q))
                # +inf candidates gain nothing and are sliced off below
                ps.append(np.stack(
                    [np.pad(np.asarray(sm, np.float32),
                            ((0, 0), (0, q_pad - q)),
                            constant_values=np.inf)
                     for sm in samples]))
            parts = [jnp.asarray(np.stack(a).astype(np.float32))
                     for a in (los, his, refs, ps)]
            parts = self._pad_lanes(parts, l_pad)
            # all four parts are host-assembled fresh every step
            # (np.stack -> device transfer), so donation is
            # unconditionally alias-safe
            launch = self._launch("ehvi", _ehvi_box_launch,
                                  _ehvi_box_launch_donated)
        with span("launch", kind="ehvi"):
            out = launch(*parts)
        with span("unpack", kind="ehvi"):
            return [np.asarray(out[j])[:q] for j in range(len(queries))]

    @staticmethod
    def _ehvi_fronts(bucket, queries, plan):
        """Each lane's box decomposition (from the plan's ``prep``),
        padded to the bucket's ``k_pad``, and its reference point."""
        k_pad = bucket.pads["k_pad"]
        los, his, refs = [], [], []
        for i, query in zip(bucket.indices, queries):
            lo, hi = plan.prep[i]
            pad = k_pad - lo.shape[0]
            # zero-volume padding: lo = hi = +inf clips every overlap to 0
            los.append(np.pad(lo, ((0, pad), (0, 0)),
                              constant_values=np.inf))
            his.append(np.pad(hi, ((0, pad), (0, 0)),
                              constant_values=np.inf))
            refs.append(np.asarray(query.ref, np.float32))
        return los, his, refs

    def _exec_ehvi_fused(self, bucket, queries, plan, impl):
        """One ``kernels.fused_ehvi`` launch for the bucket: the draw
        affine runs inside the kernel, so the (L, D, S, q) raw-scale
        draw tensor never round-trips through HBM. Sample-form queries
        still fuse via the identity affine (mu = 0, var = 1, y = eps):
        the kernel then reproduces their precomputed draws exactly."""
        from repro.kernels.fused_ehvi import fused_ehvi_launch_fn
        n_obj, s, q = bucket.key
        q_pad, l_pad = bucket.pads["q_pad"], bucket.pads["l_pad"]
        pq = q_pad - q
        with span("pack", kind="ehvi"):
            los, his, refs = self._ehvi_fronts(bucket, queries, plan)
            # exact-shape draws for every posterior-form lane of the
            # bucket in ONE dispatch — normal(key, (n_mc, q)) per
            # objective, the identical stream _draw_launch and the
            # per-session loop consume
            key_rows = [jnp.asarray(k) for query in queries
                        if query.samples is None for k in query.keys]
            eps_all = (jax.vmap(lambda k: jax.random.normal(k, (s, q)))(
                jnp.stack(key_rows)) if key_rows else None)
            mus, vars_, yms, yss, epss = [], [], [], [], []
            off = 0
            for query in queries:
                if query.samples is None:
                    # padded candidates carry mu = +inf / var = 0: their
                    # draws land at +inf and gain nothing
                    mus.append(np.pad(
                        np.stack([np.asarray(m, np.float32)
                                  for m in query.mu]),
                        ((0, 0), (0, pq)), constant_values=np.inf))
                    vars_.append(np.pad(
                        np.stack([np.asarray(v, np.float32)
                                  for v in query.var]), ((0, 0), (0, pq))))
                    yms.append(np.asarray(query.y_mean, np.float32))
                    yss.append(np.asarray(query.y_std, np.float32))
                    eps = eps_all[off:off + n_obj]
                    off += n_obj
                    if pq:
                        eps = jnp.pad(eps, ((0, 0), (0, 0), (0, pq)))
                    epss.append(eps)
                else:
                    # identity affine; the +inf pad rides on the samples
                    mus.append(np.zeros((n_obj, q_pad), np.float32))
                    vars_.append(np.ones((n_obj, q_pad), np.float32))
                    yms.append(np.zeros((n_obj,), np.float32))
                    yss.append(np.ones((n_obj,), np.float32))
                    epss.append(jnp.asarray(np.stack(
                        [np.pad(np.asarray(sm, np.float32),
                                ((0, 0), (0, pq)), constant_values=np.inf)
                         for sm in query.samples])))
            parts = [jnp.asarray(np.stack(a).astype(np.float32))
                     for a in (los, his, refs, mus, vars_, yms, yss)]
            parts.append(jnp.stack(epss))
            parts = self._pad_lanes(parts, l_pad)
            r_impl = self.bucket_impl(bucket, impl)
            # every argument is rebuilt per step (host-assembled stacks,
            # fresh draws), so the donating twin is alias-safe here too
            launch = self._launch("fused_ehvi",
                                  fused_ehvi_launch_fn(donate=False),
                                  fused_ehvi_launch_fn(donate=True))
        with span("launch", kind="ehvi"):
            out = launch(*parts, impl=r_impl)
        with span("unpack", kind="ehvi"):
            return [np.asarray(out[j])[:q] for j in range(len(queries))]

    def _exec_fit(self, bucket, queries, plan, impl):
        """One ``kernels.fused_fit`` launch for the bucket: pack the raw
        observations host-side (vectorised standardisation, zero-padded
        lanes), overlay warm-start rows, fit every lane in one launch,
        and hand each query ``(stack, lane)`` into the bucket's fitted
        ``BatchedGP``. Only the warm-start rows are donated — the
        packed x/y/mask become the stack the posterior legs query, so
        they must outlive the launch."""
        from repro.kernels.fused_fit import fused_fit_launch_fn
        d, steps, noise = bucket.key
        n_pad, m_pad = bucket.pads["n_pad"], bucket.pads["m_pad"]
        with span("pack", kind="fit"):
            xs = [np.asarray(query.x, np.float32) for query in queries]
            ys = [np.asarray(query.y, np.float32) for query in queries]
            ns = [int(yi.shape[0]) for yi in ys]
            if m_pad > len(queries):   # padded lanes repeat lane 0
                extra = m_pad - len(queries)
                xs += [xs[0]] * extra
                ys += [ys[0]] * extra
                ns += [ns[0]] * extra
            x_np, ysd, mask_np, y_mean, y_std = _pack_fit_lanes(
                xs, ys, ns, n_pad)
            ils = np.zeros((m_pad, d), np.float32)
            isf = np.zeros((m_pad,), np.float32)
            for j, query in enumerate(queries):
                if query.init_ls is not None:
                    ils[j] = np.asarray(query.init_ls, np.float32)
                    isf[j] = np.float32(query.init_sf)
            gx = jnp.asarray(x_np)
            gy = jnp.asarray(ysd)
            gmask = jnp.asarray(mask_np)
            # all five launch args are host-built fresh above (device
            # transfers of new numpy buffers), so donation is alias-safe
            # without the single-query guard; only gils/gisf (the donated
            # positions) die at launch — x/y/mask stay live to seed the
            # returned BatchedGP
            gils = jnp.asarray(ils)
            gisf = jnp.asarray(isf)
            r_impl = self.bucket_impl(bucket, impl)
            launch = self._launch("fused_fit",
                                  fused_fit_launch_fn(donate=False),
                                  fused_fit_launch_fn(donate=True))
        with span("launch", kind="fit"):
            log_ls, log_sf, chol, alpha = launch(
                gx, gy, gmask, gils, gisf, steps=steps, noise=noise,
                impl=r_impl)
        with span("unpack", kind="fit"):
            stack = BatchedGP(gx, gy, gmask, jnp.asarray(y_mean),
                              jnp.asarray(y_std), log_ls, log_sf, noise,
                              chol, alpha, jnp.asarray(ns, jnp.int32))
            return [(stack, j) for j in range(len(queries))]
