"""Multi-tenant configuration-search service.

Karasu's premise (paper §III) is many users sharing one performance-data
repository, each running their own BO search against it. ``run_search``
serves exactly one tenant and refits its GPs in Python loops; this
module serves N tenants concurrently with the continuous-batching idiom
of ``ServeEngine``: a fixed pool of session slots, ``submit`` queues a
search, admission plays the role of prefill (the random initial
profiling runs), and every ``step`` advances ALL active sessions by one
BO iteration ("decode").

Two axes are batched/overlapped across tenants:

  - **Model math**: every step is an explicit collect → plan → execute
    → scatter round over the query-plan layer (``repro.serve.plan``):
    the step COLLECTS query nodes from every ready session — one
    ``PosteriorQuery`` per target stack and per RGPE support stack, one
    ``PosteriorDrawQuery`` per (MOO session, objective) lane, one
    ``EhviQuery`` per MOO session — each tagged with its owner; the
    ``StepPlanner`` groups them into buckets (owning ALL
    bucketing/padding policy); the ``PlanExecutor`` runs one fused
    launch per bucket (``impl="auto"`` routes to the Pallas matern
    kernel on TPU when the fused batch justifies it); and the step
    SCATTERS results back to their owning sessions. Target fits share
    one vmapped Adam/Cholesky per (search space, noise) group under the
    same planner policy, and ALL karasu sessions' RGPE ensembles score
    through ONE padded ranking-loss launch (``compute_weights_multi``,
    whose sample draws ride the same plan). RGPE mixing and the
    acquisitions (EI, constrained EI, MC-EHVI) are applied to the
    scattered rows as vectorised array ops, not per-session loops.
    ``fuse_posteriors=False`` restores the per-ensemble posterior loop
    and the per-candidate MC-EHVI reference, ``fuse_samples=False`` the
    per-job draw loop and per-session numpy EHVI — the
    parity/benchmark baselines.
  - **Profiling**: cluster runs execute through a ``ProfileExecutor``
    (``serve/profile_executor.py``). A session whose run is in flight
    sits in the explicit ``WAITING_PROFILE`` state while every session
    whose result landed keeps fitting/scoring — the step rate is set by
    the hardware, not by the slowest tenant's profiler. The default
    ``SyncProfileExecutor`` reproduces the fully synchronous service
    bitwise.

Sessions may be single-objective (``objective=...``) or multi-objective
(``objectives=[a, b, ...]``, paper §III-D: MC-EHVI weighted by every
constraint's probability of feasibility — 2 objectives evaluate via the
staircase envelope, n >= 3 via the non-dominated box decomposition, both
as ``EhviQuery`` plan nodes); all kinds mix freely in one step and share
the same fused fit/weight/posterior launches. ``run_search_moo`` is a
thin driver over this path.

Support models come from one ``SupportModelStore`` shared by every
tenant and invalidated incrementally per (workload, measure) when
``add_run`` bumps that workload's repository version — results a tenant
publishes mid-search become another tenant's support data on its very
next step.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.acquisition import (mc_ehvi, mc_ehvi_batched, mc_ehvi_nd,
                                    pareto_of_observations,
                                    probability_of_feasibility)
from repro.core.bo import (KEY_PURPOSE_MOO_EHVI, KEY_PURPOSE_RGPE, BOConfig,
                           KarasuContext, ProfileFn, _acquisition,
                           _best_index_so_far, _feasible,
                           _model_posteriors_augmented, _should_stop_early,
                           _target_runs, derive_key, derive_keys)
from repro.core.encoding import SearchSpace
from repro.core.gp import (GP, BatchedGP, GPParams, _pad_stack_obs,
                           batched_posterior)
from repro.core.repository import Repository
from repro.core.rgpe import WeightJob, mix_weighted
from repro.core.selection import CandidateIndex
from repro.kernels.ranking_loss import ranking_loss_launch_fn
from repro.core.types import (BOResult, Constraint, Objective, Observation,
                              RunRecord)
from repro.launch.compile_stats import CompileWatcher
from repro.launch.spans import root, span
from repro.serve.plan import (CohortLimits, EhviQuery, FitQuery,
                              LooSampleQuery, PlanExecutor,
                              PosteriorDrawQuery, PosteriorQuery,
                              SampleQuery, StepPlan, StepPlanner, row_pads)
from repro.serve.profile_executor import (ProfileJob, ProfileOutcome,
                                          SyncProfileExecutor)

# session states
READY = "ready"                        # observations current, can fit/score
WAITING_PROFILE = "waiting_profile"    # >=1 profiling run in flight

# The service's declared PRNG schedule: every per-iteration key it
# consumes derives as derive_key(session.key, purpose, iteration,
# index) with exactly these purposes. ``repro.analysis.prng_audit``
# cross-checks this declaration against ``bo.KEY_PURPOSES`` and proves
# the enumerated tree collision-free — extend it when a new consumer
# joins the schedule.
KEY_SCHEDULE = (
    (KEY_PURPOSE_RGPE, "per-measure RGPE support/LOO draw keys"),
    (KEY_PURPOSE_MOO_EHVI, "per-objective MOO posterior-draw keys"),
)


def _absorb_target_posts(posts, owners, tgts, mu, var) -> None:
    """Record one target stack's grid-posterior rows into each owning
    (session, measure) slot — shared by the fused plan and the loop
    fallback so the posterior dict shape cannot diverge between them.
    The stack's scalers come from one host copy, not a read per lane."""
    y_mean, y_std = jax.device_get((tgts.y_mean, tgts.y_std))
    for ji, (s, m) in enumerate(owners):
        posts.setdefault(s.rid, {})[m] = {
            "mu": mu[ji], "var": var[ji],
            "y_mean": y_mean[ji], "y_std": y_std[ji]}


@dataclasses.dataclass
class SearchRequest:
    """One tenant's search: the ``run_search`` (or ``run_search_moo``)
    arguments as a record. Exactly one of ``objective`` /
    ``objectives`` must be set; ``objectives=[a, b, ...]`` (two or
    more) makes the session multi-objective (MC-EHVI, §III-D; n >= 3
    objectives evaluate via the box-decomposition EHVI plan node)."""
    space: SearchSpace
    profile_fn: ProfileFn
    objective: Optional[Objective] = None
    constraints: Sequence[Constraint] = ()
    method: str = "karasu"            # naive | augmented | karasu
    bo_config: BOConfig = dataclasses.field(default_factory=BOConfig)
    seed: int = 0
    share_as: Optional[str] = None    # publish runs to the repo under this id
    objectives: Optional[Sequence[Objective]] = None   # MOO: two or more
    n_mc: int = 64                    # MC-EHVI posterior draws (MOO only)


@dataclasses.dataclass
class SearchCompletion:
    rid: int
    result: BOResult


class _Session:
    """Mutable per-tenant state (mirrors run_search's loop variables)."""

    def __init__(self, rid: int, req: SearchRequest):
        self.rid = rid
        self.req = req
        self.cfg = req.bo_config
        self.key = jax.random.PRNGKey(req.seed)
        self.key_data = np.asarray(self.key)   # host copy, for derive_keys
        self.rng = np.random.default_rng(req.seed)
        self.objectives = (list(req.objectives)
                           if req.objectives is not None else [])
        self.is_moo = bool(self.objectives)
        obj_names = ([o.name for o in self.objectives] if self.is_moo
                     else [req.objective.name])
        self.measures = obj_names + [c.name for c in req.constraints]
        self.xq_all = req.space.all_encoded()
        # batching/context key: spaces are interchangeable iff their
        # configs AND encodings agree — the name alone could conflate
        # two different user-built spaces that happen to share it
        self.space_key = (req.space.name, hashlib.sha1(
            np.ascontiguousarray(self.xq_all).tobytes()
            + repr(req.space.configs).encode()).hexdigest())
        self.observations: List[Observation] = []
        self.best_idx: List[int] = []
        self.profiled: set = set()
        self.stopped_at = self.cfg.max_iters
        self.meta: Dict[str, Any] = {"method": req.method, "selected": []}
        if self.is_moo:
            self.meta["moo"] = True
            self.meta["objectives"] = [o.name for o in self.objectives]
        self.state = READY
        self.inflight = 0
        self._launch_seq = 0           # session-local submission index
        self._record_seq = 0           # next seq to absorb
        self._held: Dict[int, ProfileOutcome] = {}
        # warm-start cache of the incremental fit leg: measure ->
        # (observation version, log_ls, log_sf) host rows from the last
        # fit. An entry means the next fit of that measure rides the
        # short warm rung; the version records which observation set
        # produced it (diagnostics — the warm start is a valid initial
        # point for ANY later observation set of the same model).
        self.fit_cache: Dict[str, Tuple[int, np.ndarray, np.ndarray]] = {}

    def launch(self, ci: int, tag: str = "bo") -> ProfileJob:
        """Reserve candidate ``ci`` and build its executor job; the
        session waits in WAITING_PROFILE until the outcome lands."""
        self.profiled.add(int(ci))
        self.inflight += 1
        self.state = WAITING_PROFILE
        job = ProfileJob(self.rid, int(ci), self.req.space.configs[ci],
                         tag, self._launch_seq)
        self._launch_seq += 1
        return job

    def record(self, out: ProfileOutcome,
               repo: Optional[Repository]) -> None:
        """Absorb landed profiling outcomes in LAUNCH order, holding
        early arrivals back — concurrent init runs may complete in any
        order, but a session's observation sequence (and therefore its
        whole BO trajectory) must not depend on thread timing."""
        self._held[out.job.seq] = out
        errors: List[BaseException] = []
        while self._record_seq in self._held:
            nxt = self._held.pop(self._record_seq)
            self._record_seq += 1       # consume even if nxt errors
            try:
                self._record_one(nxt, repo)
            except BaseException as e:  # noqa: BLE001 — re-raised below
                # keep draining: a held successor outcome must not be
                # stranded (the executor already handed it over)
                errors.append(e)
        if errors:
            raise errors[0]

    def _record_one(self, out: ProfileOutcome,
                    repo: Optional[Repository]) -> None:
        """The bookkeeping half of core.bo._profile_into (execution
        happened in the executor)."""
        if out.error is not None:
            # settle the state machine BEFORE raising: the failed run is
            # simply absent from the observations, so a caller that
            # swallows the error keeps a live (not wedged) session
            self.inflight -= 1
            if self.inflight == 0:
                self.state = READY
            raise out.error
        obs = Observation(config=self.req.space.configs[out.job.ci],
                          x=self.xq_all[out.job.ci],
                          measures=out.measures, metrics=out.metrics)
        self.observations.append(obs)
        if self.is_moo:
            # no scalar incumbent under two objectives; the Pareto front
            # is assembled at result() time
            self.best_idx.append(len(self.observations) - 1)
        else:
            self.best_idx.append(_best_index_so_far(
                self.observations, self.req.objective, self.req.constraints))
        # publish only complete records: Algorithm-1 needs the metric
        # matrix, and a None-metrics record would poison the shared
        # CandidateIndex for every other tenant
        if (repo is not None and self.req.share_as is not None
                and obs.metrics is not None):
            repo.add_run(RunRecord(self.req.share_as, dict(obs.config),
                                   obs.metrics, obs.measures))
        self.inflight -= 1
        if self.inflight == 0:
            self.state = READY

    def init_candidates(self) -> List[int]:
        """'Prefill' picks: the random initialisation runs (§IV-B)."""
        n = min(self.cfg.n_init, len(self.req.space))
        return [int(ci) for ci in self.rng.choice(len(self.req.space),
                                                  size=n, replace=False)]

    def remaining(self) -> List[int]:
        return [i for i in range(len(self.req.space))
                if i not in self.profiled]

    def result(self) -> BOResult:
        self.meta["n_profiled"] = len(self.observations)
        if self.is_moo:
            self.meta["pareto_front"] = pareto_of_observations(
                self.observations, self.objectives, self.req.constraints)
        return BOResult(observations=self.observations,
                        best_index_per_iter=self.best_idx,
                        stopped_at=self.stopped_at, meta=self.meta)


class SearchService:
    """N concurrent tenant searches over one shared repository.

    ``submit`` -> rid; ``step`` advances every READY session one BO
    iteration (admitting queued sessions into free slots first) while
    WAITING_PROFILE sessions' runs execute on the ``executor``;
    ``collect`` drains finished searches; ``run`` loops until idle.

    ``wait_mode``:
      - ``"any"`` (default): a step scores whichever sessions' profiling
        results have landed; slow profilers never gate fast ones.
      - ``"all"``: a step first waits for every in-flight run — the
        synchronous round structure, but profiling runs still overlap
        each other on the executor.
    ``profile_timeout`` caps any blocking wait on the executor (seconds
    of wall clock, or virtual ticks on the fake); ``None`` waits until
    results land.
    ``fuse_posteriors`` (default True) collects every grid posterior of
    a step — targets, RGPE support stacks, MOO models — as
    ``PosteriorQuery`` nodes executed by the planned fused launches and
    uses the vectorised MC-EHVI; False restores the per-ensemble
    posterior loop and the per-candidate EHVI reference (the
    parity/benchmark baseline). ``fuse_samples`` (default True) does
    the same for the step's sample draws: RGPE support draws as
    ``SampleQuery``/``LooSampleQuery`` nodes and MOO EHVI
    sampling/evaluation as ``PosteriorDrawQuery``/``EhviQuery`` nodes;
    False restores the per-job / per-session loops. Fusion is visible
    in ``stats``: per-kind ``posterior_*`` / ``sample_*`` / ``ehvi_*``
    counters plus the aggregate ``plan_batches`` (fused launches) /
    ``plan_queries`` (query nodes they carried) across every planned
    round.
    """

    # how each plan-node kind rolls up into the service stats (the
    # sample-side kinds share one pair: they are all "draws the step
    # needed", whether from a support stack, a LOO target, or posterior
    # rows)
    _STAT_KEYS = {"posterior": ("posterior_batches", "posterior_queries"),
                  "sample": ("sample_batches", "sample_queries"),
                  "loo": ("sample_batches", "sample_queries"),
                  "draw": ("sample_batches", "sample_queries"),
                  "ehvi": ("ehvi_batches", "ehvi_jobs"),
                  "fit": ("fit_batches", "fit_jobs")}

    # the phase spans of ``step`` (children of ``karasu.step``), in order:
    # admit, absorb (poll/drain), profile_wait (the blocking collect when
    # every session waits), fit.collect (FitQuery building), fit.cache
    # (the warm-start refresh, a host transfer), regroup (target stacks
    # and their posterior queries), select (one candidate-index launch,
    # support stacks, host target slices, one key launch), score (RGPE
    # weights, its sample round nested), moo.front (fronts and EHVI
    # queries), acquire (per-tenant acquisition and the next runs'
    # submits), finish
    STEP_PHASES = ("admit", "absorb", "profile_wait", "fit.collect",
                   "fit.cache", "regroup", "select", "score", "moo.front",
                   "acquire", "finish")

    def __init__(self, repository: Optional[Repository] = None, *,
                 slots: int = 8, executor=None, wait_mode: str = "any",
                 profile_timeout: Optional[float] = None,
                 fuse_posteriors: bool = True, fuse_samples: bool = True,
                 planner: Optional[StepPlanner] = None,
                 plan_executor: Optional[PlanExecutor] = None,
                 mesh=None, data_axis: str = "data",
                 fit_steps: int = 120,
                 fit_warm_steps: Optional[int] = 16):
        if wait_mode not in ("any", "all"):
            raise ValueError(f"unknown wait_mode {wait_mode!r}")
        self.repo = repository if repository is not None else Repository()
        self.slots = slots
        self.executor = executor if executor is not None \
            else SyncProfileExecutor()
        self.wait_mode = wait_mode
        self.profile_timeout = profile_timeout
        self.fuse_posteriors = fuse_posteriors
        self.fuse_samples = fuse_samples
        # the incremental fit leg: models with cached hyperparameters
        # refit on the short warm rung, new/cold models pay the full
        # schedule. ``fit_warm_steps=None`` (or 0) disables warm starts
        # — every lane refits cold, the parity/benchmark baseline.
        self.fit_steps = int(fit_steps)
        self.fit_warm_steps = (int(fit_warm_steps)
                               if fit_warm_steps else 0)
        # ALL bucketing/padding policy lives in the planner; the service
        # only emits queries and scatters results. ``mesh`` constructs
        # BOTH defaults in sharded mode (lane pads rounded to shard
        # multiples, bucket launches shard-mapped over ``data_axis``) —
        # callers passing their own planner/executor own the pairing.
        self.planner = (planner if planner is not None
                        else StepPlanner(mesh=mesh, data_axis=data_axis))
        self.plan_executor = (
            plan_executor if plan_executor is not None
            else PlanExecutor(mesh=mesh, data_axis=data_axis))
        self.queue: List[_Session] = []
        self.active: Dict[int, _Session] = {}
        self.done: List[SearchCompletion] = []
        self._next_rid = 0
        # one KarasuContext (store + candidate index) per (space, noise):
        # support GPs depend on the encoder and the noise level only
        self._contexts: Dict[Tuple[Any, float], KarasuContext] = {}
        self.stats = {"steps": 0, "fit_batches": 0, "fit_jobs": 0,
                      "iterations": 0, "rgpe_batches": 0, "rgpe_jobs": 0,
                      "profile_waits": 0, "posterior_batches": 0,
                      "posterior_queries": 0, "sample_batches": 0,
                      "sample_queries": 0, "ehvi_batches": 0,
                      "ehvi_jobs": 0, "plan_batches": 0, "plan_queries": 0,
                      "plan_compile_misses": 0, "precompiled_buckets": 0,
                      "precompile_compiles": 0,
                      "fit_warm_lanes": 0, "fit_cold_lanes": 0,
                      "fit_fused_batches": 0,
                      "select_pearson_launches": 0, "select_tenants": 0,
                      "support_stack_misses": 0, "ehvi_boxes_live": 0,
                      "ehvi_boxes_padded": 0, "host_unpack_rows": 0,
                      "host_unpack_reads": 0}
        # ``step`` and ``precompile`` also add, as they occur, the spans'
        # self seconds (``span_s.<phase>``) and the programs each phase
        # built (``compiles.<phase>``): see ``repro.launch.spans``
        # launch signatures covered by precompile() — empty until called
        self.precompiled_signatures: set = set()

    # -- request lifecycle --------------------------------------------------
    def submit(self, req: SearchRequest) -> int:
        if req.method not in ("naive", "augmented", "karasu"):
            raise ValueError(f"unknown method {req.method!r}")
        if req.objectives is not None:
            if req.objective is not None:
                raise ValueError("pass either objective or objectives, "
                                 "not both")
            if len(req.objectives) < 2:
                raise ValueError("multi-objective serving needs "
                                 "objectives=[a, b, ...] (two or more)")
            if req.method == "augmented":
                raise ValueError("MOO supports methods naive|karasu")
        elif req.objective is None:
            raise ValueError("SearchRequest needs an objective "
                             "(or objectives=[a, b, ...])")
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append(_Session(rid, req))
        return rid

    def collect(self, *, wait: bool = False,
                timeout: Optional[float] = None) -> List[SearchCompletion]:
        """Drain finished searches. Non-blocking by default; with
        ``wait=True`` steps the service until at least one search
        finishes or ``timeout`` (seconds) elapses. A service with zero
        submitted searches always returns ``[]`` immediately."""
        if wait:
            deadline = (None if timeout is None
                        else time.monotonic() + timeout)
            while not self.done and (self.queue or self.active):
                if deadline is None:
                    self.step()
                    continue
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                # cap the executor waits inside step() so the overall
                # deadline is honored even while profilers are slow
                cap = (left if self.profile_timeout is None
                       else min(left, self.profile_timeout))
                self.step(profile_timeout=cap)
        out, self.done = self.done, []
        return out

    def context_for(self, session: _Session) -> KarasuContext:
        k = (session.space_key, session.cfg.noise)
        if k not in self._contexts:
            self._contexts[k] = KarasuContext(self.repo, session.req.space,
                                              noise=session.cfg.noise)
        return self._contexts[k]

    def close(self) -> None:
        self.executor.shutdown()

    # -- AOT bucket precompile ----------------------------------------------
    def precompile(self, limits: CohortLimits) -> Dict[str, int]:
        """Warm the jit cache for EVERY launch shape a cohort bounded by
        ``limits`` can produce, so serving runs at a zero-recompile
        steady state (asserted by ``stats['plan_compile_misses']``).

        The bucket vocabulary comes from the planner
        (``enumerate_buckets``); each bucket is driven through the REAL
        executor path with a dummy query pinned at the bucket's padded
        shape — executing (not just AOT-lowering) is deliberate: in
        jax 0.9 ``lower().compile()`` still does not populate the jit
        call cache, and only the executed path exercises the identical
        impl routing and kernel dispatch serving will use. The target
        fit leg is part of the enumerated vocabulary (fit buckets walk
        both the warm and cold ``steps`` rungs). The padded
        ranking-loss launch (the RGPE scoring hot spot) is warmed over
        its limits-closed shape set too: its row count is the step's
        ensemble rows — at most ``max_lanes`` stacks of ``n_samples``
        draws — rounded by the lane policy, and its column count rounds
        like an observation axis. Returns ``{"buckets", "compiles"}``
        and folds both into ``stats``. It is the root span
        ``karasu.precompile``, so ``stats["compiles.<span>"]`` says
        which of its phases built the programs."""
        with root("precompile", self.stats):
            return self._precompile(limits)

    def _precompile(self, limits: CohortLimits) -> Dict[str, int]:
        watch = CompileWatcher()
        # a step's EHVI launch has one lane per multi-objective session,
        # and at most ``slots`` sessions are active
        buckets = self.planner.enumerate_buckets(
            limits, ehvi_lanes=min(self.slots, limits.max_lanes))
        for bucket in buckets:
            queries, prep = self._dummy_bucket(bucket, limits)
            self.plan_executor.execute(StepPlan(
                queries,
                [dataclasses.replace(
                    bucket, indices=tuple(range(len(queries))))],
                prep))
        if limits.n_samples:
            # the launch's impl is jit-static and comes from the
            # tenants' BOConfig.kernel_impl; the cohort default ("xla")
            # is the warmed vocabulary — a per-tenant Pallas override
            # opts out of the zero-recompile claim for this leg
            launch = ranking_loss_launch_fn(donate=self.plan_executor.donate)
            loss_rows = sorted({self.planner.round_models(k * s)
                                for s in limits.n_samples
                                for k in range(1, limits.max_lanes + 1)})
            for n_pad in self.planner._obs_pads(limits.max_obs):
                for r_pad in loss_rows:
                    launch(jnp.zeros((r_pad, n_pad), jnp.float32),
                           jnp.zeros((r_pad, n_pad), jnp.float32),
                           jnp.zeros((r_pad,), jnp.int32), impl="xla")
        # the select phase's two launches, at every row pad: Algorithm 1
        # for the step's karasu tenants (each holds a target lane, so at
        # most max_lanes of them, and at most max_obs runs each) against
        # the repository's candidate index, and the step's RGPE keys
        # (one per target lane). The Pearson impl follows the same
        # cohort-default rule as the ranking loss; runs published later
        # can move the candidate pad, which then compiles once
        index = CandidateIndex(self.repo.all_runs())
        if not index.empty:
            tenants = min(self.slots, limits.max_lanes)
            for r in row_pads(tenants * limits.max_obs):
                index.correlate(np.zeros((r, index.metric_dim), np.float32),
                                "xla")
        key = np.asarray(jax.random.PRNGKey(0))
        for r in row_pads(limits.max_lanes):
            derive_keys(np.broadcast_to(key, (r,) + key.shape),
                        KEY_PURPOSE_RGPE, [0] * r, [0] * r)
        self.precompiled_signatures = {
            self.planner.launch_signature(b) for b in buckets}
        compiles = watch.misses()
        self.stats["precompiled_buckets"] += len(buckets)
        self.stats["precompile_compiles"] += compiles
        return {"buckets": len(buckets), "compiles": compiles}

    def _dummy_bucket(self, bucket, limits: CohortLimits):
        """Owner-less queries pinned at an enumerated bucket's padded
        shape (every padded length is a fixed point of the rounding
        policy, so the executor launches exactly the enumerated
        program). Values are immaterial — only shapes compile."""
        noise = limits.noises[0]
        d = limits.d
        kind, key, pads = bucket.kind, bucket.key, bucket.pads
        if kind == "posterior":
            stack = self._dummy_stack(pads["m_pad"], pads["n_pad"], d,
                                      noise)
            return [PosteriorQuery(stack, np.zeros((key[0], d),
                                                   np.float32))], {}
        if kind == "sample":
            s, q_pad, _ = key
            stack = self._dummy_stack(pads["m_pad"], pads["n_pad"], d,
                                      noise)
            keys = jax.random.split(jax.random.PRNGKey(0), pads["m_pad"])
            return [SampleQuery(stack, np.zeros((q_pad, d), np.float32),
                                keys, s)], {}
        if kind == "loo":
            s, n_pad = key
            gp = GP(jnp.zeros((n_pad, d), jnp.float32),
                    jnp.zeros((n_pad,)), jnp.zeros((n_pad,)),
                    jnp.zeros(()), jnp.ones(()),
                    GPParams(jnp.zeros((d,)), jnp.zeros(()), noise),
                    jnp.eye(n_pad, dtype=jnp.float32),
                    jnp.zeros((n_pad,)))
            return [LooSampleQuery(gp, jax.random.PRNGKey(0), s)
                    for _ in range(pads["l_pad"])], {}
        if kind == "ehvi":
            n_obj, s, q_pad = key
            box = (np.zeros((1, n_obj)), np.ones((1, n_obj)))
            if self.plan_executor.fused_ehvi:
                # posterior form: the dummy must drive the SAME fused
                # launch (and eps draw dispatch) serving will, at the
                # full lane count
                queries = [EhviQuery(
                    None, np.ones((1, n_obj)), np.full((n_obj,), 2.0),
                    mu=tuple(np.zeros((q_pad,), np.float32)
                             for _ in range(n_obj)),
                    var=tuple(np.ones((q_pad,), np.float32)
                              for _ in range(n_obj)),
                    y_mean=(0.0,) * n_obj, y_std=(1.0,) * n_obj,
                    keys=tuple(jax.random.PRNGKey(0)
                               for _ in range(n_obj)),
                    n_mc=s) for _ in range(pads["l_pad"])]
            else:
                samples = tuple(np.zeros((s, q_pad), np.float32)
                                for _ in range(n_obj))
                queries = [EhviQuery(samples, np.ones((1, n_obj)),
                                     np.full((n_obj,), 2.0))
                           for _ in range(pads["l_pad"])]
            return queries, {i: box for i in range(len(queries))}
        if kind == "fit":
            d_, steps, noise_ = key
            # nonzero distinct y: the packing standardises per lane and
            # clamps y_std, so any values compile — but a spread keeps
            # the dummy on the same numeric path as live data
            return [FitQuery(np.zeros((pads["n_pad"], d_), np.float32),
                             np.arange(pads["n_pad"], dtype=np.float32),
                             noise_, steps)
                    for _ in range(pads["m_pad"])], {}
        raise ValueError(f"unknown bucket kind {kind!r}")

    @staticmethod
    def _dummy_stack(m: int, n: int, d: int, noise: float) -> BatchedGP:
        eye = jnp.broadcast_to(jnp.eye(n, dtype=jnp.float32)[None],
                               (m, n, n))
        return BatchedGP(jnp.zeros((m, n, d), jnp.float32),
                         jnp.zeros((m, n)), jnp.ones((m, n)),
                         jnp.zeros((m,)), jnp.ones((m,)),
                         jnp.zeros((m, d)), jnp.zeros((m,)), noise,
                         eye, jnp.zeros((m, n)),
                         jnp.full((m,), n, jnp.int32))

    # -- scheduling internals -----------------------------------------------
    def _admit(self) -> None:
        while self.queue and len(self.active) < self.slots:
            s = self.queue.pop(0)
            self.active[s.rid] = s
            for ci in s.init_candidates():
                self.executor.submit(s.launch(ci, "init"),
                                     s.req.profile_fn)

    def _absorb(self, outcomes: List[ProfileOutcome]) -> None:
        """Record a batch of outcomes. One tenant's profiling error must
        not drop the rest of the batch (the executor already popped it),
        so every outcome is recorded before the first error re-raises."""
        errors: List[BaseException] = []
        for out in outcomes:
            try:
                self.active[out.job.rid].record(out, self.repo)
            except BaseException as e:          # noqa: BLE001 — re-raised
                errors.append(e)
        if errors:
            raise errors[0]

    def _finish(self, s: _Session) -> None:
        del self.active[s.rid]
        self.done.append(SearchCompletion(s.rid, s.result()))

    # -- one scheduling round -----------------------------------------------
    def step(self, *, profile_timeout: Optional[float] = None) -> int:
        """Admit queued sessions, absorb landed profiling results, then
        advance each READY session one BO iteration with the target fits
        and RGPE weightings batched across tenants. Returns the number
        of sessions whose next profiling run was launched.
        ``profile_timeout`` overrides the service-level default for this
        step's blocking executor waits (used by ``collect(wait=True)``
        to honor its own deadline).

        The step is the root span ``karasu.step`` (``repro.launch.spans``,
        step number ``stats["steps"]``); its children are the disjoint
        ``STEP_PHASES`` plus the planner's and executor's spans of the
        rounds that run directly under it, and together they cover it."""
        self.stats["steps"] += 1
        with root("step", self.stats, step_num=self.stats["steps"]):
            return self._step(profile_timeout)

    def _step(self, profile_timeout: Optional[float]) -> int:
        wait_t = (self.profile_timeout if profile_timeout is None
                  else profile_timeout)
        # one deadline for the WHOLE step: wait_mode="all" may wait twice
        # (drain, then collect), and the budget must not double
        deadline = (None if wait_t is None
                    else time.monotonic() + wait_t)

        def left() -> Optional[float]:
            return (None if deadline is None
                    else max(0.0, deadline - time.monotonic()))

        # any compile of a tracked plan launch during this step is a
        # steady-state violation candidate — surfaced, never silent
        compile_watch = CompileWatcher()
        with span("admit"):
            self._admit()
        with span("absorb"):
            self._absorb(self.executor.poll())
            if self.wait_mode == "all" and self.executor.pending():
                self._absorb(self.executor.drain(left()))
            ready = self._ready_sessions()

        if not ready and self.executor.pending():
            # every active session is WAITING_PROFILE: block until at
            # least one result lands rather than spinning
            with span("profile_wait"):
                self.stats["profile_waits"] += 1
                self._absorb(self.executor.collect(left()))
                ready = self._ready_sessions()

        with span("admit"):
            # a session whose completed runs ALL errored has nothing to
            # fit: re-admit it with a fresh random candidate instead of
            # scoring (failed candidates stay reserved in `profiled`,
            # never retried)
            for s, rem in ready:
                if not s.observations:
                    ci = rem[int(s.rng.integers(len(rem)))]
                    self.executor.submit(s.launch(ci, "init"),
                                         s.req.profile_fn)
            ready = [(s, rem) for s, rem in ready if s.observations]
        if not ready:
            with span("absorb"):
                self._absorb(self.executor.poll())
                self.stats["plan_compile_misses"] += compile_watch.misses()
            return 0

        # the model math of the step: two planned rounds over the query
        # layer (collect -> plan -> execute -> scatter); the second
        # consumes the first's scattered posteriors
        posts = self._posterior_phase([s for s, _ in ready])
        moo_acq = self._moo_phase(
            [(s, rem) for s, rem in ready if s.is_moo], posts)

        with span("acquire"):
            advanced = 0
            for s, rem in ready:
                if s.is_moo:
                    # MC-EHVI x PoF; no scalar incumbent, so no early stop
                    acq = moo_acq[s.rid]
                else:
                    acq, best_raw, obj_post = _acquisition(
                        posts[s.rid], s.observations, s.req.objective,
                        s.req.constraints)
                    acq = acq[np.asarray(rem)]

                    if _should_stop_early(s.cfg, len(s.observations), acq,
                                          obj_post, best_raw):
                        s.stopped_at = len(s.observations)
                        self._finish(s)
                        continue

                self.executor.submit(s.launch(rem[int(np.argmax(acq))]),
                                     s.req.profile_fn)
                advanced += 1
                self.stats["iterations"] += 1

        # with a synchronous executor every launch has already landed;
        # absorbing here preserves the one-step-one-iteration semantics
        with span("absorb"):
            self._absorb(self.executor.poll())
        with span("finish"):
            for s in list(self.active.values()):
                if (s.state == READY
                        and len(s.observations) >= s.cfg.max_iters):
                    self._finish(s)
            self.stats["plan_compile_misses"] += compile_watch.misses()
        return advanced

    def _ready_sessions(self) -> List[Tuple[_Session, List[int]]]:
        """READY sessions that still have work, finishing exhausted ones
        (max_iters reached or the whole space profiled)."""
        out: List[Tuple[_Session, List[int]]] = []
        for s in list(self.active.values()):
            if s.state != READY:
                continue
            if len(s.observations) >= s.cfg.max_iters:
                self._finish(s)
                continue
            rem = s.remaining()
            if not rem:
                s.stopped_at = len(s.observations)
                self._finish(s)
                continue
            out.append((s, rem))
        return out

    def _count_plan(self, counters: Dict[str, Dict[str, int]]) -> None:
        """Roll one planned round's per-kind counters into the service
        stats: the per-kind pairs (``_STAT_KEYS``), the aggregate
        ``plan_batches``/``plan_queries``, the EHVI launches' live and
        padded boxes (``ehvi_boxes_live``/``ehvi_boxes_padded``), and
        the rows handed back as host arrays with the reads made for them
        (``host_unpack_rows``/``host_unpack_reads``)."""
        for kind, c in counters.items():
            bk, qk = self._STAT_KEYS[kind]
            self.stats[bk] += c.get("launches", 0)
            self.stats[qk] += c.get("queries", 0)
            self.stats["plan_batches"] += c.get("launches", 0)
            self.stats["plan_queries"] += c.get("queries", 0)
            for k in ("host_unpack_rows", "host_unpack_reads"):
                self.stats[k] += c.get(k, 0)
            if kind == "ehvi":
                self.stats["ehvi_boxes_live"] += c.get("boxes_live", 0)
                self.stats["ehvi_boxes_padded"] += c.get("boxes_padded", 0)

    @staticmethod
    def _regroup_fit(entries: List[Tuple[BatchedGP, int]],
                     noise: float) -> BatchedGP:
        """Assemble one (space, noise) group's target stack from the
        fit round's per-query ``(bucket stack, lane)`` results. Warm
        and cold lanes of a group come back in DIFFERENT bucket stacks
        (the schedule length is part of the bucket key), possibly at
        different observation pads — re-pad to the common maximum
        (``_pad_stack_obs``'s exactness contract) and gather each
        lane's rows, preserving the group's owner order."""
        n_max = max(st.n_max for st, _ in entries)
        padded: Dict[int, Tuple] = {}
        rows: Dict[str, List[Any]] = {k: [] for k in (
            "x", "y", "mask", "y_mean", "y_std", "ls", "sf", "chol",
            "alpha", "cnt")}
        for st, ln in entries:
            c = padded.get(id(st))
            if c is None:
                p = n_max - st.n_max
                x, mask, chol, alpha = _pad_stack_obs(st, n_max)
                y = jnp.pad(st.y, ((0, 0), (0, p))) if p else st.y
                c = (x, y, mask, chol, alpha)
                padded[id(st)] = c
            x, y, mask, chol, alpha = c
            rows["x"].append(x[ln])
            rows["y"].append(y[ln])
            rows["mask"].append(mask[ln])
            rows["chol"].append(chol[ln])
            rows["alpha"].append(alpha[ln])
            rows["y_mean"].append(st.y_mean[ln])
            rows["y_std"].append(st.y_std[ln])
            rows["ls"].append(st.log_lengthscales[ln])
            rows["sf"].append(st.log_signal[ln])
            rows["cnt"].append(st.counts[ln])
        return BatchedGP(
            jnp.stack(rows["x"]), jnp.stack(rows["y"]),
            jnp.stack(rows["mask"]), jnp.stack(rows["y_mean"]),
            jnp.stack(rows["y_std"]), jnp.stack(rows["ls"]),
            jnp.stack(rows["sf"]), noise, jnp.stack(rows["chol"]),
            jnp.stack(rows["alpha"]), jnp.stack(rows["cnt"]))

    def _posterior_phase(self, sessions: List[_Session]
                         ) -> Dict[int, Dict[str, Dict]]:
        """COLLECT every model query of the step in two planned rounds.
        The FIT round first: one ``FitQuery`` per (session, measure)
        target model across all (space, noise) groups — warm lanes
        (hyperparameters cached from the previous step) on the short
        refine rung, cold lanes on the full schedule — executed as one
        ``kernels.fused_fit`` launch per (d, steps, noise) bucket, then
        regrouped into per-group target stacks. Then the POSTERIOR
        round: every grid-posterior query — target stacks, every karasu
        ensemble's support stack, MOO models, all tenants — planned
        into fused buckets, one launch per bucket, rows scattered back
        to their owning (session, measure) slots. RGPE weights score
        between collect and scatter (one padded ranking-loss launch per
        kernel impl, its sample draws planned through the same layer).
        With ``fuse_posteriors=False`` the posterior half degrades to
        the historical per-group + per-ensemble loop (the fit round
        still plans)."""
        groups: Dict[Tuple[Any, float], List[_Session]] = {}
        posts: Dict[int, Dict[str, Dict]] = {}
        # -- collect: the fit round ------------------------------------------
        # one FitQuery per (session, measure) model across ALL groups —
        # warm lanes (cached hyperparameters) ask for the short refine
        # rung, cold lanes the full schedule; the planner buckets them
        # by (d, steps, noise) and the executor runs ONE fused launch
        # per bucket, so a step's whole fit leg is a handful of
        # ``kernels.fused_fit`` launches instead of a vmapped 120-step
        # Adam per group
        fit_queries: List[FitQuery] = []
        fit_owners: List[Tuple[_Session, str]] = []
        group_lanes: Dict[Tuple[Any, float], List[int]] = {}
        with span("fit.collect"):
            for s in sessions:
                if s.req.method == "augmented":
                    # Extra-Trees have no batched path; keep them
                    # per-session
                    posts[s.rid] = _model_posteriors_augmented(
                        s.observations, s.measures, s.cfg, s.xq_all,
                        s.req.seed)
                    continue
                groups.setdefault((s.space_key, s.cfg.noise),
                                  []).append(s)
            for gk, group in groups.items():
                noise = gk[1]
                lanes = group_lanes.setdefault(gk, [])
                for s in group:
                    x = np.stack([o.x for o in s.observations])
                    for m in s.measures:
                        y = np.array([o.measures[m]
                                      for o in s.observations])
                        entry = (s.fit_cache.get(m) if self.fit_warm_steps
                                 else None)
                        if entry is not None:
                            self.stats["fit_warm_lanes"] += 1
                            q = FitQuery(x, y, noise, self.fit_warm_steps,
                                         init_ls=entry[1], init_sf=entry[2])
                        else:
                            self.stats["fit_cold_lanes"] += 1
                            q = FitQuery(x, y, noise, self.fit_steps)
                        lanes.append(len(fit_queries))
                        fit_queries.append(q)
                        fit_owners.append((s, m))
        fc: Dict[str, Dict[str, int]] = {}
        fit_res = self.plan_executor.execute(
            self.planner.plan(fit_queries), counters=fc)
        self._count_plan(fc)
        self.stats["fit_fused_batches"] += \
            fc.get("fit", {}).get("launches", 0)
        # refresh every lane's warm-start cache from the fitted stacks
        # (one host transfer per bucket stack, not per lane)
        with span("fit.cache"):
            host: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
            for (s, m), (st, ln) in zip(fit_owners, fit_res):
                h = host.get(id(st))
                if h is None:
                    h = (np.asarray(st.log_lengthscales),
                         np.asarray(st.log_signal))
                    host[id(st)] = h
                s.fit_cache[m] = (len(s.observations), h[0][ln], h[1][ln])

        # -- collect: posteriors over the fitted stacks ----------------------
        queries: List[PosteriorQuery] = []
        with span("regroup"):
            tgts = {gk: self._regroup_fit(
                [fit_res[i] for i in group_lanes[gk]], gk[1])
                for gk in groups}
            owners = {gk: [(s, m) for s in group for m in s.measures]
                      for gk, group in groups.items()}
            for gk, group in groups.items():
                xq_all = group[0].xq_all
                if self.fuse_posteriors:
                    queries.append(PosteriorQuery(
                        tgts[gk], xq_all,
                        owner=lambda res, o=owners[gk], t=tgts[gk]:
                            _absorb_target_posts(posts, o, t, *res)))
                else:
                    mu_all, var_all = batched_posterior(tgts[gk], xq_all)
                    _absorb_target_posts(posts, owners[gk], tgts[gk],
                                         mu_all, var_all)

        # (session, measure, bases, WeightJob) across ALL groups
        with span("select"):
            rgpe_jobs = self._rgpe_jobs(groups, tgts, owners)

        with span("score"):
            weights = self._score_weights(rgpe_jobs)
            if not self.fuse_posteriors:
                for i, (s, m, bases, _job) in enumerate(rgpe_jobs):
                    self._mix_rgpe(s, m, bases, weights[i], posts[s.rid])
                return posts
            # support stacks join the targets' queries; the executor
            # fires owners in query order, so mixes overlay the target
            # rows the earlier queries already absorbed into ``posts``
            for i, (s, m, bases, _job) in enumerate(rgpe_jobs):
                queries.append(PosteriorQuery(
                    bases, s.xq_all,
                    owner=lambda res, s=s, m=m, w=weights[i]:
                        self._mix_into(posts, s, m, w, res)))
        if not queries:
            return posts

        # -- plan / execute / scatter (owner callbacks) ----------------------
        counters: Dict[str, Dict[str, int]] = {}
        self.plan_executor.execute(self.planner.plan(queries),
                                   counters=counters)
        self._count_plan(counters)
        return posts

    def _score_weights(self, rgpe_jobs) -> Dict[int, Any]:
        """ONE padded ranking-loss launch for every ensemble of the step
        (per kernel impl — sessions normally share one); the jobs'
        sample draws ride the shared planner."""
        weights: Dict[int, Any] = {}
        by_impl: Dict[str, List[int]] = {}
        for idx, (s, *_rest) in enumerate(rgpe_jobs):
            by_impl.setdefault(s.cfg.kernel_impl, []).append(idx)
        for impl, idxs in by_impl.items():
            sc: Dict[str, int] = {}
            ws = KarasuContext.score_ensembles(
                [rgpe_jobs[i][3] for i in idxs], impl=impl,
                fuse_samples=self.fuse_samples, sample_counters=sc,
                planner=self.planner, plan_executor=self.plan_executor)
            self.stats["rgpe_batches"] += 1
            self.stats["rgpe_jobs"] += len(idxs)
            self.stats["sample_batches"] += sc.get("launches", 0)
            self.stats["sample_queries"] += sc.get("queries", 0)
            self.stats["plan_batches"] += sc.get("launches", 0)
            self.stats["plan_queries"] += sc.get("queries", 0)
            for i, w in zip(idxs, ws):
                weights[i] = w
        return weights

    @staticmethod
    def _mix_into(posts, s: _Session, m: str, w, res) -> None:
        """Owner callback of an RGPE support-stack query: overlay the
        weighted mixture on the already-scattered target posterior. The
        executor hands back host rows, so the mix runs in numpy."""
        mu, var = res
        p = posts[s.rid][m]
        w = np.asarray(w)
        mu_m, var_m = mix_weighted(mu, var, p["mu"], p["var"], w)
        posts[s.rid][m] = {"mu": mu_m, "var": var_m,
                           "y_mean": p["y_mean"], "y_std": p["y_std"],
                           "weights": w}

    def _rgpe_jobs(self, groups, tgts, owners
                   ) -> List[Tuple[_Session, str, Any, WeightJob]]:
        """Queue one weighting job per (karasu session, measure) whose
        support stack is usable, for every group of the step at once:
        Algorithm 1 for all tenants in one Pearson launch per (context,
        support count, kernel impl), support stacks from the shared
        store, each target sliced from one host copy of its group's
        target stack, and every key in one ``derive_keys`` launch. Jobs
        come in (group, session, measure) order; keys match the
        sequential path's ``derive_key`` schedule exactly."""
        karasu = [(gk, s) for gk, group in groups.items() for s in group
                  if s.req.method == "karasu"]
        calls: Dict[Tuple[Any, int, str], List[_Session]] = {}
        for _gk, s in karasu:
            calls.setdefault((self.context_for(s), s.cfg.n_support,
                              s.cfg.kernel_impl), []).append(s)
        selected: Dict[int, List[Tuple[str, float]]] = {}
        launches: Dict[str, int] = {}
        for (ctx, k, impl), members in calls.items():
            # a tenant must never pick its own published runs as
            # "support": they would score ~1.0 against themselves and
            # sidestep the LOO sampling that keeps the target honest on
            # its training points
            found = ctx.candidate_index().query_many(
                [_target_runs(s.observations) for s in members], k,
                impl=impl, counters=launches,
                exclude=[(s.req.share_as,) if s.req.share_as else None
                         for s in members])
            selected.update((s.rid, f) for s, f in zip(members, found))
        self.stats["select_pearson_launches"] += launches.get("launches", 0)
        self.stats["select_tenants"] += len(karasu)

        # support stacks: one batched request per store, so the stacks
        # that miss go to the device in one transfer
        wanted: Dict[Any, List[Tuple[int, str, List[str]]]] = {}
        for _gk, s in karasu:
            ids = [z for z, _ in selected[s.rid]]
            s.meta["selected"].append(ids)
            if ids:
                wanted.setdefault(self.context_for(s).store, []).extend(
                    (s.rid, m, ids) for m in s.measures)
        stacks = {}
        for store, reqs in wanted.items():
            got = store.get_stacked_many([(ids, m) for _, m, ids in reqs])
            stacks.update(((rid, m), bases)
                          for (rid, m, _), (bases, _ids) in zip(reqs, got))
        self.stats["support_stack_misses"] = sum(
            c.store.stack_misses for c in self._contexts.values())
        pending = [(gk, s, mi, m, stacks[s.rid, m]) for gk, s in karasu
                   for mi, m in enumerate(s.measures)
                   if stacks.get((s.rid, m)) is not None]
        if not pending:
            return []
        keys = derive_keys(np.stack([p[1].key_data for p in pending]),
                           KEY_PURPOSE_RGPE,
                           [len(p[1].observations) for p in pending],
                           [p[2] for p in pending])
        # targets: each group's stack read to the host once and sliced in
        # numpy; the slices the planned draws consume go back to the
        # device in one batched transfer, so packing the draws costs no
        # per-job transfer
        host = {gk: tgts[gk].to_host() for gk in {p[0] for p in pending}}
        lane = {gk: {(o.rid, m): ji for ji, (o, m) in enumerate(owners[gk])}
                for gk in host}
        targets = [host[gk].extract(lane[gk][s.rid, m])
                   for gk, s, _mi, m, _b in pending]
        put = jax.device_put([(t.x, t.y, t.chol, t.alpha) for t in targets])
        return [(s, m, bases,
                 WeightJob(bases, dataclasses.replace(
                     t, x=x, y=y, chol=chol, alpha=alpha), key,
                     s.cfg.rgpe_samples))
                for (gk, s, _mi, m, bases), t, (x, y, chol, alpha), key
                in zip(pending, targets, put, keys)]

    def _mix_rgpe(self, s: _Session, m: str, bases, w, post) -> None:
        """Replace one (session, measure) plain target posterior with the
        RGPE mixture built from the shared support store — the
        per-ensemble posterior loop (``fuse_posteriors=False`` only; the
        fused plan queries every stack in one launch instead)."""
        mu_b, var_b = batched_posterior(bases, s.xq_all)
        mu, var = mix_weighted(mu_b, var_b, post[m]["mu"], post[m]["var"], w)
        post[m] = {"mu": mu, "var": var,
                   "y_mean": post[m]["y_mean"],
                   "y_std": post[m]["y_std"],
                   "weights": np.asarray(w)}

    @staticmethod
    def _moo_front_ref(s: _Session) -> Tuple[np.ndarray, np.ndarray]:
        """The (observed, ref) pair EHVI is computed against: feasible
        observations (all, if none feasible yet) and the 1.1-scaled
        nadir — one rule shared by the fused and loop paths, any
        objective count."""
        names = [o.name for o in s.objectives]
        feas = [o for o in s.observations
                if _feasible(o, s.req.constraints)] or s.observations
        observed = np.array([[o.measures[n] for n in names] for o in feas])
        return observed, observed.max(axis=0) * 1.1 + 1e-9

    def _apply_pof(self, s: _Session, post: Dict[str, Dict],
                   idx: np.ndarray, acq: np.ndarray) -> np.ndarray:
        """Weight an EHVI row by every constraint's probability of
        feasibility — the scatter step both MOO paths share."""
        acq = np.asarray(acq)
        for c in s.req.constraints:
            cp = post[c.name]
            ub_std = (c.upper_bound - cp["y_mean"]) / cp["y_std"]
            pof = np.asarray(probability_of_feasibility(
                cp["mu"][idx], cp["var"][idx], float(ub_std)))
            acq = acq * pof
        return acq

    def _moo_phase(self, moo_ready: List[Tuple[_Session, List[int]]],
                   posts: Dict[int, Dict[str, Dict]]
                   ) -> Dict[int, np.ndarray]:
        """MC-EHVI x PoF for EVERY MOO session of the step (paper
        §III-D), fed by the scattered grid posteriors. Two further
        planned rounds: COLLECT one ``PosteriorDrawQuery`` per (session,
        objective) lane (fused draw launch per (n_mc, n_rem) bucket),
        scatter the draws, then COLLECT one ``EhviQuery`` per session
        (fused box-decomposition launch per (n_obj, S, q) bucket — 2-
        and n>=3-objective sessions just land in different buckets) and
        scatter the acquisition rows through the PoF weighting.
        ``fuse_samples=False`` restores the per-session sampling + numpy
        EHVI loop (the parity/bench baseline). Keys derive per
        (MOO_EHVI, iteration, objective), so fusion order can never
        change a session's draws."""
        if not moo_ready:
            return {}
        if not self.fuse_samples:
            return {s.rid: self._moo_acquisition(s, posts[s.rid], rem)
                    for s, rem in moo_ready}
        if self.plan_executor.fused_ehvi:
            return self._moo_phase_fused(moo_ready, posts)

        # -- collect / plan / execute / scatter: the draw round --------------
        samples: Dict[int, List[Optional[np.ndarray]]] = {
            s.rid: [None] * len(s.objectives) for s, _ in moo_ready}
        draw_queries: List[PosteriorDrawQuery] = []
        with span("moo.front"):
            for s, rem in moo_ready:
                idx = np.asarray(rem)
                it = len(s.observations)
                for oi, obj in enumerate(s.objectives):
                    p = posts[s.rid][obj.name]
                    k = derive_key(s.key, KEY_PURPOSE_MOO_EHVI, it, oi)
                    draw_queries.append(PosteriorDrawQuery(
                        p["mu"][idx], p["var"][idx], p["y_mean"],
                        p["y_std"], k, s.req.n_mc,
                        owner=lambda d, rid=s.rid, oi=oi:
                            samples[rid].__setitem__(oi, d)))
        dc: Dict[str, Dict[str, int]] = {}
        self.plan_executor.execute(self.planner.plan(draw_queries),
                                   counters=dc)
        self._count_plan(dc)

        # -- collect / plan / execute / scatter: the EHVI round --------------
        out: Dict[int, np.ndarray] = {}
        ehvi_queries = []
        with span("moo.front"):
            for s, rem in moo_ready:
                observed, ref = self._moo_front_ref(s)
                ehvi_queries.append(EhviQuery(
                    tuple(samples[s.rid]), observed, ref,
                    owner=lambda acq, s=s, rem=rem:
                        out.__setitem__(s.rid, self._apply_pof(
                            s, posts[s.rid], np.asarray(rem), acq))))
        ec: Dict[str, Dict[str, int]] = {}
        self.plan_executor.execute(self.planner.plan(ehvi_queries),
                                   counters=ec)
        self._count_plan(ec)
        return out

    def _moo_phase_fused(self, moo_ready: List[Tuple[_Session, List[int]]],
                         posts: Dict[int, Dict[str, Dict]]
                         ) -> Dict[int, np.ndarray]:
        """The fused-EHVI MOO round: ONE planned round instead of two —
        each session emits a posterior-form ``EhviQuery`` and the draw
        affine runs inside the ``kernels.fused_ehvi`` launch, so the
        per-objective (S, q) draw tensors never round-trip through HBM.
        Keys derive per (MOO_EHVI, iteration, objective) exactly as the
        draw round does, so switching the executor to ``fused_ehvi``
        never changes a session's draws or its acquisition."""
        out: Dict[int, np.ndarray] = {}
        ehvi_queries = []
        with span("moo.front"):
            for s, rem in moo_ready:
                idx = np.asarray(rem)
                it = len(s.observations)
                observed, ref = self._moo_front_ref(s)
                ps = [posts[s.rid][obj.name] for obj in s.objectives]
                ehvi_queries.append(EhviQuery(
                    None, observed, ref,
                    mu=tuple(p["mu"][idx] for p in ps),
                    var=tuple(p["var"][idx] for p in ps),
                    y_mean=tuple(float(p["y_mean"]) for p in ps),
                    y_std=tuple(float(p["y_std"]) for p in ps),
                    keys=tuple(
                        derive_key(s.key, KEY_PURPOSE_MOO_EHVI, it, oi)
                        for oi in range(len(s.objectives))),
                    n_mc=s.req.n_mc,
                    owner=lambda acq, s=s, rem=rem:
                        out.__setitem__(s.rid, self._apply_pof(
                            s, posts[s.rid], np.asarray(rem), acq))))
        ec: Dict[str, Dict[str, int]] = {}
        self.plan_executor.execute(self.planner.plan(ehvi_queries),
                                   counters=ec)
        self._count_plan(ec)
        return out

    def _moo_acquisition(self, s: _Session, post: Dict[str, Dict],
                         rem: List[int]) -> np.ndarray:
        """The per-session MC-EHVI x PoF loop (``fuse_samples=False``
        only — the fused path plans all sessions' draws and EHVI
        evaluations instead). Same key schedule and front rule as the
        fused path, so both produce the same acquisition up to float
        roundoff. Two objectives keep the staircase references
        (vectorised when ``fuse_posteriors``, the per-candidate
        ``_hv_2d`` loop otherwise); n >= 3 use the recursive-sweep
        ``mc_ehvi_nd`` oracle — the parity baseline of the fused box
        decomposition."""
        idx = np.asarray(rem)
        it = len(s.observations)
        samples = []
        for oi, obj in enumerate(s.objectives):
            p = post[obj.name]
            k = derive_key(s.key, KEY_PURPOSE_MOO_EHVI, it, oi)
            eps = jax.random.normal(k, (s.req.n_mc, len(rem)))
            sm = p["mu"][idx][None] + eps * jnp.sqrt(p["var"][idx])[None]
            samples.append(np.asarray(sm * p["y_std"] + p["y_mean"]))
        observed, ref = self._moo_front_ref(s)
        if len(s.objectives) == 2:
            ehvi = mc_ehvi_batched if self.fuse_posteriors else mc_ehvi
            acq = np.asarray(ehvi(samples[0], samples[1], observed, ref))
        else:
            acq = mc_ehvi_nd(samples, observed, ref)
        return self._apply_pof(s, post, idx, acq)

    # -- driver -------------------------------------------------------------
    def run(self, max_steps: int = 10_000) -> List[SearchCompletion]:
        steps = 0
        while (self.queue or self.active) and steps < max_steps:
            self.step()
            steps += 1
        return self.collect()
