"""Search-based resource-configuration profiling loops.

  - NaiveBO    (CherryPick): Matern-5/2 GP prior + EI, constraints via
               probability of feasibility.
  - AugmentedBO (Arrow): Extra-Trees prior fed low-level metric averages,
               EI acquisition.
  - Karasu     : NaiveBO extended with the RGPE ensemble over support
               models chosen by Algorithm 1 from the shared repository.

All methods share the same protocol (paper §IV-C): 3 random initial
samples, <= 20 profiling runs, optional CherryPick stopping rule (stop
when max EI <= 10% of the incumbent and >= 6 runs done).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .acquisition import (constrained_ei, expected_improvement, feasible,
                          probability_of_feasibility)
from .encoding import SearchSpace
from .extra_trees import fit_extra_trees
from .gp import batched_posterior
from .plan import PlanExecutor, PosteriorQuery, StepPlanner, round_rows
from .repository import Repository, SupportModelStore
from .rgpe import WeightJob, compute_weights_multi, mix_weighted
from .selection import CandidateIndex
from .types import BOResult, Constraint, Objective, Observation, RunRecord

ProfileFn = Callable[[Mapping], Tuple[Dict[str, float], np.ndarray]]
# profile_fn(config) -> (measures, compact metric matrix)

# the single-tenant drivers share one planner/executor pair with default
# policy — the same query-plan layer a SearchService step uses, so the
# serving path and the reference loop literally share one plan
# implementation (m_round_pow2=False on fits: a fixed-size measure
# cohort never varies step to step, so lane padding buys nothing)
_PLANNER = StepPlanner()
_EXECUTOR = PlanExecutor()


# PRNG purpose tags. Every per-iteration key consumer derives its keys
# as nested fold_ins of (purpose, iteration, index) — distinct purposes
# give disjoint subtrees, so no arithmetic on tag integers can make two
# consumers collide (the old ``1000 + it * 10 + oi`` MOO tag shared the
# integer space with every other single-fold tag).
KEY_PURPOSE_RGPE = 0          # RGPE support-sample draws (index: measure)
KEY_PURPOSE_MOO_EHVI = 1      # MC-EHVI posterior draws (index: objective)

# the purpose registry: ``repro.analysis.prng_audit`` proves the tags
# distinct and the enumerated (purpose, iteration, index) tree
# collision-free — add new purposes HERE so the audit covers them
KEY_PURPOSES: Dict[str, int] = {
    "rgpe": KEY_PURPOSE_RGPE,
    "moo_ehvi": KEY_PURPOSE_MOO_EHVI,
}


def derive_key(base: jax.Array, purpose: int, it: int,
               index: int) -> jax.Array:
    """Collision-free per-(purpose, iteration, index) PRNG key."""
    k = jax.random.fold_in(base, purpose)
    k = jax.random.fold_in(k, it)
    return jax.random.fold_in(k, index)


@partial(jax.jit, static_argnames=("purpose",))
def _derive_keys_launch(bases, its, indices, purpose: int):
    return jax.vmap(lambda b, it, index: derive_key(b, purpose, it, index))(
        bases, its, indices)


def derive_keys(bases: np.ndarray, purpose: int, its: Sequence[int],
                indices: Sequence[int]) -> np.ndarray:
    """Row j is ``derive_key(bases[j], purpose, its[j], indices[j])``,
    bit for bit, as host key data: one jitted launch for all rows, read
    back once. ``bases`` stacks raw key data (``(J, 2)`` uint32 for the
    default PRNG); the row axis pads to ``plan.round_rows`` so the
    launch compiles once per rung."""
    j = len(its)
    pad = round_rows(j)
    b = np.zeros((pad,) + np.shape(bases)[1:], np.uint32)
    b[:j] = bases
    it = np.zeros((pad,), np.int32)
    it[:j] = its
    ix = np.zeros((pad,), np.int32)
    ix[:j] = indices
    return jax.device_get(
        _derive_keys_launch(b, it, ix, purpose=purpose))[:j]


@dataclasses.dataclass(frozen=True)
class BOConfig:
    n_init: int = 3
    max_iters: int = 20
    noise: float = 0.1
    early_stop: bool = False
    ei_threshold: float = 0.1     # CherryPick: stop when EI <= 10% incumbent
    min_iters: int = 6
    n_support: int = 3            # Karasu support models
    rgpe_samples: int = 256
    kernel_impl: str = "xla"      # xla | pallas | pallas_interpret


# the one feasibility rule, shared with pareto_of_observations and the
# serving layer (historical private name kept for existing importers)
_feasible = feasible


def _best_feasible_value(observations, objective, constraints):
    vals = [o.measures[objective.name] for o in observations
            if _feasible(o, constraints)]
    return min(vals) if vals else None


def _best_index_so_far(observations, objective, constraints) -> int:
    best_i, best_v = -1, np.inf
    for i, o in enumerate(observations):
        if _feasible(o, constraints) and o.measures[objective.name] < best_v:
            best_i, best_v = i, o.measures[objective.name]
    return best_i


def _profile_into(space, xq_all, profile_fn, objective, constraints,
                  observations, best_idx, profiled, ci: int) -> Observation:
    """Execute one profiling run and record it — the bookkeeping shared
    verbatim by run_search and SearchService sessions."""
    config = space.configs[ci]
    measures_out, metrics = profile_fn(config)
    obs = Observation(config=config, x=xq_all[ci], measures=measures_out,
                      metrics=metrics)
    observations.append(obs)
    profiled.add(ci)
    best_idx.append(_best_index_so_far(observations, objective, constraints))
    return obs


def _acquisition(post, observations, objective, constraints):
    """Constrained EI over whatever grid ``post`` was evaluated on.
    Shared by run_search and SearchService so the acquisition and its
    incumbent handling cannot diverge. Returns (acq, best_raw, obj_post)."""
    obj_post = post[objective.name]
    best_raw = _best_feasible_value(observations, objective, constraints)
    if best_raw is None:
        best_raw = min(o.measures[objective.name] for o in observations)
    best_std = (best_raw - obj_post["y_mean"]) / obj_post["y_std"]
    cons_posts = []
    for c in constraints:
        cp = post[c.name]
        ub_std = (c.upper_bound - cp["y_mean"]) / cp["y_std"]
        cons_posts.append((cp["mu"], cp["var"], ub_std))
    acq = np.asarray(constrained_ei(obj_post["mu"], obj_post["var"],
                                    best_std, cons_posts))
    return acq, best_raw, obj_post


def _should_stop_early(cfg, n_obs: int, acq, obj_post, best_raw) -> bool:
    """CherryPick stopping rule: max EI <= 10% of the incumbent, after at
    least min_iters profiling runs."""
    if not cfg.early_stop or n_obs < cfg.min_iters:
        return False
    ei_raw = float(np.max(acq)) * float(obj_post["y_std"])
    return ei_raw <= cfg.ei_threshold * abs(best_raw)


class KarasuContext:
    """Per-search (or per-service, shared across tenants) Karasu state:
    the incremental support-model store plus a repository-version-keyed
    Algorithm-1 candidate index. Everything in here is derived purely
    from repository contents, so N concurrent searches against the same
    repository can (and should) share one context."""

    def __init__(self, repository: Repository, space: SearchSpace, *,
                 noise: float = 0.1,
                 store: Optional[SupportModelStore] = None):
        self.repo = repository
        self.store = store or SupportModelStore(repository, space,
                                                noise=noise)
        self._index: Optional[CandidateIndex] = None
        self._index_version = -1

    def candidate_index(self) -> CandidateIndex:
        v = self.repo.global_version()
        if self._index is None or v != self._index_version:
            self._index = CandidateIndex(self.repo.all_runs())
            self._index_version = v
        return self._index

    @staticmethod
    def score_ensembles(jobs: Sequence[WeightJob], *,
                        impl: str = "xla", fuse_samples: bool = True,
                        sample_counters: Optional[dict] = None,
                        planner: Optional[StepPlanner] = None,
                        plan_executor: Optional[PlanExecutor] = None
                        ) -> List:
        """RGPE weights for every queued (tenant, measure) ensemble of a
        scheduling round in ONE padded ranking-loss launch, with every
        job's support-sample draw emitted as ``SampleQuery`` /
        ``LooSampleQuery`` nodes into the query plan
        (``fuse_samples=False`` restores the per-job draw loop, the
        parity/benchmark baseline; ``planner`` shares the caller's
        bucketing policy). Static — the weighting depends only on the
        jobs, never on context state, so a service may score jobs
        spanning several contexts in one call. Single-tenant
        ``run_search`` batches its measures through the same entry
        point, so the serving path and the reference loop cannot
        diverge."""
        return compute_weights_multi(jobs, impl=impl,
                                     fuse_samples=fuse_samples,
                                     sample_counters=sample_counters,
                                     planner=planner,
                                     plan_executor=plan_executor)


def _target_runs(observations) -> List[RunRecord]:
    return [RunRecord("__target__", o.config, o.metrics, o.measures)
            for o in observations if o.metrics is not None]


def _model_posteriors_karasu(observations, measures, cfg,
                             ctx: KarasuContext, key, xq):
    """RGPE ensemble posterior per measure + target scalers.

    All target GPs (one per measure) are fit in ONE vmapped batch under
    the planner's shape policy, and every grid posterior the iteration
    needs — the target stack AND all measures' RGPE support stacks —
    is emitted as ``PosteriorQuery`` nodes and executed by the SAME
    collect → plan → execute → scatter layer a ``SearchService`` step
    uses, preceded by one padded ranking-loss launch for the weights.
    The old per-ensemble posterior loop lives on only in
    ``ensemble_posterior_batched``, the parity oracle."""
    selected = ctx.candidate_index().query(
        _target_runs(observations), cfg.n_support, impl=cfg.kernel_impl)

    x = np.stack([o.x for o in observations])
    ys = [np.array([o.measures[m] for o in observations])
          for m in measures]
    tgts = _PLANNER.fit_targets([x] * len(measures), ys, noise=cfg.noise,
                                m_round_pow2=False)
    jobs, job_meta = [], []
    for mi, m in enumerate(measures):
        bases, _ids = ctx.store.get_stacked([z for z, _ in selected], m)
        if bases is not None:
            jobs.append(WeightJob(bases, tgts.extract(mi),
                                  jax.random.fold_in(key, mi),
                                  cfg.rgpe_samples))
            job_meta.append((mi, m, bases))
    # all measures' ensembles scored in one padded ranking-loss launch
    ws = ctx.score_ensembles(jobs, impl=cfg.kernel_impl, planner=_PLANNER)
    # ... and ALL grid posteriors (targets + ensemble members) planned
    # into fused launches
    res = _EXECUTOR.execute(
        _PLANNER.plan([PosteriorQuery(tgts, xq)]
                      + [PosteriorQuery(bases, xq)
                         for _, _, bases in job_meta]),
        impl=cfg.kernel_impl)
    mu_t, var_t = res[0]
    out = {}
    for mi, m in enumerate(measures):
        out[m] = {"mu": mu_t[mi], "var": var_t[mi],
                  "y_mean": tgts.y_mean[mi], "y_std": tgts.y_std[mi],
                  "weights": np.array([1.0])}
    for (mi, m, bases), w, (mu_b, var_b) in zip(job_meta, ws, res[1:]):
        mu, var = mix_weighted(mu_b, var_b, out[m]["mu"], out[m]["var"], w)
        out[m] = {"mu": mu, "var": var, "y_mean": tgts.y_mean[mi],
                  "y_std": tgts.y_std[mi], "weights": np.asarray(w)}
    return out, selected


def _model_posteriors_naive(observations, measures, cfg, xq):
    """All measures' GPs share the observed x, so they fit and query as
    one BatchedGP — a single vmapped Cholesky instead of a measure loop."""
    x = np.stack([o.x for o in observations])
    ys = [np.array([o.measures[m] for o in observations])
          for m in measures]
    b = _PLANNER.fit_targets([x] * len(measures), ys, noise=cfg.noise,
                             m_round_pow2=False)
    mu, var = batched_posterior(b, xq)
    return {m: {"mu": mu[i], "var": var[i], "y_mean": b.y_mean[i],
                "y_std": b.y_std[i]}
            for i, m in enumerate(measures)}


def _model_posteriors_augmented(observations, measures, cfg, xq, seed):
    """Arrow: Extra-Trees on [encoded config ++ low-level metric means];
    candidate metrics imputed with the observed means."""
    out = {}
    metr = np.stack([
        np.mean(o.metrics, axis=1) if o.metrics is not None
        else np.zeros(6) for o in observations])
    x = np.stack([o.x for o in observations])
    x_aug = np.concatenate([x, metr], axis=1)
    imput = np.tile(metr.mean(0), (xq.shape[0], 1))
    xq_aug = np.concatenate([np.asarray(xq), imput], axis=1)
    for m in measures:
        y = np.array([o.measures[m] for o in observations])
        et = fit_extra_trees(x_aug, y, seed=seed)
        mu, var = et.posterior(xq_aug)
        out[m] = {"mu": jnp.asarray(mu), "var": jnp.asarray(var),
                  "y_mean": jnp.asarray(et.y_mean),
                  "y_std": jnp.asarray(et.y_std)}
    return out


def run_search(
    space: SearchSpace,
    profile_fn: ProfileFn,
    objective: Objective,
    constraints: Sequence[Constraint] = (),
    *,
    method: str = "naive",            # naive | augmented | karasu
    repository: Optional[Repository] = None,
    bo_config: BOConfig = BOConfig(),
    seed: int = 0,
) -> BOResult:
    cfg = bo_config
    key = jax.random.PRNGKey(seed)
    rng = np.random.default_rng(seed)
    measures = [objective.name] + [c.name for c in constraints]
    xq_all = space.all_encoded()
    ctx = (KarasuContext(repository, space, noise=cfg.noise)
           if method == "karasu" and repository is not None else None)

    observations: List[Observation] = []
    best_idx: List[int] = []
    profiled: set = set()
    stopped_at = cfg.max_iters
    meta: Dict = {"method": method, "selected": []}

    def profile(ci: int):
        _profile_into(space, xq_all, profile_fn, objective, constraints,
                      observations, best_idx, profiled, ci)

    # --- random initialisation (3 samples, paper §IV-B) -------------------
    init = rng.choice(len(space), size=min(cfg.n_init, len(space)),
                      replace=False)
    for ci in init:
        profile(int(ci))

    for it in range(len(observations), cfg.max_iters):
        remaining = [i for i in range(len(space)) if i not in profiled]
        if not remaining:
            stopped_at = it
            break
        xq = xq_all[remaining]

        if method == "karasu" and repository is not None:
            # per-measure jobs fold_in(mi) below this root, completing
            # the derive_key(key, RGPE, it, mi) schedule the service's
            # _rgpe_jobs derives identically
            rgpe_root = jax.random.fold_in(
                jax.random.fold_in(key, KEY_PURPOSE_RGPE), it)
            post, selected = _model_posteriors_karasu(
                observations, measures, cfg, ctx, rgpe_root, xq)
            meta["selected"].append([z for z, _ in selected])
        elif method == "augmented":
            post = _model_posteriors_augmented(observations, measures, cfg,
                                               xq, seed)
        else:
            post = _model_posteriors_naive(observations, measures, cfg, xq)

        # objective EI on the model's standardised scale
        acq, best_raw, obj_post = _acquisition(post, observations,
                                               objective, constraints)
        if _should_stop_early(cfg, len(observations), acq, obj_post,
                              best_raw):
            stopped_at = it
            break

        profile(remaining[int(np.argmax(acq))])

    meta["n_profiled"] = len(observations)
    return BOResult(observations=observations, best_index_per_iter=best_idx,
                    stopped_at=stopped_at, meta=meta)
