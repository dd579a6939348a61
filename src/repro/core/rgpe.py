"""Ranking-weighted Gaussian Process Ensemble (paper §III-B, after
Feurer et al. 2022).

Given base GPs fit on support workloads' shared observations and a target
GP fit on the target's own (few) observations:

 1. sample each model's predictions at the target's observed configs
    (base models: marginal posterior; target: leave-one-out posterior);
 2. score every sample with the *ranking loss* — the number of misranked
    pairs vs the target's observed y (prediction scale never matters,
    which is what makes cross-workload transfer possible);
 3. weight a_i = fraction of samples where model i achieves the minimum
    loss (ties split evenly);
 4. weight-dilution prevention: a base model is dropped when its median
    loss exceeds the 95th percentile of the target model's loss.

The ensemble posterior is the a-weighted mixture:
    mu = sum a_i mu_i,  var = sum a_i^2 var_i .

The O(S * n^2) pairwise loss over MC samples is the compute hot spot at
scale; ``repro.kernels.ranking_loss`` provides the Pallas-tiled version.

Two paths share the same weighting math: the sequential reference
(``compute_weights`` over a list of GPs) and the batched path
(``compute_weights_batched`` over one ``BatchedGP``), which draws every
base model's samples from a single vmapped posterior and scores all
(m+1) x S samples with ONE ranking-loss kernel call. Both paths split
the PRNG key identically, so they produce the same weights up to float
roundoff.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ranking_loss import (ranking_loss,
                                        ranking_loss_launch_fn,
                                        ranking_loss_padded)
from .gp import (GP, BatchedGP, batched_posterior, batched_sample,
                 gp_loo_samples, gp_posterior, gp_sample)
from .plan import (LooSampleQuery, PlanExecutor, SampleQuery,
                   StepPlanner, flatten_counters)


@dataclasses.dataclass(frozen=True)
class Ensemble:
    models: Tuple[GP, ...]         # base models + target LAST
    weights: jnp.ndarray           # (m + 1,), on the simplex

    @property
    def target(self) -> GP:
        return self.models[-1]


def compute_weights(
    base_models: Sequence[GP],
    target: GP,
    key: jax.Array,
    *,
    n_samples: int = 256,
    dilution_percentile: float = 95.0,
    impl: str = "xla",
) -> jnp.ndarray:
    """Returns (m+1,) weights; index -1 is the target model."""
    x_tar, y_tar = target.x, target.y
    n = int(y_tar.shape[0])
    m = len(base_models)
    if n < 2:
        # a single observation cannot rank pairs: spread weight uniformly
        # so the support models carry the prior (this is what lets Karasu
        # diverge from the baselines already at profiling run 2, fig. 3)
        return jnp.full((m + 1,), 1.0 / (m + 1))

    keys = jax.random.split(key, m + 1)
    losses = []
    for i, gp in enumerate(base_models):
        s = gp_sample(gp, x_tar, keys[i], n_samples)      # (S, n)
        losses.append(ranking_loss(s, y_tar, impl=impl))  # (S,)
    s_tar = gp_loo_samples(target, keys[-1], n_samples)
    losses.append(ranking_loss(s_tar, y_tar, impl=impl))
    loss_mat = jnp.stack(losses)                          # (m+1, S)
    return _weights_from_losses(loss_mat, dilution_percentile)


def _weights_from_losses(loss_mat: jnp.ndarray,
                         dilution_percentile: float) -> jnp.ndarray:
    """(m+1, S) ranking losses (target last) -> simplex weights."""
    # weight-dilution prevention (Feurer et al. §4.2)
    tar_pct = jnp.percentile(loss_mat[-1], dilution_percentile)
    medians = jnp.median(loss_mat, axis=1)
    diluted = medians > tar_pct
    diluted = diluted.at[-1].set(False)                   # never drop target
    loss_mat = jnp.where(diluted[:, None], jnp.inf, loss_mat)

    # a_i = E_s[ 1(i in argmin) / |argmin| ]
    mins = jnp.min(loss_mat, axis=0, keepdims=True)
    is_min = (loss_mat == mins).astype(jnp.float32)
    w = jnp.mean(is_min / jnp.sum(is_min, axis=0, keepdims=True), axis=1)
    return w / jnp.sum(w)


def _weights_from_losses_batched(loss_mats: jnp.ndarray,
                                 dilution_percentile: float) -> jnp.ndarray:
    """(J, m+1, S) stacked ranking losses -> (J, m+1) simplex weights.
    Same per-job ops as ``_weights_from_losses``, vectorised over the
    job axis so a scoring round reduces all ensembles of a (m, S) shape
    group in one pass of array ops instead of a per-job Python loop."""
    tar_pct = jnp.percentile(loss_mats[:, -1, :], dilution_percentile,
                             axis=-1)
    medians = jnp.median(loss_mats, axis=-1)
    diluted = medians > tar_pct[:, None]
    diluted = diluted.at[:, -1].set(False)                # never drop target
    lm = jnp.where(diluted[:, :, None], jnp.inf, loss_mats)

    mins = jnp.min(lm, axis=1, keepdims=True)
    is_min = (lm == mins).astype(jnp.float32)
    w = jnp.mean(is_min / jnp.sum(is_min, axis=1, keepdims=True), axis=2)
    return w / jnp.sum(w, axis=1, keepdims=True)


def compute_weights_batched(
    bases: BatchedGP,
    target: GP,
    key: jax.Array,
    *,
    n_samples: int = 256,
    dilution_percentile: float = 95.0,
    impl: str = "xla",
) -> jnp.ndarray:
    """Batched twin of ``compute_weights``: one vmapped posterior for all
    base models and one ranking-loss call over the stacked (m+1) x S
    samples. Splits the key exactly like the sequential path, so both
    return the same weights (modulo float roundoff)."""
    x_tar, y_tar = target.x, target.y
    n = int(y_tar.shape[0])
    m = bases.m
    if n < 2:
        return jnp.full((m + 1,), 1.0 / (m + 1))

    keys = jax.random.split(key, m + 1)
    s_base = batched_sample(bases, x_tar, keys[:m], n_samples,
                            impl=impl)                       # (m, S, n)
    s_tar = gp_loo_samples(target, keys[-1], n_samples)      # (S, n)
    stacked = jnp.concatenate([s_base.reshape(m * n_samples, n), s_tar])
    loss = ranking_loss(stacked, y_tar, impl=impl)           # ((m+1)*S,)
    loss_mat = loss.reshape(m + 1, n_samples)
    return _weights_from_losses(loss_mat, dilution_percentile)


@dataclasses.dataclass(frozen=True)
class WeightJob:
    """One RGPE weighting problem — (support stack, target, PRNG key) for
    a single (tenant, measure) ensemble. ``n_samples`` may differ per job
    (the padded scorer handles ragged sample counts like ragged n_obs)."""
    bases: BatchedGP
    target: GP
    key: jax.Array
    n_samples: int = 256


def compute_weights_multi(
    jobs: Sequence[WeightJob],
    *,
    dilution_percentile: float = 95.0,
    impl: str = "xla",
    fuse_samples: bool = True,
    sample_counters: Optional[dict] = None,
    planner: Optional[StepPlanner] = None,
    plan_executor: Optional[PlanExecutor] = None,
) -> List[jnp.ndarray]:
    """Score MANY ensembles with ONE padded ranking-loss launch.

    Cross-tenant twin of ``compute_weights_batched``: every job draws its
    samples exactly as the per-ensemble path does (same key splits, same
    shapes, so weights agree to float roundoff), then all jobs' sample
    rows are padded to a common n_max and scored by a single
    ``ranking_loss_padded`` call — ragged n_obs is handled by per-row
    validity masks, mirroring ``BatchedGP``'s padding contract. Jobs with
    n_obs < 2 short-circuit to uniform weights (no rankable pair).

    With ``fuse_samples`` (the default) every job emits its draws as
    query-plan nodes — one ``SampleQuery`` per support stack and one
    ``LooSampleQuery`` per target — and ONE planned ``PlanExecutor``
    round runs one launch per (S, q, d) / (S, n) bucket, the same
    planner a ``SearchService`` step routes its grid posteriors
    through (pass ``planner`` / ``plan_executor`` to share policy and
    launch dispatch — a service with donating or fused launches pins
    them there; defaults otherwise). Draw streams are identical to the
    per-job
    ``batched_sample`` / ``gp_loo_samples`` loops
    (``fuse_samples=False``), so weights agree to float roundoff.
    ``sample_counters`` (flat ``launches``/``queries``) reports the
    fused launch count. The final weight reduction runs vectorised per
    (m, S) shape group (``_weights_from_losses_batched``) on both
    paths.
    """
    out: List[Optional[jnp.ndarray]] = [None] * len(jobs)
    live: List[Tuple[int, WeightJob, jax.Array]] = []
    for ji, job in enumerate(jobs):
        n = int(job.target.y.shape[0])
        m = job.bases.m
        if n < 2:
            out[ji] = jnp.full((m + 1,), 1.0 / (m + 1))
            continue
        live.append((ji, job, jax.random.split(job.key, m + 1)))

    if fuse_samples:
        planner = planner if planner is not None else StepPlanner()
        queries = [SampleQuery(job.bases, job.target.x,
                               keys[:job.bases.m], job.n_samples)
                   for _, job, keys in live] + \
                  [LooSampleQuery(job.target, keys[-1], job.n_samples)
                   for _, job, keys in live]
        nested: dict = {}
        executor = (plan_executor if plan_executor is not None
                    else PlanExecutor(impl=impl))
        res = executor.execute(planner.plan(queries), counters=nested,
                               impl=impl)
        s_bases, s_tars = res[:len(live)], res[len(live):]
        flatten_counters(nested, sample_counters, ("sample", "loo"))
    else:
        s_bases = [batched_sample(job.bases, job.target.x,
                                  keys[:job.bases.m], job.n_samples,
                                  impl=impl)
                   for _, job, keys in live]
        s_tars = [gp_loo_samples(job.target, keys[-1], job.n_samples)
                  for _, job, keys in live]

    rows_p, rows_y, rows_nv, spans = [], [], [], []
    for (ji, job, keys), s_base, s_tar in zip(live, s_bases, s_tars):
        y_tar = job.target.y
        n = int(y_tar.shape[0])
        m = job.bases.m
        stacked = jnp.concatenate(
            [s_base.reshape(m * job.n_samples, n), s_tar])  # ((m+1)S, n)
        rows_p.append(stacked)
        rows_y.append(jnp.broadcast_to(y_tar[None], stacked.shape))
        rows_nv.append(jnp.full((stacked.shape[0],), n, jnp.int32))
        spans.append((ji, m, job.n_samples))
    if not rows_p:
        return out

    # planner-policy padding closes the launch's shape vocabulary: the
    # sample axis rounds like an observation axis, the row axis like a
    # fused lane axis (pow2, shard-lifted). Pad rows carry n_valid = 0
    # — zero rankable pairs, score 0 — and per-row independence keeps
    # the real rows bitwise identical to the exact-shape launch.
    planner = planner if planner is not None else StepPlanner()
    n_pad = planner.round_obs(max(p.shape[1] for p in rows_p))
    preds = jnp.concatenate(
        [jnp.pad(p, ((0, 0), (0, n_pad - p.shape[1]))) for p in rows_p])
    ys = jnp.concatenate(
        [jnp.pad(y, ((0, 0), (0, n_pad - y.shape[1]))) for y in rows_y])
    nv = jnp.concatenate(rows_nv)
    r = int(preds.shape[0])
    r_pad = planner.round_models(r)
    if r_pad > r:
        preds = jnp.pad(preds, ((0, r_pad - r), (0, 0)))
        ys = jnp.pad(ys, ((0, r_pad - r), (0, 0)))
        nv = jnp.pad(nv, (0, r_pad - r))
    # every argument is a fresh per-step stack, so the donating twin
    # (pinned by the sharing service's executor when one is passed) is
    # alias-safe
    launch = ranking_loss_launch_fn(
        donate=plan_executor.donate if plan_executor is not None
        else None)
    loss = launch(preds, ys, nv, impl=impl)[:r]
    # one vectorised weight reduction per (m, S) shape group instead of
    # a per-job loop of small eager ops
    offs, off = [], 0
    for ji, m, s in spans:
        offs.append(off)
        off += (m + 1) * s
    wgroups: dict = {}
    for (ji, m, s), o in zip(spans, offs):
        wgroups.setdefault((m, s), []).append((ji, o))
    for (m, s), entries in wgroups.items():
        mats = jnp.stack([loss[o:o + (m + 1) * s].reshape(m + 1, s)
                          for _, o in entries])
        ws = _weights_from_losses_batched(mats, dilution_percentile)
        for (ji, _), w in zip(entries, ws):
            out[ji] = w
    return out


def build_ensemble(base_models: Sequence[GP], target: GP, key: jax.Array,
                   *, n_samples: int = 256, impl: str = "xla") -> Ensemble:
    w = compute_weights(base_models, target, key, n_samples=n_samples,
                        impl=impl)
    return Ensemble(tuple(base_models) + (target,), w)


def ensemble_posterior(ens: Ensemble, xq: jnp.ndarray
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Weighted mixture posterior (standardised scale)."""
    mus, vars_ = [], []
    for gp in ens.models:
        mu, var = gp_posterior(gp, xq)
        mus.append(mu)
        vars_.append(var)
    mus = jnp.stack(mus)            # (m+1, q)
    vars_ = jnp.stack(vars_)
    w = ens.weights[:, None]
    mu = jnp.sum(w * mus, axis=0)
    var = jnp.sum((w ** 2) * vars_, axis=0)
    return mu, jnp.maximum(var, 1e-10)


@dataclasses.dataclass(frozen=True)
class BatchedEnsemble:
    """RGPE ensemble whose base models live in one BatchedGP stack; the
    target keeps its exact (unpadded) representation for LOO sampling."""
    bases: BatchedGP
    target: GP
    weights: jnp.ndarray           # (m + 1,), target last, on the simplex


def build_ensemble_batched(bases: BatchedGP, target: GP, key: jax.Array,
                           *, n_samples: int = 256, impl: str = "xla"
                           ) -> BatchedEnsemble:
    w = compute_weights_batched(bases, target, key, n_samples=n_samples,
                                impl=impl)
    return BatchedEnsemble(bases, target, w)


def mix_weighted(mu_b: jnp.ndarray, var_b: jnp.ndarray,
                 mu_t: jnp.ndarray, var_t: jnp.ndarray,
                 w: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """RGPE mixture from stacked base posterior rows ``(m, q)`` plus the
    target row ``(q,)``; ``w`` is ``(m+1,)`` with the target LAST. The
    one mixing rule every path (run_search, run_search_moo, the service)
    applies after its grid posteriors come back from the query plan.
    It runs in the array module of its rows: host (numpy) rows, as the
    plan executor hands them back, mix in numpy float32 on the host;
    device rows mix as jax ops on the device."""
    xp = np if all(isinstance(a, np.ndarray)
                   for a in (mu_b, var_b, mu_t, var_t)) else jnp
    if xp is np:
        mu_b, var_b, mu_t, var_t, w = (np.asarray(a, np.float32) for a in
                                       (mu_b, var_b, mu_t, var_t, w))
    wb, wt = w[:-1, None], w[-1]
    mu = xp.sum(wb * mu_b, axis=0) + wt * mu_t
    var = xp.sum((wb ** 2) * var_b, axis=0) + (wt ** 2) * var_t
    return mu, xp.maximum(var, 1e-10)


def ensemble_posterior_batched(ens: BatchedEnsemble, xq: jnp.ndarray, *,
                               impl: str = "xla"
                               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Weighted mixture posterior from one batched base query + the
    target query (standardised scale); matches ``ensemble_posterior``."""
    mu_b, var_b = batched_posterior(ens.bases, xq, impl=impl)   # (m, q)
    mu_t, var_t = gp_posterior(ens.target, xq, impl=impl)
    return mix_weighted(mu_b, var_b, mu_t, var_t, ens.weights)


def target_best(ens) -> jnp.ndarray:
    """Best (min) observed target value on the ensemble's output scale;
    works for both Ensemble and BatchedEnsemble (anything with .target).

    The ensemble mean at observed data is dominated by the target model's
    standardised y, so the incumbent for EI is the target's standardised
    minimum scaled by its weight-mixed mean — we use the plain
    standardised min, which is exact when the target carries the weight
    and rank-correct otherwise."""
    return jnp.min(ens.target.y)
