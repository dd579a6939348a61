"""Similarity-based data selection — the paper's Algorithm 1 (§III-C).

For a target workload z_i and candidate workloads z_j in the repository:
for every pair of runs (r_n of z_i, r_m of z_j) on the SAME machine type,
    weight = |log2 nodes(r_n) - log2 nodes(r_m)|
    DIST   -> (1 / 2^weight,  (pearsonr(metrics) + 1) / 2)
The candidate score is the scaling-factor-weighted average of the
similarity scores; candidates sorted descending, best k returned.

Two paths: the faithful pure-python loop (exactly Algorithm 1, used at
search-time sizes) and a vectorised batch path over the whole repository
using the ``pairwise_pearson`` kernel (the "proper distance operator" a
real deployment needs, §IV-E). The batch path serves many targets at
once (``CandidateIndex.query_many``): a ``SearchService`` step scores
every karasu tenant in one launch.
"""
from __future__ import annotations

import math
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.pairwise_pearson.ops import _pearson_launch
from repro.kernels.routing import resolve_impl
from .plan import CAND_ROUND_TO, round_rows
from .types import RunRecord


def dist(r_n: RunRecord, r_m: RunRecord) -> Tuple[float, float]:
    """The paper's DIST: (scaling factor, similarity score in [0,1])."""
    weight = abs(math.log2(max(r_n.node_count, 1))
                 - math.log2(max(r_m.node_count, 1)))
    a, b = r_n.metric_vector(), r_m.metric_vector()
    sa, sb = np.std(a), np.std(b)
    if sa < 1e-12 or sb < 1e-12:
        score = 0.0
    else:
        score = float(np.corrcoef(a, b)[0, 1])
    return 1.0 / (2.0 ** weight), (score + 1.0) / 2.0


def select_similar(
    target_runs: Sequence[RunRecord],
    candidates: Dict[str, Sequence[RunRecord]],
    k: int,
    *,
    default_score: float = 0.5,
) -> List[Tuple[str, float]]:
    """Algorithm 1, faithful loop. Returns the k best (workload_id, score)."""
    results: List[Tuple[str, float]] = []
    for z_j, runs_j in candidates.items():
        num, den = 0.0, 0.0
        for r_n in target_runs:
            for r_m in runs_j:
                if r_n.machine_type == r_m.machine_type:
                    w, s = dist(r_n, r_m)
                else:
                    w, s = 0.0, default_score  # default for unmatched types
                num += w * s
                den += w
        score = num / den if den > 0 else default_score
        results.append((z_j, score))
    results.sort(key=lambda t: -t[1])
    return results[:k]


class CandidateIndex:
    """Precomputed candidate-side arrays for repeated Algorithm-1 queries.

    Stacking every candidate run's metric vector / machine type / node
    count is O(repository) work; a multi-tenant ``SearchService`` runs
    Algorithm 1 for every tenant every iteration against the *same*
    repository snapshot, so the index is built once (and rebuilt only
    when the repository version moves) and each round of queries pays
    one pairwise-Pearson launch plus vectorised segment reductions.
    The candidate rows live on the device, padded with zero rows to a
    multiple of ``plan.CAND_ROUND_TO``."""

    def __init__(self, candidates: Dict[str, Sequence[RunRecord]]):
        cand_ids: List[str] = []
        cand_runs: List[RunRecord] = []
        for z_j, runs_j in candidates.items():
            for r in runs_j:
                if r.metrics is None:    # unusable without agg(l)
                    continue
                cand_ids.append(z_j)
                cand_runs.append(r)
        self.workload_ids: List[str] = list(candidates.keys())
        self.empty = not cand_runs
        if self.empty:
            return
        self._zindex = {z: i for i, z in enumerate(self.workload_ids)}
        self._seg = np.array([self._zindex[z] for z in cand_ids])
        self.n_cand = len(cand_runs)
        rows = np.stack([r.metric_vector() for r in cand_runs])
        padded = np.zeros((-(-self.n_cand // CAND_ROUND_TO) * CAND_ROUND_TO,
                           rows.shape[1]), np.float32)
        padded[:self.n_cand] = rows
        self._metrics = jnp.asarray(padded)
        self._types = np.array([r.machine_type for r in cand_runs])
        self._log_nodes = np.log2(
            np.array([max(r.node_count, 1) for r in cand_runs]))

    @property
    def metric_dim(self) -> int:
        return int(self._metrics.shape[1])

    def correlate(self, rows: np.ndarray, impl: str) -> np.ndarray:
        """Pearson correlation of each metric row with every candidate,
        ``(r, n_cand)`` on the host: one launch of ``_pearson_launch``
        at the padded row count (``plan.round_rows``), read back once.
        ``impl`` must be concrete (not ``"auto"``)."""
        r = rows.shape[0]
        a = np.zeros((round_rows(r), rows.shape[1]), np.float32)
        a[:r] = rows
        corr = jax.device_get(_pearson_launch(a, self._metrics, impl=impl))
        return corr[:r, :self.n_cand]

    def query(self, target_runs: Sequence[RunRecord], k: int, *,
              impl: str = "xla", default_score: float = 0.5,
              exclude: Optional[Sequence[str]] = None
              ) -> List[Tuple[str, float]]:
        """Top-k candidates of one target: ``query_many`` of one."""
        return self.query_many([target_runs], k, impl=impl,
                               default_score=default_score,
                               exclude=[exclude])[0]

    def query_many(self, targets: Sequence[Sequence[RunRecord]], k: int, *,
                   impl: str = "xla", default_score: float = 0.5,
                   exclude: Optional[Sequence[Optional[Sequence[str]]]]
                   = None, counters: Optional[dict] = None
                   ) -> List[List[Tuple[str, float]]]:
        """Top-k candidates of each target run list, in input order (an
        empty list gets ``[]``). Every target's metric rows go to ONE
        Pearson launch per kernel impl — each target's ``impl``
        resolves on its own cell count, as a query of it alone would —
        and the weighting, segment reduction and cut run in numpy on the
        target's rows. ``exclude[i]`` drops workload ids from target
        ``i`` before the cut (e.g. a tenant's own published runs —
        which would otherwise score ~1.0 against themselves and defeat
        the LOO safeguard). ``counters["launches"]`` counts the Pearson
        launches."""
        out: List[List[Tuple[str, float]]] = [[] for _ in targets]
        if self.empty:
            return out
        by_impl: Dict[str, List[int]] = {}
        for i, runs in enumerate(targets):
            if runs:
                by_impl.setdefault(resolve_impl(
                    impl, cells=len(runs) * self.n_cand), []).append(i)
        for r_impl, idxs in by_impl.items():
            corr = self.correlate(np.concatenate(
                [np.stack([r.metric_vector() for r in targets[i]])
                 for i in idxs]), r_impl)
            off = 0
            for i in idxs:
                n = len(targets[i])
                out[i] = self._top_k(
                    targets[i], corr[off:off + n], k, default_score,
                    exclude[i] if exclude is not None else None)
                off += n
            if counters is not None:
                counters["launches"] = counters.get("launches", 0) + 1
        return out

    def _top_k(self, target_runs: Sequence[RunRecord], corr: np.ndarray,
               k: int, default_score: float,
               exclude: Optional[Sequence[str]]
               ) -> List[Tuple[str, float]]:
        """Algorithm 1's weighting and cut for one target, from its
        ``(n_runs, n_cand)`` correlation rows."""
        sim = (corr + 1.0) / 2.0
        t_types = np.array([r.machine_type for r in target_runs])
        t_nodes = np.log2(np.array([max(r.node_count, 1)
                                    for r in target_runs]))
        w = np.exp2(-np.abs(t_nodes[:, None] - self._log_nodes[None, :]))
        same = t_types[:, None] == self._types[None, :]
        w = np.where(same, w, 0.0)
        sim = np.where(same, sim, default_score)

        nz = len(self.workload_ids)
        num = np.bincount(self._seg, weights=(w * sim).sum(0), minlength=nz)
        den = np.bincount(self._seg, weights=w.sum(0), minlength=nz)
        score = np.where(den > 0, num / np.maximum(den, 1e-300),
                         default_score)
        out = list(zip(self.workload_ids, score.tolist()))
        if exclude:
            banned = set(exclude)
            out = [t for t in out if t[0] not in banned]
        out.sort(key=lambda t: -t[1])
        return out[:k]


def select_similar_batched(
    target_runs: Sequence[RunRecord],
    candidates: Dict[str, Sequence[RunRecord]],
    k: int,
    *,
    impl: str = "xla",
    default_score: float = 0.5,
    index: Optional[CandidateIndex] = None,
) -> List[Tuple[str, float]]:
    """Vectorised Algorithm 1: one pairwise-Pearson kernel call between
    the target's runs and ALL candidate runs, then a weighted reduction.
    Semantics identical to select_similar. Pass a prebuilt
    ``CandidateIndex`` to amortise candidate stacking across queries."""
    if not target_runs or (index is None and not candidates):
        return []
    if index is None:
        index = CandidateIndex(candidates)
    return index.query(target_runs, k, impl=impl,
                       default_score=default_score)
