"""The shared performance-data repository (paper §III-B "Sharing").

Stores only minimal tuples (z, c, agg(l), y). Supports the evaluation's
data-availability filters (Cases A-D) through arbitrary predicates over
*private* workload tags kept OUTSIDE the shared record (the emulation
layer knows each workload's framework/algorithm/dataset; the repository
payload itself never contains them).

Every workload carries a monotonically increasing *version* bumped on
``add_run``; the ``SupportModelStore`` keys its per-(workload, measure)
support GPs on that version, so one shared store serves many concurrent
searches and refits a model only when that workload actually received
new data — instead of every search rebuilding every support model from
scratch.
"""
from __future__ import annotations

import dataclasses
import json
import os
from collections import OrderedDict, defaultdict
from typing import (Callable, Dict, Iterable, List, Mapping, Optional,
                    Sequence, Tuple)

import numpy as np

from .types import RunRecord


class Repository:
    def __init__(self) -> None:
        self._runs: Dict[str, List[RunRecord]] = defaultdict(list)
        self._versions: Dict[str, int] = defaultdict(int)

    # -- sharing API -------------------------------------------------------
    def add_run(self, run: RunRecord) -> None:
        self._runs[run.workload_id].append(run)
        self._versions[run.workload_id] += 1

    def add_runs(self, runs: Iterable[RunRecord]) -> None:
        for r in runs:
            self.add_run(r)

    def workloads(self) -> List[str]:
        return list(self._runs.keys())

    def runs(self, workload_id: str) -> List[RunRecord]:
        return list(self._runs.get(workload_id, []))

    def all_runs(self) -> Dict[str, List[RunRecord]]:
        return {z: list(rs) for z, rs in self._runs.items()}

    def version(self, workload_id: str) -> int:
        """Data version of one workload (0 if absent, bumped by add_run)."""
        return self._versions.get(workload_id, 0)

    def global_version(self) -> int:
        """Sum of all workload versions — changes iff any run was added."""
        return sum(self._versions.values())

    def __len__(self) -> int:
        return sum(len(rs) for rs in self._runs.values())

    # -- filtering (evaluation harness) -------------------------------------
    def filtered(self, keep: Callable[[str], bool]) -> "Repository":
        out = Repository()
        for z, rs in self._runs.items():
            if keep(z):
                out.add_runs(rs)
        return out

    def truncated(self, counts: Mapping[str, int]) -> "Repository":
        """Keep only the first counts[z] runs per workload (heterogeneous
        data-amount experiments, paper §IV-D)."""
        out = Repository()
        for z, rs in self._runs.items():
            out.add_runs(rs[:counts.get(z, len(rs))])
        return out

    # -- persistence ---------------------------------------------------------
    def save(self, path: str) -> None:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        payload = []
        for z, rs in self._runs.items():
            for r in rs:
                payload.append({
                    "z": z,
                    "config": dict(r.config),
                    "metrics": np.asarray(r.metrics).tolist(),
                    "measures": {k: float(v) for k, v in r.measures.items()},
                })
        with open(path, "w") as f:
            json.dump(payload, f)

    @classmethod
    def load(cls, path: str) -> "Repository":
        repo = cls()
        with open(path) as f:
            payload = json.load(f)
        for item in payload:
            repo.add_run(RunRecord(
                workload_id=item["z"],
                config=item["config"],
                metrics=np.asarray(item["metrics"]),
                measures=item["measures"]))
        return repo


# ---------------------------------------------------------------------------
# Incremental support-model store
# ---------------------------------------------------------------------------


class SupportModelStore:
    """Version-keyed cache of support GPs, one per (workload, measure).

    Shared across every search hitting the same repository (the
    ``SearchService`` holds one per search space): a support model is
    (re)fit only when its workload's repository version moved since the
    cached fit, i.e. ``add_run`` invalidates exactly the workloads it
    touched. Workloads with fewer than ``min_runs`` usable observations
    (or zero spread in the measure) cache ``None``.

    The stack cache is LRU-bounded at ``max_entries`` (generous by
    default — a steady multi-tenant cohort re-requests a handful of
    support sets per step, but a LONG-lived service whose tenants churn
    through many (support set, measure) combinations must not grow
    memory without bound; each padded stack holds (m, n, n) Cholesky
    factors). Capacity evictions are counted in ``evictions``;
    version-stale entries are dropped separately (and for free) on
    misses.
    """

    def __init__(self, repository: Repository, space, *,
                 noise: float = 0.1, min_runs: int = 3,
                 max_entries: int = 256):
        self._repo = repository
        self._space = space
        self._noise = noise
        self._min_runs = min_runs
        self._max_entries = max_entries
        # (workload, measure) -> (repo version at fit time, GP | None,
        # its host copy | None): the host copy is read back once, when
        # the fit is made, so a stack miss builds from host arrays
        self._cache: Dict[Tuple[str, str],
                          Tuple[int, Optional[object], Optional[object]]] = {}
        # (workload ids, measure) -> (versions at stack time, stack, ids)
        # in LRU order (most recently used last)
        self._stacked: "OrderedDict[Tuple[Tuple[str, ...], str], " \
            "Tuple[Tuple[int, ...], object, list]]" = OrderedDict()
        # (workload ids, measure) -> (versions, SharedMemory, handle) of
        # the stacks this store has exported cross-process
        self._shared: Dict[Tuple[Tuple[str, ...], str],
                           Tuple[Tuple[int, ...], object,
                                 "SharedStackHandle"]] = {}
        self._swept_at: Optional[int] = None   # repo version last swept
        self.hits = 0
        self.misses = 0
        self.stack_misses = 0
        self.evictions = 0

    @property
    def repository(self) -> Repository:
        return self._repo

    def get(self, workload_id: str, measure: str):
        """Support GP for (workload, measure), refit iff data changed."""
        return self._fitted(workload_id, measure)[1]

    def _fitted(self, workload_id: str, measure: str):
        """The cache entry of (workload, measure), refit iff data
        changed: (version, GP | None, host copy | None)."""
        from .gp import fit_gp
        v = self._repo.version(workload_id)
        k = (workload_id, measure)
        hit = self._cache.get(k)
        if hit is not None and hit[0] == v:
            self.hits += 1
            return hit
        self.misses += 1
        xs, ys = [], []
        for r in self._repo.runs(workload_id):
            if measure in r.measures:
                xs.append(self._space.encode(r.config))
                ys.append(r.measures[measure])
        if len(ys) >= self._min_runs and np.ptp(ys) > 0:
            gp = fit_gp(np.stack(xs), np.array(ys), noise=self._noise)
            self._cache[k] = (v, gp, gp.to_host())
        else:
            self._cache[k] = (v, None, None)
        return self._cache[k]

    def get_stacked(self, workload_ids: Sequence[str], measure: str):
        """BatchedGP over the available support models for ``measure``
        (skipping unusable workloads); returns (BatchedGP | None, ids).

        Stacks are version-cached like the per-model fits (a service
        step re-requests the same support stacks every round — without
        the cache each request re-assembles and re-uploads the padded
        arrays) and padded to multiples of 8, so the posterior/sample
        query plans see stable, already-bucketed shapes. A miss
        (counted in ``stack_misses``) stacks the models' host copies in
        numpy and puts the stack on the device in one transfer."""
        return self.get_stacked_many([(workload_ids, measure)])[0]

    def get_stacked_many(self, requests: Sequence[Tuple[Sequence[str], str]]
                         ) -> List[Tuple[Optional[object], List[str]]]:
        """``get_stacked`` for many ``(workload ids, measure)`` requests,
        in order: every miss's stack is assembled in numpy and all of
        them go to the device in ONE batched transfer; a key asked for
        twice is built once."""
        from .gp import put_stacks, stack_gps_host
        from .plan import OBS_ROUND_TO
        keys = [(tuple(ids), measure) for ids, measure in requests]
        found: Dict[Tuple[Tuple[str, ...], str], Tuple[object, list]] = {}
        misses: Dict[Tuple[Tuple[str, ...], str],
                     Tuple[Tuple[int, ...], object, list]] = {}
        for key in keys:
            if key in found or key in misses:      # asked for twice
                self.hits += len(found[key][1] if key in found
                                 else misses[key][2])
                continue
            vers = tuple(self._repo.version(z) for z in key[0])
            hit = self._stacked.get(key)
            if hit is not None and hit[0] == vers:
                self.hits += len(hit[2])
                self._stacked.move_to_end(key)          # LRU touch
                found[key] = (hit[1], hit[2])
                continue
            self.stack_misses += 1
            gps, ids = [], []
            for z in key[0]:
                host = self._fitted(z, key[1])[2]
                if host is not None:
                    gps.append(host)
                    ids.append(z)
            # stack at the planner's observation bucket so repeated steps
            # re-enter the query plans on already-bucketed shapes
            misses[key] = (vers, stack_gps_host(gps, round_to=OBS_ROUND_TO)
                           if gps else None, ids)
        if misses:
            built = [k for k, (_, st, _) in misses.items() if st is not None]
            on_device = dict(zip(built, put_stacks(
                [misses[k][1] for k in built])))
            self._drop_stale()
            for key, (vers, _, ids) in misses.items():
                found[key] = (on_device.get(key), ids)
                self._stacked[key] = (vers, on_device.get(key), ids)
            # ... and the capacity bound evicts the least recently used
            # live entries beyond it
            while len(self._stacked) > self._max_entries:
                self._stacked.popitem(last=False)
                self.evictions += 1
        return [(found[key][0], list(found[key][1])) for key in keys]

    def _drop_stale(self) -> None:
        """Evict version-stale stacks, so a long-running service's cache
        tracks the live support sets instead of accumulating dead
        padded stacks. An entry goes stale only when the repository
        moves, so the sweep runs once per repository version."""
        v = self._repo.global_version()
        if v == self._swept_at:
            return
        self._swept_at = v
        stale = [k for k, (vers, _, _) in self._stacked.items()
                 if vers != tuple(self._repo.version(z) for z in k[0])]
        for k in stale:
            del self._stacked[k]

    def invalidate(self, workload_id: Optional[str] = None) -> None:
        """Drop cached fits (one workload, or everything)."""
        if workload_id is None:
            self._cache.clear()
            self._stacked.clear()
        else:
            for k in [k for k in self._cache if k[0] == workload_id]:
                del self._cache[k]
            for k in [k for k in self._stacked if workload_id in k[0]]:
                del self._stacked[k]

    # -- process-shared stacks ----------------------------------------------
    def export_shared(self, workload_ids: Sequence[str],
                      measure: str) -> Optional["SharedStackHandle"]:
        """Pack one support stack into a shared-memory segment and
        return its picklable ``SharedStackHandle`` — the cross-process
        twin of ``get_stacked``, for deployments running one service
        worker per process against a single repository owner: the owner
        exports, the tiny handle crosses the pickle boundary (the same
        boundary ``ProcessPoolProfileExecutor`` already imposes), and
        each worker attaches to the one segment instead of re-fitting
        and re-stacking every support model per process.

        The owner keeps the segment alive (re-exporting the same key at
        the same versions reuses it); ``close_shared()`` unlinks all
        exported segments. Returns ``None`` when no workload of the set
        is usable (the same cases ``get_stacked`` returns ``None``)."""
        stack, ids = self.get_stacked(workload_ids, measure)
        if stack is None:
            return None
        key = (tuple(workload_ids), measure)
        vers = tuple(self._repo.version(z) for z in workload_ids)
        hit = self._shared.get(key)
        if hit is not None and hit[0] == vers:
            return hit[2]
        from multiprocessing import shared_memory
        arrays = [(f, np.asarray(getattr(stack, f)))
                  for f in _SHARED_STACK_FIELDS]
        total = sum(a.nbytes for _, a in arrays)
        seg = shared_memory.SharedMemory(create=True, size=max(total, 1))
        fields, off = [], 0
        for f, a in arrays:
            view = np.ndarray(a.shape, a.dtype, buffer=seg.buf, offset=off)
            view[...] = a
            fields.append((f, a.shape, a.dtype.str, off))
            off += a.nbytes
        handle = SharedStackHandle(seg.name, tuple(fields),
                                   float(stack.noise), tuple(ids), vers)
        if hit is not None:       # versions moved: retire the old segment
            hit[1].close()
            hit[1].unlink()
        self._shared[key] = (vers, seg, handle)
        return handle

    def close_shared(self) -> None:
        """Release every exported segment (owner-side lifecycle end)."""
        for _, seg, _ in self._shared.values():
            seg.close()
            seg.unlink()
        self._shared.clear()


# which BatchedGP fields ride the shared segment, in layout order (the
# full posterior/sample working set: a worker attaching the handle can
# serve every plan-layer query without touching the repository)
_SHARED_STACK_FIELDS = ("x", "y", "mask", "y_mean", "y_std",
                        "log_lengthscales", "log_signal", "chol", "alpha",
                        "counts")


@dataclasses.dataclass(frozen=True)
class SharedStackHandle:
    """Picklable description of one exported support stack: the segment
    name plus each field's (name, shape, dtype, byte offset) — no array
    payload crosses the boundary, only this metadata."""
    shm_name: str
    fields: Tuple[Tuple[str, Tuple[int, ...], str, int], ...]
    noise: float
    ids: Tuple[str, ...]
    versions: Tuple[int, ...]


def load_shared_stack(handle: SharedStackHandle):
    """Attach a ``SharedStackHandle`` and materialise its ``BatchedGP``.

    Arrays are COPIED out of the segment onto the worker's device:
    numpy views into ``shm.buf`` die with the mapping (and jax would
    copy host->device anyway), so attach-copy-close leaves no lifetime
    coupling between the worker's stack and the owner's segment.
    Returns ``(BatchedGP, ids)`` — the ``get_stacked`` result shape."""
    import jax.numpy as jnp
    from multiprocessing import shared_memory

    from .gp import BatchedGP
    seg = shared_memory.SharedMemory(name=handle.shm_name)
    try:
        parts = {}
        for f, shape, dtype, off in handle.fields:
            view = np.ndarray(shape, np.dtype(dtype), buffer=seg.buf,
                              offset=off)
            parts[f] = jnp.asarray(np.array(view, copy=True))
    finally:
        seg.close()
    return (BatchedGP(parts["x"], parts["y"], parts["mask"],
                      parts["y_mean"], parts["y_std"],
                      parts["log_lengthscales"], parts["log_signal"],
                      handle.noise, parts["chol"], parts["alpha"],
                      parts["counts"]),
            list(handle.ids))


class SharedSupportModelStore:
    """Worker-side ``SupportModelStore`` twin serving stacks from
    shared-memory handles instead of fitting models: the owner process
    exports (``SupportModelStore.export_shared``), hands the pickled
    handles over, and workers resolve ``get_stacked`` against them —
    one repository fit, N processes serving it.

    ``get_stacked`` is handle-version-cached like the owner's stack
    cache: re-publishing a handle for the same key with moved versions
    (the owner re-exported after ``add_run``) re-attaches; an identical
    handle serves the already-materialised stack."""

    def __init__(self, handles: Optional[Mapping[Tuple[Tuple[str, ...],
                                                       str],
                                                 SharedStackHandle]] = None):
        self._handles: Dict[Tuple[Tuple[str, ...], str],
                            SharedStackHandle] = dict(handles or {})
        self._attached: Dict[Tuple[Tuple[str, ...], str],
                             Tuple[Tuple[int, ...], object, list]] = {}
        self.hits = 0
        self.misses = 0

    def publish(self, workload_ids: Sequence[str], measure: str,
                handle: Optional[SharedStackHandle]) -> None:
        """Install (or clear, with ``None``) the handle for one key."""
        key = (tuple(workload_ids), measure)
        if handle is None:
            self._handles.pop(key, None)
            self._attached.pop(key, None)
        else:
            self._handles[key] = handle

    def get_stacked(self, workload_ids: Sequence[str], measure: str):
        key = (tuple(workload_ids), measure)
        handle = self._handles.get(key)
        if handle is None:
            return None, []
        hit = self._attached.get(key)
        if hit is not None and hit[0] == handle.versions:
            self.hits += 1
            return hit[1], list(hit[2])
        self.misses += 1
        stack, ids = load_shared_stack(handle)
        self._attached[key] = (handle.versions, stack, ids)
        return stack, list(ids)
