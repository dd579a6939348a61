"""The serving layer's view of the query-plan layer.

The implementation lives in ``repro.core.plan`` (the drivers —
``run_search``, ``run_search_moo``, ``KarasuContext.score_ensembles`` —
route through it too, and ``core`` must not import up into ``serve``);
this module re-exports it under the serving namespace so
``SearchService`` and service-layer tooling have one canonical import
for the step lifecycle:

    collect  — every ready session emits query nodes (owner-tagged)
    plan     — ``StepPlanner.plan`` groups them into fused buckets and
               fixes every pad decision (the ONLY home of shape policy)
    execute  — ``PlanExecutor.execute`` runs one launch per bucket
    scatter  — results return in query order / callable owners fire

See ``repro.core.plan`` for the node table and the exact-padding
contract.
"""
from repro.core.plan import (GRID_ROUND_TO, M_ROUND_POW2, OBS_ROUND_TO,
                             Bucket, CohortLimits, EhviQuery, FitQuery,
                             LooSampleQuery, PlanExecutor,
                             PosteriorDrawQuery, PosteriorQuery,
                             SampleQuery, StepPlan, StepPlanner, row_pads)

__all__ = [
    "OBS_ROUND_TO", "GRID_ROUND_TO", "M_ROUND_POW2",
    "Bucket", "CohortLimits", "StepPlan", "StepPlanner", "PlanExecutor",
    "PosteriorQuery", "SampleQuery", "LooSampleQuery",
    "PosteriorDrawQuery", "EhviQuery", "FitQuery", "row_pads",
]
