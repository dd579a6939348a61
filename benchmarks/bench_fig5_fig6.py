"""Fig 5 + Fig 6: collaborative applicability across data-availability
cases A-D, with Algorithm-1 selection and 3 support models; Fig 6 adds
the early-stopping variant and heterogeneous data amounts (hatched bars).
"""
from __future__ import annotations

import numpy as np

from repro.core import BOConfig, Constraint, Objective, run_search

from . import common as C

CASES = ["A", "B", "C", "D"]


def run(early_stop: bool, heterogeneous: bool):
    sc = C.scale()
    out = {c: {"final": [], "time": [], "cost": [], "timeout": []}
           for c in CASES + ["naive"]}
    timer = C.Timer()
    rng = np.random.default_rng(5)
    for wid in C.bench_workloads():
        pool = C.build_same_workload_pool(wid, 4, iters=sc.max_iters)
        for pct in sc.percentiles[:1] if early_stop else sc.percentiles:
            rt = C.emulator().runtime_target(wid, pct)
            opt = C.emulator().optimal_cost(wid, rt)
            for rep in range(max(1, sc.reps // 2)):
                seed = rep * 31 + pct

                def record(tag, res):
                    timer.calls += len(res.observations)
                    final = res.best_index_per_iter[-1]
                    o = out[tag]
                    o["final"].append(
                        C.noise_free_cost(
                            wid, res.observations[final].config) / opt
                        if final >= 0 else np.nan)
                    rts = res.measures_array("runtime")
                    o["time"].append(float(rts.sum()))
                    o["cost"].append(float(
                        res.measures_array("cost").sum()))
                    o["timeout"].append(float(np.mean(rts > rt)))

                res = run_search(
                    C.space(), C.profile_fn(wid, seed), Objective("cost"),
                    [Constraint("runtime", rt)], method="naive",
                    bo_config=BOConfig(max_iters=sc.max_iters,
                                       early_stop=early_stop), seed=seed)
                record("naive", res)
                for case in CASES:
                    repo = C.case_repo(wid, case, pool=pool,
                                       seed=seed + ord(case))
                    if heterogeneous:
                        counts = {z: int(rng.integers(3, 13))
                                  for z in repo.workloads()}
                        repo = repo.truncated(counts)
                    res = run_search(
                        C.space(), C.profile_fn(wid, seed),
                        Objective("cost"), [Constraint("runtime", rt)],
                        method="karasu", repository=repo,
                        bo_config=BOConfig(max_iters=sc.max_iters,
                                           early_stop=early_stop,
                                           n_init=1, n_support=3),
                        seed=seed)
                    record(case, res)
    return out, timer


def main():
    out, timer = run(early_stop=False, heterogeneous=False)
    for tag, st in out.items():
        C.emit(f"fig5_case{tag}_final_ratio", timer.us_per_call(),
               f"{np.nanmean(st['final']):.3f}")

    out_es, timer_es = run(early_stop=True, heterogeneous=False)
    for tag, st in out_es.items():
        C.emit(f"fig6_case{tag}_final_ratio", timer_es.us_per_call(),
               f"{np.nanmean(st['final']):.3f}")
        C.emit(f"fig6_case{tag}_search_time_s", timer_es.us_per_call(),
               f"{np.mean(st['time']):.1f}")
        C.emit(f"fig6_case{tag}_timeout_frac", timer_es.us_per_call(),
               f"{np.mean(st['timeout']):.3f}")

    out_h, timer_h = run(early_stop=True, heterogeneous=True)
    for tag, st in out_h.items():
        if tag == "naive":
            continue
        C.emit(f"fig6_hatched_case{tag}_final_ratio", timer_h.us_per_call(),
               f"{np.nanmean(st['final']):.3f}")


if __name__ == "__main__":
    from repro.launch.compile_stats import use_compile_cache
    use_compile_cache()
    main()
