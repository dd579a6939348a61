"""Fig 3 (general performance boost) + Fig 4 (early stopping).

Scenario: support models from the SAME workload, different runtime
targets/initialisations (paper: near-optimal case). Compares NaiveBO,
AugmentedBO, NaiveBO+Karasu with 1 and 3 support models.

Paper claims checked (reported as `derived` values):
  - fig3: % of cases within 25% of optimal cost by profiling run 2
          (paper: 88.4-90.2% Karasu vs 33.0% NaiveBO)
  - fig3: % of cases at the optimum by run 5 (paper: 21.4-26.3% vs 5.8%)
  - fig4: with the CherryPick stopping rule — search time, search cost,
          final cost ratio, timeout fraction
"""
from __future__ import annotations

import numpy as np

from repro.core import BOConfig, Constraint, Objective, run_search

from . import common as C


def _experiments():
    sc = C.scale()
    for wid in C.bench_workloads():
        pool = C.build_same_workload_pool(wid, 4, iters=sc.max_iters)
        for pct in sc.percentiles:
            rt = C.emulator().runtime_target(wid, pct)
            opt = C.emulator().optimal_cost(wid, rt)
            for rep in range(sc.reps):
                yield wid, pool, pct, rt, opt, rep


def run(early_stop: bool = False):
    sc = C.scale()
    methods = ["naive", "augmented", "karasu1", "karasu3"]
    traj: dict = {m: [] for m in methods}
    stats: dict = {m: {"time": [], "cost": [], "final": [], "timeout": [],
                       "runs": []} for m in methods}
    timer = C.Timer()

    for wid, pool, pct, rt, opt, rep in _experiments():
        for m in methods:
            seed = rep * 17 + pct
            kwargs = {}
            if m.startswith("karasu"):
                nm = int(m[-1])
                which = list(np.random.default_rng(seed).choice(
                    len(pool), nm, replace=False))
                kwargs = {"repository": C.repo_from_pool(pool, which),
                          "method": "karasu"}
            else:
                kwargs = {"method": m}
            # Karasu needs only ONE initial run (support models carry the
            # prior; fig. 3 diverges from run 2), baselines use 3 (§IV-B)
            n_init = 1 if m.startswith("karasu") else 3
            res = run_search(
                C.space(), C.profile_fn(wid, seed), Objective("cost"),
                [Constraint("runtime", rt)],
                bo_config=BOConfig(max_iters=sc.max_iters,
                                   early_stop=early_stop, n_init=n_init,
                                   n_support=3), seed=seed, **kwargs)
            timer.calls += len(res.observations)
            traj[m].append(C.regret_trajectory(res, wid, opt))
            st = stats[m]
            rts = res.measures_array("runtime")
            st["time"].append(float(rts.sum()))
            st["cost"].append(float(res.measures_array("cost").sum()))
            st["timeout"].append(float(np.mean(rts > rt)))
            st["runs"].append(len(res.observations))
            final = res.best_index_per_iter[-1]
            st["final"].append(
                C.noise_free_cost(wid, res.observations[final].config) / opt
                if final >= 0 else np.nan)
    return traj, stats, timer


def main():
    traj, stats, timer = run(early_stop=False)
    for m, t in traj.items():
        arr = np.array([r + [r[-1]] * (C.scale().max_iters - len(r))
                        for r in t])
        within25_at2 = float(np.nanmean(arr[:, 1] <= 1.25))
        at_opt_5 = float(np.nanmean(arr[:, min(4, arr.shape[1] - 1)]
                                    <= 1.02))
        C.emit(f"fig3_{m}_within25_run2", timer.us_per_call(),
               f"{within25_at2:.3f}")
        C.emit(f"fig3_{m}_atopt_run5", timer.us_per_call(),
               f"{at_opt_5:.3f}")
        C.emit(f"fig3_{m}_final_ratio", timer.us_per_call(),
               f"{np.nanmean(arr[:, -1]):.3f}")

    traj_es, stats_es, timer_es = run(early_stop=True)
    for m, st in stats_es.items():
        C.emit(f"fig4_{m}_search_time_s", timer_es.us_per_call(),
               f"{np.mean(st['time']):.1f}")
        C.emit(f"fig4_{m}_search_cost", timer_es.us_per_call(),
               f"{np.mean(st['cost']):.4f}")
        C.emit(f"fig4_{m}_final_ratio", timer_es.us_per_call(),
               f"{np.nanmean(st['final']):.3f}")
        C.emit(f"fig4_{m}_timeout_frac", timer_es.us_per_call(),
               f"{np.mean(st['timeout']):.3f}")
        C.emit(f"fig4_{m}_n_runs", timer_es.us_per_call(),
               f"{np.mean(st['runs']):.1f}")


if __name__ == "__main__":
    from repro.launch.compile_stats import use_compile_cache
    use_compile_cache()
    main()
