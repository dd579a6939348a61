"""Fig 7 (dataset cost/energy structure), Fig 8 (SOO vs MOO example),
Fig 9 (MOO with/without Karasu)."""
from __future__ import annotations

import numpy as np

from repro.core import (BOConfig, Constraint, Objective, run_search,
                        run_search_moo)
from repro.core.acquisition import _hv_2d, pareto_front

from . import common as C


def fig7():
    """Cost/energy correlation across the emulated dataset (paper: the
    two objectives correlate, tighter near their minima)."""
    timer = C.Timer()
    cost, energy, mtypes = [], [], []
    for wid in C.emulator().workload_ids():
        for cfg, m in C.emulator().full_table(wid):
            timer.calls += 1
            cost.append(m["cost"])
            energy.append(m["energy"])
            mtypes.append(cfg["machine_type"])
    cost, energy = np.array(cost), np.array(energy)
    C.emit("fig7_n_runs", timer.us_per_call(), len(cost))
    C.emit("fig7_corr_cost_energy", timer.us_per_call(),
           f"{np.corrcoef(cost, energy)[0, 1]:.3f}")
    # correlation in the cheapest quartile (paper: correlated near minimum)
    q = cost <= np.quantile(cost, 0.25)
    C.emit("fig7_corr_cheapest_quartile", timer.us_per_call(),
           f"{np.corrcoef(cost[q], energy[q])[0, 1]:.3f}")


def fig8_fig9():
    sc = C.scale()
    wid = C.bench_workloads()[0]
    pct = sc.percentiles[-1]
    rt = C.emulator().runtime_target(wid, pct)
    objs = [Objective("cost"), Objective("energy")]
    cons = [Constraint("runtime", rt)]
    timer = C.Timer()

    pool = C.build_same_workload_pool(wid, 3, iters=sc.max_iters)
    repo = C.repo_from_pool(pool, [0, 1, 2])

    # fig8: SOO vs MOO (both with Karasu, as in the paper's example)
    soo = run_search(C.space(), C.profile_fn(wid, 0), objs[0], cons,
                     method="karasu", repository=repo,
                     bo_config=BOConfig(max_iters=sc.max_iters), seed=0)
    moo = run_search_moo(C.space(), C.profile_fn(wid, 0), objs, cons,
                         method="karasu", repository=repo,
                         bo_config=BOConfig(max_iters=sc.max_iters),
                         seed=0, n_mc=32)
    timer.calls += len(soo.observations) + len(moo.observations)

    def best_pair(res):
        feas = [o for o in res.observations
                if o.measures["runtime"] <= rt] or res.observations
        bc = min(o.measures["cost"] for o in feas)
        be = min(o.measures["energy"] for o in feas)
        return bc, be

    sc_, se_ = best_pair(soo)
    mc_, me_ = best_pair(moo)
    C.emit("fig8_soo_best_cost", timer.us_per_call(), f"{sc_:.4f}")
    C.emit("fig8_soo_best_energy", timer.us_per_call(), f"{se_:.5f}")
    C.emit("fig8_moo_best_cost", timer.us_per_call(), f"{mc_:.4f}")
    C.emit("fig8_moo_best_energy", timer.us_per_call(), f"{me_:.5f}")

    # fig9: MOO naive vs karasu — final dominated hypervolume (higher
    # is better) + cost of best feasible config
    hv = {}
    for method, kwargs in [("naive", {}),
                           ("karasu", {"repository": repo})]:
        hvs, costs = [], []
        for rep in range(max(1, sc.reps // 2)):
            res = run_search_moo(C.space(), C.profile_fn(wid, rep), objs,
                                 cons, method=method,
                                 bo_config=BOConfig(max_iters=sc.max_iters),
                                 seed=rep, n_mc=32, **kwargs)
            timer.calls += len(res.observations)
            pts = np.array([[o.measures["cost"], o.measures["energy"]]
                            for o in res.observations
                            if o.measures["runtime"] <= rt])
            if len(pts) == 0:
                continue
            ref = np.array([2.0, 0.3])  # fixed ref above all observations
            hvs.append(_hv_2d(pareto_front(pts), ref))
            costs.append(pts[:, 0].min())
        hv[method] = (np.mean(hvs) if hvs else np.nan,
                      np.mean(costs) if costs else np.nan)
        C.emit(f"fig9_moo_{method}_hypervolume", timer.us_per_call(),
               f"{hv[method][0]:.5f}")
        C.emit(f"fig9_moo_{method}_best_cost", timer.us_per_call(),
               f"{hv[method][1]:.4f}")


def main():
    fig7()
    fig8_fig9()


if __name__ == "__main__":
    from repro.launch.compile_stats import use_compile_cache
    use_compile_cache()
    main()
