"""Benchmark entry point: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Scale with REPRO_BENCH_SCALE
(ci | full; see common.py).
"""
from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from . import bench_analysis, bench_kernels, bench_roofline
    from . import bench_search_service
    from . import bench_fig3_fig4, bench_fig5_fig6, bench_fig7_fig8_fig9

    print("name,us_per_call,derived")
    t0 = time.time()
    failed = []
    for mod in [bench_roofline, bench_analysis, bench_kernels,
                bench_search_service,
                bench_fig7_fig8_fig9, bench_fig3_fig4, bench_fig5_fig6]:
        try:
            mod.main()
        except Exception as e:  # run the rest, then exit non-zero below
            traceback.print_exc()
            print(f"{mod.__name__.split('.')[-1]}_error,0.0,"
                  f"{type(e).__name__}")
            failed.append(mod.__name__)
    print(f"total_bench_wall_s,0.0,{time.time() - t0:.1f}")
    if failed:
        sys.exit(f"benchmark modules failed: {', '.join(failed)}")


if __name__ == "__main__":
    from repro.launch.compile_stats import use_compile_cache
    use_compile_cache()
    main()
