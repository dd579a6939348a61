"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout. Exits non-zero, printing no result, where
JAX finds no TPU or fewer chips than the cell asks for. Otherwise prints,
as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, every number compared with its limit,
which also close standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.resolve(args.workload, ROOT)
    import jax
    harness.configure_jax(ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX's devices are {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"{args.workload} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), root=ROOT, t_start=T_START)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
