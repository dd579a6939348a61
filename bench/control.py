"""Readings for setting a cell's limits: the program's numbers on many
seeds, and on the first few of them those of the control and of the
planted faults, each put in the program's place on the same decisions.
One process, so set-up builds the window's programs once.

    python3 bench/control.py --workload <name> --seeds 11,12,13 \
        --seconds <window> [--variant-seeds 3] [--out <dir>]

Each seed runs the cell's cohort and a window of ``--seconds`` at the
cell's own load (only the first seed rehearses: the later ones reuse its
programs), then prints one JSON line: the program's readings
(``program``) and, on the first ``--variant-seeds`` seeds, those of the
control and the faults (``check.CONTROL``, ``check.LOWER``,
``check.FAULTS``). With ``--out`` it also writes, per seed, every
decision's regret and every model's NLML excess (``raw-<seed>.json``).
The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--variant-seeds", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from bench import check, harness
    from bench.drive import Driver
    cell = harness.resolve(args.workload, ROOT)
    import jax
    harness.configure_jax(ROOT)
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    if args.out:
        os.makedirs(args.out, exist_ok=True)
    sample = int(check.limits_for(args.workload)["sample"])
    variants = {**check.CONTROL, **check.LOWER, **check.FAULTS}
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        rec = Driver(cell.config, cell.traffic, seed,
                     rehearse=i == 0).run(args.seconds)
        replay = check.Replay(rec, cell.config, seed, sample)
        raw = {"program": replay.raw()}
        if i < args.variant_seeds:
            for name, v in variants.items():
                raw[name] = replay.raw(v)
        line = {"workload": args.workload, "seed": seed,
                "setup_s": rec.setup_s, "decisions": len(rec.decisions),
                "steps": rec.steps}
        line.update({k: check.summarise(r, rec.unanswered)
                     for k, r in raw.items()})
        print(json.dumps(line), flush=True)
        if args.out:
            with open(os.path.join(args.out, f"raw-{seed}.json"), "w") as f:
                json.dump(raw, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
