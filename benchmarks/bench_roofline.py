"""Roofline table benchmark: per (arch x shape x mesh) cell, read the
dry-run artifact and emit the three terms + projected MFU (the §Roofline
deliverable). `us_per_call` is the dominant roofline term (the projected
step time bound) in microseconds."""
from __future__ import annotations

import os

from repro.launch.roofline import load_artifacts, roofline_from_artifact

from . import common as C

ART_DIR = os.environ.get("REPRO_DRYRUN_DIR", "artifacts/dryrun")


def main():
    arts = load_artifacts(ART_DIR)
    if not arts:
        C.emit("roofline_missing_artifacts", 0.0,
               f"run: python -m repro.launch.dryrun --all --out {ART_DIR}")
        return
    n_ok = 0
    for a in arts:
        r = roofline_from_artifact(a)
        name = f"roofline_{a.get('arch')}_{a.get('shape')}_{a.get('mesh')}"
        if r is None:
            C.emit(name, 0.0, f"status={a.get('status')}")
            continue
        n_ok += 1
        bound_us = max(r.compute_s, r.memory_s, r.collective_s) * 1e6
        C.emit(name, bound_us,
               f"MFU={r.projected_mfu:.3f};dom={r.dominant};"
               f"useful={r.useful_ratio:.2f};hbm={r.hbm_gib:.1f}GiB;"
               f"fits={'y' if r.fits_hbm else 'N'}")
    C.emit("roofline_cells_ok", 0.0, n_ok)


if __name__ == "__main__":
    from repro.launch.compile_stats import use_compile_cache
    use_compile_cache()
    main()
