"""Static-analyzer wall time: per-rule and total lint cost. The lint
CLI gates CI, so its latency is a budget like any other — `us_per_call`
is the rule's wall time, `derived` its finding count."""
from __future__ import annotations

import time

from repro.analysis.lint import RULES, run_rule
from repro.analysis.findings import apply_suppressions

from . import common as C


def main():
    total = 0.0
    n_findings = 0
    for rule in RULES:
        t0 = time.perf_counter()
        findings = apply_suppressions(run_rule(rule))
        wall = time.perf_counter() - t0
        total += wall
        n_findings += len(findings)
        errors = sum(1 for f in findings if f.severity == "error")
        C.emit(f"lint_{rule.replace('-', '_')}", wall * 1e6,
               f"findings={len(findings)};errors={errors}")
    C.emit("lint_total", total * 1e6, f"findings={n_findings}")


if __name__ == "__main__":
    from repro.launch.compile_stats import use_compile_cache
    use_compile_cache()
    main()
