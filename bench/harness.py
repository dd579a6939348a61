"""The data-driven harness: a cell, its configuration, its traffic mix and
its metrics are all found by name from ``BENCHMARK.json``.

- a configuration is ``configs[i].file``, a JSON file of the deployment;
- a traffic mix ``<traffic>`` is ``bench/traffic/<traffic>.json``, read by
  the one generator in ``executor.py``;
- a metric ``<name>`` is read by ``bench/metrics/<name>.py`` or, where no
  such file exists, by the reader of its stem (the name up to its first
  '.'): a module with ``read(ctx) -> float | None``; ``None`` leaves the
  metric out of the result line;
- a cell's limits for ``correct`` are ``bench/limits/<workload>.json``.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
import shutil
import sys
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _applies(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(workload: str, root: str = ROOT) -> Cell:
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     f"{w['traffic']}.json"))
    return Cell(workload, int(w["chips"]), config, traffic,
                [m for m in spec["end_to_end"] if _applies(m, workload)],
                [m for m in spec["per_layer"] if _applies(m, workload)])


def reader_path(name: str, root: str = ROOT) -> str:
    base = os.path.join(root, "bench", "metrics")
    for stem in (name, name.split(".")[0]):
        path = os.path.join(base, f"{stem}.py")
        if os.path.exists(path):
            return path
    raise FileNotFoundError(f"no reader for metric {name!r} in {base}")


@functools.lru_cache(maxsize=None)
def _load_reader(path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + os.path.basename(path)[:-3].replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Context:
    """What a reader may read: the run's record and (in a traced run)
    the reduced trace, loaded once."""

    def __init__(self, record, device_kind: str):
        self.record = record
        self.device_kind = device_kind
        self._trace = None

    @property
    def trace(self):
        if self._trace is None:
            from bench import trace as tr
            self._trace = tr.load(tr.find_xplane(self.record.trace_dir),
                                  ("step", "plan", "execute."))
        return self._trace

    def window_ns(self):
        from bench import trace as tr
        return tr.window(self.trace)

    @functools.cached_property
    def peaks(self) -> Dict:
        table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
        if self.device_kind not in table:
            raise KeyError(f"device {self.device_kind!r} is not in "
                           f"bench/peaks.json")
        return table[self.device_kind]


def read_metrics(ctx: Context, metrics: List[Dict]) -> Dict[str, Dict]:
    out = {}
    for m in metrics:
        value = _load_reader(reader_path(m["name"]))(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown(ctx: Context) -> Dict:
    from bench import trace as tr
    lo, hi = ctx.window_ns()
    return {"device_ops": tr.top(tr.op_times(ctx.trace, lo, hi)),
            "idle_gaps": tr.top(tr.idle_gaps(ctx.trace, lo, hi))}


def device_info(record, trace_ctx: Optional[Context] = None) -> Dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": record.memory_peak_bytes}
    if trace_ctx is not None:
        from bench import trace as tr
        lo, hi = trace_ctx.window_ns()
        info["busy_s"] = tr.busy_ns(trace_ctx.trace, lo, hi) * 1e-9
        info["window_s"] = (hi - lo) * 1e-9
    return info


def configure_jax(root: str) -> str:
    """JAX's persistent compilation cache at one fixed path inside the
    checkout, every program kept (the service's launches and the small
    programs around them all compile in well under a second)."""
    import jax
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, t_start: Optional[float] = None,
             limits: Optional[Dict] = None, service_cls=None) -> Dict:
    """One run of one cell; returns the result line's object."""
    import jax
    from bench import check
    from bench.drive import Driver
    out_dir = os.path.join(root, "bench", ".out")
    os.makedirs(out_dir, exist_ok=True)
    driver = Driver(cell.config, cell.traffic, seed, trace=trace,
                    trace_root=out_dir, service_cls=service_cls,
                    t_start=t_start)
    record = driver.run(seconds)
    del driver                       # the program's state goes here
    kind = jax.devices()[0].device_kind
    ctx = Context(record, kind)
    try:
        if trace:
            metrics = read_metrics(ctx, cell.per_layer)
            result_bd = breakdown(ctx)
            device = device_info(record, ctx)
        else:
            metrics = read_metrics(ctx, cell.end_to_end)
            result_bd = None
            device = device_info(record)
    finally:
        if record.trace_dir:
            shutil.rmtree(record.trace_dir, ignore_errors=True)
    limits = limits or check.limits_for(cell.name)
    r = check.readings(record, cell.config, seed, int(limits["sample"]))
    verdict = check.judge(r, limits)
    result = {"correct": all(ok for *_, ok in verdict),
              "attempted": record.attempted, "failed": record.unanswered,
              "metrics": metrics, "device": device}
    if result_bd is not None:
        result["breakdown"] = result_bd
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim, _ in verdict}
    for k in ("regret_max", "fit_nlml_gap"):
        result["_" + k] = r[k]
    result["_window_compiles"] = record.window_compiles[:20]
    return result


def emit(result: Dict) -> None:
    """The check lines last on standard error, the result line last on
    standard output ('checks' its last key)."""
    extra = {k: result.pop(k) for k in list(result) if k.startswith("_")}
    for k, v in extra.items():
        print(f"{k[1:]}: {v}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    checks = result.pop("checks")
    result["checks"] = checks
    print(json.dumps(result), flush=True)
