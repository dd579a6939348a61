"""The benchmark's own copy of the scout deployment's data (paper §IV-A).

A statistically faithful emulation of the scout dataset (18 HiBench /
spark-perf workloads on Hadoop 2.7 / Spark 1.5 / Spark 2.1 x 69 AWS
configurations): each workload has an Amdahl-type runtime surface

    T(mt, n) = serial + work * spill_penalty / (n * cores * speed)
             + shuffle * n^gamma / (8 * net_scale)

with per-workload coefficients drawn from per-algorithm hyperpriors,
multiplicative noise, cost from on-demand prices, energy from a linear
power model, and sar-style metrics compacted to quantiles (the paper's
``agg``). The search space (9 machine types x scale-outs, trimmed to 69)
and its 7-dimensional encoding are here too, for the reference.

Kept apart from the program's ``repro.simdata`` so that no program
change can move the benchmark's traffic; ``bench/tests`` checks that the
two still agree.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

# -- the search space ---------------------------------------------------------

# family -> (cores, mem_gb, io_scale, net_scale) of the '.large' size
AWS_FAMILIES: Dict[str, Tuple[int, float, float, float]] = {
    "c4": (2, 3.75, 1.0, 1.0),
    "m4": (2, 8.0, 1.0, 1.0),
    "r4": (2, 15.25, 1.0, 2.0),
}
AWS_SIZES = {"large": 1, "xlarge": 2, "2xlarge": 4}
MACHINE_TYPES = tuple(f"{fam}.{size}" for fam in AWS_FAMILIES
                      for size in AWS_SIZES)
NODE_COUNTS = (4, 6, 8, 10, 12, 16, 20, 24)


def machine_features(machine_type: str) -> Dict[str, float]:
    family, size = machine_type.split(".")
    cores, mem, io, net = AWS_FAMILIES[family]
    scale = AWS_SIZES[size]
    return {"cores": cores * scale, "mem_gb": mem * scale,
            "io_scale": io * scale, "net_scale": net * scale,
            "mem_per_core": mem / cores}


def space_configs() -> List[Dict]:
    """The 69 scout configurations: 9 machine types x 8 scale-outs, the
    three largest r4.2xlarge scale-outs absent."""
    configs = [{"machine_type": mt, "node_count": nc}
               for mt in MACHINE_TYPES for nc in NODE_COUNTS]
    configs = [c for c in configs
               if not (c["machine_type"] == "r4.2xlarge"
                       and c["node_count"] >= 20)]
    return configs[:69]


def encode(config: Mapping) -> np.ndarray:
    f = machine_features(str(config["machine_type"]))
    n = int(config["node_count"])
    return np.array([
        math.log2(n) / 6.0, math.log2(f["cores"]) / 5.0,
        math.log2(f["mem_gb"]) / 7.0, f["mem_per_core"] / 8.0,
        f["net_scale"] / 8.0, math.log2(f["cores"] * n) / 9.0,
        math.log2(f["mem_gb"] * n) / 11.0])


# -- prices, power, metric compaction ----------------------------------------

# AWS on-demand USD/hour, us-east-1, July 2023
USD_PER_HOUR = {"c4.large": 0.100, "c4.xlarge": 0.199, "c4.2xlarge": 0.398,
                "m4.large": 0.100, "m4.xlarge": 0.200, "m4.2xlarge": 0.400,
                "r4.large": 0.133, "r4.xlarge": 0.266, "r4.2xlarge": 0.532}
# (idle W, peak W) of the '.large' size per family
_LARGE_WATTS = {"c4": (6.0, 16.0), "m4": (7.0, 19.0), "r4": (8.5, 24.0)}
_SIZE_SCALE = {"large": 1.0, "xlarge": 2.0, "2xlarge": 4.0}


def energy_kwh(machine_type: str, node_count: int, runtime_s: float,
               cpu_util: float) -> float:
    family, size = machine_type.split(".")
    idle, peak = _LARGE_WATTS[family]
    u = min(max(cpu_util, 0.0), 1.0)
    watts = (idle + (peak - idle) * u) * _SIZE_SCALE[size]
    return watts * node_count * runtime_s / 3600.0 / 1000.0


def aggregate_metrics(raw: np.ndarray,
                      quantiles: Sequence[float] = (0.1, 0.5, 0.9)
                      ) -> np.ndarray:
    flat = np.asarray(raw, np.float64).reshape(raw.shape[0], -1)
    return np.quantile(flat, list(quantiles), axis=1).T.copy()


# -- workloads ----------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WorkloadSpec:
    workload_id: str
    framework: str
    algorithm: str
    dataset: str
    work: float
    serial: float
    shuffle: float
    gamma: float
    mem_demand: float
    cpu_frac: float
    noise: float


# algorithm hyperpriors: (work_mu, shuffle_mu, mem_mu, cpu_frac_mu)
_ALGO_PRIORS = {
    "pagerank": (9.5, 3.2, 5.0, 0.55), "terasort": (9.0, 4.0, 5.5, 0.35),
    "wordcount": (8.8, 2.0, 4.0, 0.65), "kmeans": (9.8, 2.5, 4.5, 0.80),
    "naive-bayes": (9.0, 2.2, 4.8, 0.70), "join": (9.2, 3.8, 5.2, 0.40),
    "regression": (9.6, 2.4, 4.2, 0.85), "als": (9.9, 3.0, 5.0, 0.75),
    "pca": (9.4, 2.8, 4.6, 0.78),
}

WORKLOADS: Tuple[Tuple[str, str, str], ...] = (
    ("hadoop2.7", "pagerank", "web-small"),
    ("hadoop2.7", "terasort", "tera-300g"),
    ("hadoop2.7", "wordcount", "wiki-50g"),
    ("hadoop2.7", "join", "tpch-100"),
    ("hadoop2.7", "naive-bayes", "news-20"),
    ("spark1.5", "pagerank", "web-small"),
    ("spark1.5", "terasort", "tera-300g"),
    ("spark1.5", "wordcount", "wiki-50g"),
    ("spark1.5", "kmeans", "points-100m"),
    ("spark1.5", "regression", "features-10m"),
    ("spark2.1", "pagerank", "web-large"),
    ("spark2.1", "terasort", "tera-1t"),
    ("spark2.1", "kmeans", "points-100m"),
    ("spark2.1", "kmeans", "points-1b"),
    ("spark2.1", "naive-bayes", "news-20"),
    ("spark2.1", "regression", "features-10m"),
    ("spark2.1", "als", "ratings-1b"),
    ("spark2.1", "pca", "features-10m"),
)

_FRAMEWORK_SPEED = {"hadoop2.7": 0.72, "spark1.5": 0.95, "spark2.1": 1.1}


def _seed_from(s: str) -> int:
    return int(hashlib.sha256(s.encode()).hexdigest()[:8], 16)


def make_workload(framework: str, algorithm: str,
                  dataset: str) -> WorkloadSpec:
    wid = f"{framework}/{algorithm}/{dataset}"
    rng = np.random.default_rng(_seed_from(wid))
    wmu, smu, mmu, cmu = _ALGO_PRIORS[algorithm]
    dscale = 1.0 + 1.5 * (rng.random() if "large" in dataset or "1b" in
                          dataset or "1t" in dataset else 0.0)
    return WorkloadSpec(
        workload_id=wid, framework=framework, algorithm=algorithm,
        dataset=dataset,
        work=float(np.exp(rng.normal(wmu, 0.25))) * dscale,
        serial=float(np.exp(rng.normal(3.6, 0.4))),
        shuffle=float(np.exp(rng.normal(smu, 0.3))) * dscale,
        gamma=float(rng.uniform(0.15, 0.55)),
        mem_demand=float(np.exp(rng.normal(mmu, 0.3))) * dscale,
        cpu_frac=float(np.clip(rng.normal(cmu, 0.08), 0.1, 0.95)),
        noise=float(rng.uniform(0.02, 0.06)))


class Emulator:
    """Black box: run(workload, config) -> (measures, agg metrics)."""

    def __init__(self):
        self.specs = {s.workload_id: s for s in
                      (make_workload(*w) for w in WORKLOADS)}
        self.configs = space_configs()

    def workload_ids(self) -> List[str]:
        return list(self.specs)

    def _runtime(self, w: WorkloadSpec, mt: str, n: int,
                 rng: Optional[np.random.Generator]):
        f = machine_features(mt)
        speed = _FRAMEWORK_SPEED[w.framework] * (0.9 + 0.05 * f["net_scale"])
        total_mem = f["mem_gb"] * n
        spill = max(0.0, w.mem_demand / total_mem - 1.0)
        spill_pen = 1.0 + (1.0 - w.cpu_frac) * 2.0 * spill + 0.6 * spill
        compute = w.work * spill_pen / (n * f["cores"] * speed)
        comm = w.shuffle * (n ** w.gamma) / (8.0 * f["net_scale"])
        t = w.serial + compute + comm
        if rng is not None:
            t *= float(np.exp(rng.normal(0.0, w.noise)))
        return t, {"compute": compute, "comm": comm, "spill": spill,
                   "total_mem": total_mem}

    def run(self, workload_id: str, config: Mapping,
            rng: Optional[np.random.Generator] = None):
        w = self.specs[workload_id]
        mt, n = str(config["machine_type"]), int(config["node_count"])
        t, parts = self._runtime(w, mt, n, rng)
        cpu_util = min(0.98, w.cpu_frac * parts["compute"] / max(t, 1e-9)
                       + 0.05)
        measures = {"runtime": t, "cost": t / 3600.0 * USD_PER_HOUR[mt] * n,
                    "energy": energy_kwh(mt, n, t, cpu_util)}
        return measures, self._metrics(w, parts, t, cpu_util, n, rng)

    def _metrics(self, w: WorkloadSpec, parts: Dict, t: float,
                 cpu_util: float, n: int,
                 rng: Optional[np.random.Generator]) -> np.ndarray:
        r = rng or np.random.default_rng(_seed_from(w.workload_id + "m"))
        spill = parts["spill"]
        means = np.array([
            100.0 * (1.0 - cpu_util),
            100.0 * min(0.97, w.mem_demand / parts["total_mem"]),
            100.0 * min(0.95, (1.0 - w.cpu_frac) * 0.5 + 0.4 * spill),
            100.0 * min(0.95, parts["comm"] / max(t, 1e-9) + 0.02),
            100.0 * min(0.9, 0.8 * spill),
            100.0 * max(0.05, 1.0 - 0.7 * spill)])
        spread = np.array([0.25, 0.08, 0.30, 0.35, 0.10, 0.12])
        samples = means[:, None] * (
            1.0 + spread[:, None] * r.standard_normal((6, 8 * max(n, 2))))
        return aggregate_metrics(np.clip(samples, 0.0, 100.0))

    def runtime_target(self, workload_id: str, percentile: float) -> float:
        ts = [self.run(workload_id, c)[0]["runtime"] for c in self.configs]
        return float(np.percentile(ts, percentile))
