"""BENCHMARK.json resolves, cell by cell, to the files the harness reads,
and every metric moves an end-to-end metric that its cells report."""
import json
import os
import re

import pytest

from bench import harness

ROOT = harness.ROOT
SPEC = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
WORKLOADS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves(workload):
    cell = harness.resolve(workload)
    assert cell.config["tenants"] >= 1 and cell.traffic["release"]
    for m in cell.end_to_end + cell.per_layer:
        assert os.path.exists(harness.reader_path(m["name"]))
    limits = json.load(open(os.path.join(ROOT, "bench", "limits",
                                         f"{workload}.json")))["limits"]
    assert 0 < limits["regret_share"] < 1
    assert 0 < limits["fit_gap_share"] < 1
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer


@pytest.mark.parametrize("workload", WORKLOADS)
def test_moves_names_a_reported_metric(workload):
    cell = harness.resolve(workload)
    reported = {m["name"] for m in cell.end_to_end}
    for m in cell.per_layer:
        assert m["moves"] in reported, (m["name"], workload)


def test_names_units_and_bounds():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = ([m["name"] for m in metrics] + WORKLOADS
             + [c["name"] for c in SPEC["configs"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        for w in m.get("workloads", ()):
            assert w in WORKLOADS
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
