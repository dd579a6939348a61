"""The per-layer metrics that read the program's own spans
(``bench/metrics/_program.py``): the six ``BENCHMARK.json`` entries as
they are declared, the ``*_ms_per_step`` readers on a run record, the
unattributed idle share on a hand-made trace with known gaps inside and
outside the step's child spans, and ``None`` — never an error — from a
program that has no such spans."""
import os
import types

import pytest

from bench import harness
from bench import trace as tr
from bench.metrics import _program

SPEC = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
CELLS = ["so64.saturated", "moo64.saturated"]
NEW = {
    "select_ms_per_step.sat": ("ms", "program_span", "serve/search_service",
                               ("select",)),
    "acquire_ms_per_step.sat": ("ms", "program_span",
                                "serve/search_service", ("acquire",)),
    "regroup_ms_per_step.sat": ("ms", "program_span",
                                "serve/search_service",
                                ("fit.collect", "fit.cache", "regroup")),
    "pack_ms_per_step.sat": ("ms", "program_span", "core/plan", ("pack",)),
    "scatter_ms_per_step.sat": ("ms", "program_span", "core/plan",
                                ("unpack", "scatter")),
    "idle_unattributed_pct.sat": ("%", "device_trace", "device", None),
}
MS = [n for n, v in NEW.items() if v[3] is not None]


def _read(name, ctx):
    return harness._load_reader(harness.reader_path(name))(ctx)


def _ctx(stats_delta, steps=4, trace_dir=None, window=(0, 1000)):
    record = types.SimpleNamespace(stats_delta=stats_delta, steps=steps,
                                   trace_dir=trace_dir)
    return types.SimpleNamespace(record=record, window_ns=lambda: window)


@pytest.mark.parametrize("name", sorted(NEW))
def test_entry_as_declared(name):
    entries = [m for m in SPEC["per_layer"] if m["name"] == name]
    assert len(entries) == 1
    m = entries[0]
    unit, source, layer, _ = NEW[name]
    assert m == {"name": name, "unit": unit, "better": "lower",
                 "source": source, "layer": layer,
                 "moves": "decisions_per_s", "workloads": CELLS}
    assert os.path.exists(harness.reader_path(name))


@pytest.mark.parametrize("name", MS)
def test_ms_reader_sums_its_spans_per_step(name):
    names = NEW[name][3]
    delta = {f"span_s.{n}": 0.5 * (i + 1) for i, n in enumerate(names)}
    delta["span_s.elsewhere"] = 9.0
    delta["plan_batches"] = 16
    want = sum(delta[f"span_s.{n}"] for n in names) * 1e3 / 4
    assert _read(name, _ctx(delta)) == pytest.approx(want)


@pytest.mark.parametrize("name", MS)
def test_ms_reader_without_program_spans_is_none(name):
    assert _read(name, _ctx({"plan_batches": 16})) is None
    assert _read(name, _ctx({f"span_s.{NEW[name][3][0]}": 1.0},
                            steps=0)) is None


def _hand_trace(with_program_spans=True):
    # window 0-1000; device busy 100-200 and 600-700; the step 0-900
    # holds select 0-300 and acquire 400-800, with a pack 450-500 nested
    # in acquire: idle inside the step is 0-100, 200-600 and 700-900
    # (700 ns), of which 300-400 and 800-900 (200 ns) have no child open
    spans = [("step", 0, 950)]
    if with_program_spans:
        spans += [("karasu.step", 0, 900), ("karasu.select", 0, 300),
                  ("karasu.acquire", 400, 800), ("karasu.pack", 450, 500)]
    return tr.Trace(ops=[("fusion", 100, 200), ("dot", 600, 700)],
                    modules=[], spans=sorted(spans, key=lambda t: t[1]),
                    devices=1)


def test_idle_by_span_and_unattributed_share():
    gaps = _program.idle_by_span(_hand_trace(), 0, 1000)
    assert gaps == pytest.approx({"karasu.step": 200e-9,
                                  "karasu.select": 200e-9,
                                  "karasu.acquire": 250e-9,
                                  "karasu.pack": 50e-9})
    assert _program.unattributed_pct(gaps) == pytest.approx(100 * 2 / 7)


def test_idle_reader_on_a_trace(monkeypatch, tmp_path):
    open(tmp_path / "t.xplane.pb", "wb").close()
    ctx = _ctx({}, trace_dir=str(tmp_path))
    monkeypatch.setattr(_program, "_load", lambda path: _hand_trace())
    assert _read("idle_unattributed_pct.sat", ctx) == \
        pytest.approx(100 * 2 / 7)
    monkeypatch.setattr(_program, "_load",
                        lambda path: _hand_trace(False))
    assert _read("idle_unattributed_pct.sat", ctx) is None
