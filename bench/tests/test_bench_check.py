"""The harness end to end on the CPU at a tiny cohort, without its look
for a chip: a sound run comes out correct, the control (the reference in
float32 with its products at ``high``, put in the program's place) does
not, and neither does a run whose timed path is broken underneath."""
import copy

import numpy as np
import pytest

from bench import check, harness

SEED = 2 ** 33 + 17
WINDOW_S = 5.0


def _cell(workload):
    cell = harness.resolve(workload)
    cfg = copy.deepcopy(cell.config)
    cfg.update(tenants=4)
    cfg["bo"]["max_iters"] = 8
    cell.config = cfg
    cell.traffic = dict(cell.traffic)
    if "rate_per_s" in cell.traffic:
        cell.traffic["rate_per_s"] = 4.0
    return cell


def _limits(workload):
    return dict(check.limits_for(workload), sample=40,
                min_decisions_checked=8)


def _run(workload, service_cls=None):
    from bench.drive import Driver
    cell = _cell(workload)
    rec = Driver(cell.config, cell.traffic, SEED,
                 service_cls=service_cls).run(WINDOW_S)
    r = check.readings(rec, cell.config, SEED, 40)
    ok = all(ok for *_, ok in check.judge(r, _limits(workload)))
    return rec, r, ok


@pytest.fixture(scope="module")
def sound():
    return _run("so64.saturated")


def test_sound_run_is_correct(sound):
    rec, r, ok = sound
    assert ok, r
    assert rec.decisions and rec.unanswered == 0


def test_control_is_not_correct(sound):
    rec, _, _ = sound
    cell = _cell("so64.saturated")
    replay = check.Replay(rec, cell.config, SEED, 40)
    r = replay.readings(check.CONTROL["high"])
    assert not all(ok for *_, ok in check.judge(r, _limits(
        "so64.saturated"))), r


def _service(**overrides):
    from repro.serve.search_service import SearchService
    return type("Broken", (SearchService,), overrides)


def _unchanged(self, **kw):
    return 0


def _half_batch(self):
    ready = type(self).__mro__[1]._ready_sessions(self)
    return [(s, rem) for s, rem in ready if s.rid % 2 == 0]


def _unfitted(self, *args, **kw):
    # every fit runs no Adam step: hyperparameters stay at their start
    type(self).__mro__[1].__init__(self, *args, **kw, fit_steps=0,
                                   fit_warm_steps=None)


def _uniform_weights(self, rgpe_jobs):
    ws = type(self).__mro__[1]._score_weights(self, rgpe_jobs)
    return {i: np.full_like(np.asarray(w), 1.0 / np.size(w))
            for i, w in ws.items()}


def _altered(self, sessions):
    # every posterior row reversed over the candidates: each decision is
    # made on another configuration's model
    posts = type(self).__mro__[1]._posterior_phase(self, sessions)
    for s in sessions:
        for p in posts[s.rid].values():
            p["mu"], p["var"] = (np.asarray(p["mu"])[::-1],
                                 np.asarray(p["var"])[::-1])
    return posts


@pytest.mark.parametrize("fault", [
    {"step": _unchanged},                 # a step that leaves state as is
    {"_ready_sessions": _half_batch},     # half of the batch left out
    {"_posterior_phase": _altered},       # the answer altered at its source
    {"__init__": _unfitted},              # the fit leg left undone
    {"_score_weights": _uniform_weights},  # RGPE weights left uniform
], ids=["unchanged", "half_batch", "altered", "unfitted", "rgpe_uniform"])
def test_broken_timed_path_is_not_correct(sound, fault, monkeypatch):
    monkeypatch.setattr("bench.drive.DRAIN_LIMIT_S", 2.0)
    _, r, ok = _run("so64.saturated", _service(**fault))
    assert not ok, r
