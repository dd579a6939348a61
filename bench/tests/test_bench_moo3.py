"""The three-objective cell end to end on the CPU at a tiny cohort: a
sound traced run comes out correct and its EHVI launches carry three
objectives, the box readers return numbers, and the reference in one
bfloat16 pass or with uniform RGPE weights, each put in the program's
place on the same decisions, does not come out correct."""
import copy
import shutil

import pytest

from bench import check, harness

WORKLOAD = "moo3-64.saturated"
SEED = 2 ** 33 + 29
WINDOW_S = 5.0
SAMPLE = 24


def _cell():
    cell = harness.resolve(WORKLOAD)
    cfg = copy.deepcopy(cell.config)
    cfg.update(tenants=4)
    cfg["bo"]["max_iters"] = 8
    cell.config = cfg
    return cell


def _limits():
    return dict(check.limits_for(WORKLOAD), sample=SAMPLE,
                min_decisions_checked=8)


def _ok(r):
    return all(ok for *_, ok in check.judge(r, _limits()))


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    from bench.drive import Driver
    cell = _cell()
    rec = Driver(cell.config, cell.traffic, SEED, trace=True,
                 trace_root=str(tmp_path_factory.mktemp("trace"))
                 ).run(WINDOW_S)
    shutil.rmtree(rec.trace_dir, ignore_errors=True)
    replay = check.Replay(rec, cell.config, SEED, SAMPLE)
    return rec, replay


def test_sound_run_is_correct(sound):
    rec, replay = sound
    r = replay.readings()
    assert _ok(r), r
    assert rec.decisions and rec.unanswered == 0
    assert not rec.window_compiles


@pytest.mark.parametrize("variant", [check.LOWER["bf16"],
                                     check.FAULTS["rgpe_uniform"]],
                         ids=["bf16", "rgpe_uniform"])
def test_variant_in_the_programs_place_is_not_correct(sound, variant):
    _, replay = sound
    r = replay.readings(variant)
    assert not _ok(r), r


def test_ehvi_lanes_carry_three_objectives(sound):
    rec, _ = sound
    lanes = [lane for plan in rec.plan_work for bucket in plan["ehvi"]
             for lane in bucket]
    assert lanes and {n_obj for n_obj, *_ in lanes} == {3}
    # (n_obj, draws, live candidates, live boxes) of a scout tenant
    assert all(s == 64 and 0 < q <= 69 and k >= 1
               for _, s, q, k in lanes)


@pytest.mark.parametrize("name", ["boxes_ms_per_step.sat",
                                  "ehvi_box_fill_pct.sat"])
def test_box_readers_return_numbers(sound, name):
    rec, _ = sound
    ctx = harness.Context(rec, "cpu")
    value = harness._load_reader(harness.reader_path(name))(ctx)
    assert value is not None and value > 0
    if name.startswith("ehvi_box_fill"):
        assert value <= 100.0
