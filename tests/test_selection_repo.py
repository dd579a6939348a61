"""Algorithm 1 selection + repository semantics."""
import numpy as np
import pytest

from repro.core import Repository, RunRecord, select_similar, select_similar_batched
from repro.core.selection import CandidateIndex
from repro.simdata import make_emulator


def _records(emu, shared_id, wid, n, seed, space):
    rng = np.random.default_rng(seed)
    out = []
    for ci in rng.choice(len(space), n, replace=False):
        out.append(emu.make_record(shared_id, wid, space.configs[ci], rng))
    return out


def test_selection_prefers_same_algorithm():
    emu = make_emulator()
    space = emu.space
    wids = emu.workload_ids()
    # target: spark2.1 kmeans; candidates: the same-algo twin + others
    target_id = "spark2.1/kmeans/points-100m"
    twin = "spark1.5/kmeans/points-100m"
    others = ["hadoop2.7/terasort/tera-300g", "spark2.1/als/ratings-1b"]
    target_runs = _records(emu, "t", target_id, 8, 0, space)
    candidates = {
        "twin": _records(emu, "twin", twin, 10, 1, space),
        "other1": _records(emu, "other1", others[0], 10, 2, space),
        "other2": _records(emu, "other2", others[1], 10, 3, space),
    }
    ranked = select_similar(target_runs, candidates, k=3)
    assert ranked[0][0] == "twin", ranked
    batched = select_similar_batched(target_runs, candidates, k=3)
    assert batched[0][0] == "twin", batched
    # both paths agree on scores
    d1 = dict(ranked); d2 = dict(batched)
    for z in d1:
        np.testing.assert_allclose(d1[z], d2[z], atol=1e-6)


@pytest.mark.parametrize("case", ["plain", "exclude", "empty_target"])
def test_query_many_matches_per_target_query(case):
    """``query_many`` scores ragged targets (1 to 8 runs) in one Pearson
    launch: each target's top-k is the one its own ``query`` gives, its
    scores are within 1e-6 of the faithful Algorithm-1 loop, a target's
    ``exclude`` drops only its own ids, and an empty target gets
    ``[]``."""
    emu = make_emulator()
    space = emu.space
    wids = emu.workload_ids()
    candidates = {f"c{j}": _records(emu, f"c{j}", wids[j], 9, 10 + j,
                                    space) for j in range(6)}
    targets = [_records(emu, "t", wids[(3 * t) % len(wids)], n, 40 + t,
                        space) for t, n in enumerate((1, 3, 5, 8))]
    excludes = [None] * len(targets)
    if case == "exclude":
        excludes = [None, ("c0", "c3"), None, ("c5",)]
    if case == "empty_target":
        targets.insert(2, [])
        excludes.insert(2, None)
    index = CandidateIndex(candidates)
    counters = {}
    many = index.query_many(targets, 4, exclude=excludes,
                            counters=counters)
    assert counters == {"launches": 1}
    assert len(many) == len(targets)
    for runs, excl, got in zip(targets, excludes, many):
        if not runs:
            assert got == []
            continue
        one = index.query(runs, 4, exclude=excl)
        assert [z for z, _ in got] == [z for z, _ in one]
        np.testing.assert_allclose([v for _, v in got],
                                   [v for _, v in one], atol=1e-6)
        full = dict(select_similar(runs, candidates, k=len(candidates)))
        for z, v in got:
            np.testing.assert_allclose(v, full[z], atol=1e-6)
        assert not set(excl or ()) & {z for z, _ in got}
        kept = [z for z, _ in sorted(full.items(), key=lambda t: -t[1])
                if z not in set(excl or ())]
        assert len(got) == min(4, len(kept))


def test_repository_roundtrip(tmp_path):
    emu = make_emulator()
    space = emu.space
    repo = Repository()
    repo.add_runs(_records(emu, "anon-1", emu.workload_ids()[0], 5, 0,
                           space))
    repo.add_runs(_records(emu, "anon-2", emu.workload_ids()[1], 4, 1,
                           space))
    path = str(tmp_path / "repo.json")
    repo.save(path)
    back = Repository.load(path)
    assert len(back) == 9
    assert set(back.workloads()) == {"anon-1", "anon-2"}
    r0 = repo.runs("anon-1")[0]
    b0 = back.runs("anon-1")[0]
    np.testing.assert_allclose(r0.metrics, b0.metrics)
    assert r0.measures["cost"] == b0.measures["cost"]


def test_repository_minimalism():
    """Shared records must not contain framework/algorithm/dataset tags."""
    emu = make_emulator()
    rec = emu.make_record("anon-1", emu.workload_ids()[0],
                          emu.space.configs[0])
    assert set(rec.config.keys()) == {"machine_type", "node_count"}
    assert rec.workload_id == "anon-1"   # opaque id only


def test_truncated_counts():
    emu = make_emulator()
    repo = Repository()
    repo.add_runs(_records(emu, "a", emu.workload_ids()[0], 10, 0,
                           emu.space))
    t = repo.truncated({"a": 4})
    assert len(t.runs("a")) == 4
