"""The traffic generator is a pure function of the seed, and every seed
gets the same work in another order."""

import numpy as np
import pytest

from _bench_path import cell as make_cell

MODELS = ["tableV-3x3", "tableV-2x2"]


def _traffic(mix_cell: str, seed: int, seconds: float = 10.0):
    cell = make_cell(*mix_cell.split("."))
    return cell.generator().build(cell.mix, cell.cfg["models"], seed, seconds)


@pytest.mark.parametrize("cell", ["tablev-2res-fused.backlog", "tablev-2res-fused.poisson",
                                  "tablev-3x3-fabric.backlog"])
def test_same_seed_same_sessions(cell):
    a, b = _traffic(cell, 2**31 + 17), _traffic(cell, 2**31 + 17)
    assert [a.session(i) for i in range(300)] == [b.session(i) for i in range(300)]
    if a.due is not None:
        np.testing.assert_array_equal(a.due, b.due)


def test_seeds_permute_one_universe():
    a, b = _traffic("tablev-2res-fused.backlog", 1), _traffic("tablev-2res-fused.backlog", 2**31 + 9)
    n = 7 * len(a.block)
    sa = [a.session(i) for i in range(n)]
    sb = [b.session(i) for i in range(n)]
    assert [s["stream_id"] for s in sa] != [s["stream_id"] for s in sb]
    key = lambda s: (s["stream_id"], s["label"], s["model"], tuple(sorted(s["stream"].items())))
    assert sorted(map(key, sa)) == sorted(map(key, sb))
    # every block is balanced: each suit, model and event rate equally often
    for blk in range(7):
        part = sa[blk * len(a.block):(blk + 1) * len(a.block)]
        assert sorted((s["label"], s["model"], s["stream"]["events_per_step"]) for s in part) \
            == sorted(a.block)
    labels = np.bincount([s["label"] for s in sa], minlength=4)
    assert labels.min() == labels.max()
    models = [s["model"] for s in sa]
    assert models.count("tableV-3x3") == models.count("tableV-2x2")
    rates = np.unique([s["stream"]["events_per_step"] for s in sa], return_counts=True)
    assert rates[0].tolist() == a.mix["events_per_step"] and rates[1].min() == rates[1].max()


def test_poisson_gaps_are_one_set_in_another_order():
    a = _traffic("tablev-2res-fused.poisson", 5)
    b = _traffic("tablev-2res-fused.poisson", 6)
    ga, gb = np.diff(a.due), np.diff(b.due)
    assert not np.array_equal(ga, gb)
    # the same gaps (one of each set opens the schedule)
    assert len(set(np.round(ga, 12)) ^ set(np.round(gb, 12))) <= 2
    lead = a.mix["lead_s"]
    assert a.due[0] == pytest.approx(-lead)
    rate = a.mix["rate_per_s"]
    # the set offers the stated rate (one gap of it opens the schedule)
    assert np.mean(ga) == pytest.approx(1.0 / rate, rel=0.01)


def test_a_session_model_must_be_resident():
    cell = make_cell("tablev-2res-fused", "backlog")
    with pytest.raises(ValueError):
        cell.generator().build(cell.mix, ["elsewhere"], 1, 1.0)
