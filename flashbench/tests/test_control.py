"""The control of the correctness check (the reference with int16 counts
in the program's place) comes out not correct, and the faults planted in
the timed path underneath a run make ``correct`` false."""
import numpy as np
import pytest

import control
import tiny
from repro.core import table_jax as tj

INGEST = ["wiki-mdbl.ingest", "meme-mb.ingest"]
LOOKUP = ["meme-mb.lookup"]


@pytest.mark.parametrize("name", INGEST + LOOKUP)
def test_control_is_not_correct(name):
    out = tiny.run(name, answers=control.control_answers)
    assert not out["correct"]
    assert out["checks"]["mismatches"]["value"] > 0


def _patch_update(monkeypatch, fault):
    """Break the store's update program on the window's chunk shape
    (the pre-load's larger chunks pass through untouched). "Half of the
    batch left out" drops every second entry of a dispatch, and of a
    lookup chunk's answers, so that it hits real entries wherever the
    padding sits."""
    real = tj.update
    chunk = tiny.TABLE["chunk"]

    def broken(cfg, state, keys, deltas):
        if keys.shape[0] != chunk:
            return real(cfg, state, keys, deltas)
        return fault(real, cfg, state, keys, deltas)
    monkeypatch.setattr(tj, "update", broken)


def _patch_lookup(monkeypatch, fault):
    real = tj.lookup_ex

    def broken(cfg, state, q):
        cnt, dist, tiles = real(cfg, state, q)
        return fault(cnt), dist, tiles
    monkeypatch.setattr(tj, "lookup_ex", broken)


UPDATE_FAULTS = {
    "state_unchanged": lambda real, cfg, st, k, d: st,
    "half_batch_left_out": lambda real, cfg, st, k, d: real(
        cfg, st, k.at[1::2].set(tj.EMPTY), d),
    "token_altered": lambda real, cfg, st, k, d: real(
        cfg, st, k, d.at[0].add(1)),
}
ANSWER_FAULTS = {
    "half_batch_left_out": lambda c: c.at[1::2].set(0),
    "answer_altered": lambda c: c.at[0].add(1),
}


@pytest.mark.parametrize("fault", sorted(UPDATE_FAULTS))
@pytest.mark.parametrize("name", INGEST)
def test_update_fault_is_caught(monkeypatch, name, fault):
    _patch_update(monkeypatch, UPDATE_FAULTS[fault])
    out = tiny.run(name)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", sorted(ANSWER_FAULTS))
@pytest.mark.parametrize("name", INGEST + LOOKUP)
def test_answer_fault_is_caught(monkeypatch, name, fault):
    _patch_lookup(monkeypatch, ANSWER_FAULTS[fault])
    out = tiny.run(name)
    assert not out["correct"], out["checks"]


def test_control_differs_only_where_counts_exceed_int16():
    import reference
    ref = reference.Reference(np.array([5, 40000, 70000, 1]))
    ranks = np.arange(4)
    assert (ref.counts(ranks) == [5, 40000, 70000, 1]).all()
    assert reference.mismatches(ref.counts(ranks, control.CONTROL_DTYPE),
                                ref.counts(ranks)) == 2
