"""Tests of the measured window's length and of what ``step_ms_p95`` stands
on, on the CPU at the rehearsal size: the stop rule (``--seconds``, the
traffic's ``min_steps``, a traced run's ``trace_steps``), the pool of batches
(one traced body, the parent's batches bit for bit, none twice), the
estimator's position arithmetic, and the floor of samples under a whole run's
percentile.  No speed is read here."""
import importlib.util
import json
import os
import statistics

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "chipbench")
SDAR, BERT = "sdar_30b_a3b_bd4k", "bert_base_t512"


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "window_test_" + name, os.path.join(BENCH, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


bench = _load("run")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def _never(workload, n):
    raise AssertionError("the floor of samples was asked about %s (%d): only "
                         "a whole run on the chip may ask" % (workload, n))


# ------------------------------------------------------------ the stop rule
def test_only_the_long_stepped_cell_asks_for_steps():
    """``min_steps`` is this cell's: the four cells PR 29 found steady keep
    their files, and their windows end at ``--seconds`` as they did.  (A
    cell that a later PR adds may set the key; nothing here forbids it.)"""
    traffic = bench.load_json("traffic", "bd4k_b2.json")
    assert traffic["min_steps"] == 100 and traffic["_note"]
    # the followed, the warm-up and the window's steps each draw a batch of
    # their own
    assert traffic["pool"] >= traffic["reference"]["steps"] \
        + traffic["warmup_steps"] + traffic["min_steps"]
    for name in ("t512_b64", "img224_b256", "t128_b256", "t512_b256_dp4"):
        steady = bench.load_json("traffic", name + ".json")
        assert "min_steps" not in steady
        assert "min_steps" not in steady["rehearse"]


def test_window_runs_on_until_the_cells_steps_are_done(monkeypatch):
    monkeypatch.setattr(bench, "enough_samples", _never)
    traffic = bench.load_cell(SDAR, rehearse=True)[3]
    assert (traffic["min_steps"], traffic["pool"]) == (6, 8)
    # 2 followed + 2 warm-up + 6 > 8: the window wraps around the pool
    result = bench.run_cell(SDAR, 3_000_000_019, 0.01, 0, rehearse=True)
    assert result["attempted"] == result["step_samples"] == 6
    assert result["correct"] is True, result["numbers"]
    assert result["window_s"] > 0.01


def test_window_without_the_key_ends_at_the_first_fetch_past_seconds(
        monkeypatch):
    monkeypatch.setattr(bench, "enough_samples", _never)
    result = bench.run_cell(BERT, 3_000_000_021, 0.01, 0, rehearse=True)
    assert result["attempted"] == result["step_samples"] == 1


@pytest.mark.parametrize("seconds,steps", [(3600.0, 3), (0.01, 1)])
def test_traced_run_stops_at_trace_steps_whatever_min_steps_says(
        monkeypatch, seconds, steps):
    """``trace_steps`` (3 at the rehearsal size) ends a traced run, and so
    does ``--seconds``, as before; ``min_steps`` (6) does not hold it."""
    monkeypatch.setattr(bench, "enough_samples", _never)
    result = bench.run_cell(SDAR, 7, seconds, 1, rehearse=True)
    assert result["attempted"] == result["step_samples"] == steps
    assert result["metrics"] == {}   # no device metric from a CPU


# ------------------------------------------------------- the pool of batches
def _parents_pool(cell, seed):
    """``make_all``'s batches as the parent of PR 29 made them: one
    ``make_batch`` a batch, unrolled."""
    b, cfg, traffic = cell.builder, cell.cfg, cell.traffic
    return jax.jit(lambda key: [
        b.make_batch(cfg, traffic, jax.random.fold_in(key, i))
        for i in range(1, traffic["pool"] + 1)])(bench.seed_key(seed))


def _leaves(batch):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(batch)]


@pytest.mark.parametrize("cell_name", CELLS)
def test_pool_is_the_parents_batches_bit_for_bit(cell_name):
    cell = bench.Cell(cell_name, rehearse=True)
    seed = 2_147_491_907
    _weights, pool = cell.make(seed)
    want = _parents_pool(cell, seed)
    assert len(pool) == len(want) == cell.traffic["pool"]
    for got, ref in zip(pool, want):
        assert jax.tree_util.tree_structure(got) \
            == jax.tree_util.tree_structure(ref)
        for a, r in zip(_leaves(got), _leaves(ref)):
            assert a.dtype == r.dtype and a.shape == r.shape
            assert a.tobytes() == r.tobytes()


def test_pool_of_128_starts_with_the_parents_four_and_repeats_none():
    """At the cell's own batch shape (2 x 4,096; the weights are the
    rehearsal's, the batches do not depend on them)."""
    cell = bench.Cell(SDAR, rehearse=True)
    cell.traffic = bench.load_json("traffic", "bd4k_b2.json")
    assert cell.traffic["pool"] == 128
    seed = 2_147_491_907
    _weights, pool = cell.make(seed)
    assert len(pool) == 128
    cell.traffic = dict(cell.traffic, pool=4)       # the parent's file
    for got, ref in zip(pool, _parents_pool(cell, seed)):
        for a, r in zip(_leaves(got), _leaves(ref)):
            assert a.shape[:2] == (2, 4096) and a.tobytes() == r.tobytes()
    # x = (xt, x0): the noised and the clean ids; both differ batch by batch
    for leaf in range(2):
        seen = {_leaves(batch)[leaf].tobytes() for batch in pool}
        assert len(seen) == 128


# ------------------------------------------------------------ the estimator
def _p95(samples):
    return statistics.quantiles(samples, n=20)[-1]   # run.py's estimator


@pytest.mark.parametrize("n,stall_moves_it", [(100, False), (40, False),
                                              (38, True), (29, True)])
def test_one_stalled_step_moves_the_percentile_only_under_the_floor(
        n, stall_moves_it):
    """``statistics.quantiles(samples, n=20)[-1]`` (the exclusive method)
    stands at position 0.95 x (n + 1), counted from 1 over the sorted
    samples.  29 samples: 28.5, the mean of the two largest.  100: 95.95,
    with four samples beyond it.  One stalled step (3 x the median here) is
    the largest sample; it leaves the reading alone as soon as the position
    is n - 1 or lower: 0.95 (n + 1) <= n - 1, n >= 39.  Hence the floor."""
    steady = [700.0 + 0.01 * i for i in range(n)]
    stalled = steady[:-1] + [2100.0]
    position = 0.95 * (n + 1)
    assert (position > n - 1) == stall_moves_it
    assert (_p95(stalled) > steady[-1]) == stall_moves_it
    if not stall_moves_it:
        assert _p95(stalled) == _p95(steady)
    assert (n < bench.MIN_SAMPLES) == stall_moves_it


def test_two_largest_of_29_samples_are_the_reading():
    samples = [700.0] * 27 + [710.0, 730.0]
    assert _p95(samples) == pytest.approx(720.0)       # position 28.5


# ------------------------------------------------- the floor under a whole run
def test_floor_raises_under_forty_samples_and_not_at_forty():
    bench.enough_samples(BERT, 40)
    bench.enough_samples(BERT, 115)
    with pytest.raises(SystemExit, match="39 samples"):
        bench.enough_samples(BERT, 39)


def test_whole_run_with_too_few_samples_is_a_harness_error(monkeypatch):
    """The rest of a whole run, without the harness's look for a chip (the
    cell opened at its rehearsal size whatever the run says): one sample in
    the window, and no result line."""
    real = bench.Cell

    class Tiny(real):
        def __init__(self, workload, rehearse=False):
            super().__init__(workload, rehearse=True)

    monkeypatch.setattr(bench, "Cell", Tiny)
    with pytest.raises(SystemExit, match="over 1 samples"):
        bench.run_cell(BERT, 5, 0.01, 0, rehearse=False)
