"""mx.data — sharded streaming input pipeline (ISSUE 15).

Covers: deterministic shard assignment + epoch order, the prefetch
ring's occupancy/stall accounting, bit-identical mid-epoch cursor
resume (standalone and through Trainer checkpoints), the data_read
fault site, preemption drain (StreamLoader AND the gluon DataLoader
worker processes), the unsharded-iterator guard, ring depth and workers
from the environment, mesh-sharded staging consumed by the captured step,
and the data_* telemetry families.
"""
from __future__ import annotations

import io as _bio
import os
import tempfile

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import data as mxdata
from mxnet_tpu import gluon, recordio, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn


def _write_shards(td, n_shards=3, per_shard=20, dim=8, name="t"):
    rs = np.random.RandomState(42)
    for s in range(n_shards):
        w = recordio.MXIndexedRecordIO(
            os.path.join(td, "%s-%d.idx" % (name, s)),
            os.path.join(td, "%s-%d.rec" % (name, s)), "w")
        for i in range(per_shard):
            buf = _bio.BytesIO()
            np.save(buf, rs.rand(dim).astype(np.float32))
            gid = s * per_shard + i
            w.write_idx(i, recordio.pack(
                recordio.IRHeader(0, float(gid % 4), gid, 0),
                buf.getvalue()))
        w.close()
    return os.path.join(td, "%s-*.rec" % name)


@pytest.fixture
def shard_dir():
    with tempfile.TemporaryDirectory(prefix="mxdata_") as td:
        yield td


def _drain_ids(loader):
    out = []
    for _ in loader:
        out.append(loader.last_ids.tolist())
    return out


# ---------------------------------------------------------------------------
# ShardSet: assignment + order
# ---------------------------------------------------------------------------

def test_shardset_counts_and_ids(shard_dir):
    pat = _write_shards(shard_dir)
    ss = mxdata.ShardSet.from_pattern(pat)
    assert len(ss) == 3 and ss.total_records == 60
    assert ss.global_id(0, 0) == 0
    assert ss.global_id(2, 5) == 45


def test_shard_assignment_round_robin(shard_dir):
    pat = _write_shards(shard_dir, n_shards=4, per_shard=5)
    ss = mxdata.ShardSet.from_pattern(pat)
    e0, mode0 = ss.assignment(2, 0)
    e1, mode1 = ss.assignment(2, 1)
    assert mode0 == mode1 == "shard"
    # whole shards round-robin; slices are disjoint and cover all
    assert {si for si, _ in e0} == {0, 2}
    assert {si for si, _ in e1} == {1, 3}
    assert len(e0) + len(e1) == ss.total_records
    assert ss.host_record_count(2, 0) == len(e0)
    assert ss.host_record_count(2, 1) == len(e1)


def test_record_striping_when_fewer_shards_than_hosts(shard_dir):
    pat = _write_shards(shard_dir, n_shards=1, per_shard=10)
    ss = mxdata.ShardSet.from_pattern(pat)
    e0, mode = ss.assignment(2, 0)
    e1, _ = ss.assignment(2, 1)
    assert mode == "record"
    assert len(e0) == 5 and len(e1) == 5
    assert set(e0).isdisjoint(e1)
    assert ss.host_record_count(2, 0) == 5


def test_epoch_order_pure_function(shard_dir):
    pat = _write_shards(shard_dir)
    ss = mxdata.ShardSet.from_pattern(pat)
    entries, _ = ss.assignment(1, 0)
    a = mxdata.ShardSet.epoch_order(entries, seed=3, epoch=0)
    b = mxdata.ShardSet.epoch_order(entries, seed=3, epoch=0)
    c = mxdata.ShardSet.epoch_order(entries, seed=3, epoch=1)
    d = mxdata.ShardSet.epoch_order(entries, seed=4, epoch=0)
    assert a == b
    assert a != c and a != d
    assert sorted(a) == list(range(len(entries)))
    seq = mxdata.ShardSet.epoch_order(entries, 3, 0, shuffle=False)
    assert seq == list(range(len(entries)))


def test_missing_idx_sidecar_scans_offsets(shard_dir):
    pat = _write_shards(shard_dir, n_shards=1, per_shard=6)
    os.unlink(os.path.join(shard_dir, "t-0.idx"))
    ss = mxdata.ShardSet.from_pattern(pat)
    assert ss.total_records == 6
    ldr = mxdata.StreamLoader(ss, batch_size=2, shuffle=False,
                              num_workers=1, prefetch=2)
    ids = _drain_ids(ldr)
    assert [i for b in ids for i in b] == list(range(6))
    ldr.close()


# ---------------------------------------------------------------------------
# StreamLoader: determinism, epochs, resume
# ---------------------------------------------------------------------------

def test_stream_deterministic_and_epochs_differ(shard_dir):
    pat = _write_shards(shard_dir)
    a = mxdata.StreamLoader(pat, batch_size=10, seed=5, num_workers=2,
                            prefetch=2)
    b = mxdata.StreamLoader(pat, batch_size=10, seed=5, num_workers=1,
                            prefetch=3)
    ep0_a, ep0_b = _drain_ids(a), _drain_ids(b)
    assert ep0_a == ep0_b            # worker/depth never change order
    ep1_a = _drain_ids(a)
    assert ep1_a != ep0_a            # epoch reshuffles
    assert a.epoch == 2
    a.close(), b.close()


def test_batch_shapes_and_device_arrays(shard_dir):
    pat = _write_shards(shard_dir, dim=4)
    ldr = mxdata.StreamLoader(pat, batch_size=6, seed=0, num_workers=1,
                              prefetch=2)
    batch = next(iter(ldr))
    x, y = batch
    assert isinstance(x, mx.nd.NDArray) and x.shape == (6, 4)
    assert y.shape == (6,)
    ldr.close()


def test_mid_epoch_cursor_resume_bit_identical(shard_dir):
    pat = _write_shards(shard_dir)
    ref = mxdata.StreamLoader(pat, batch_size=4, seed=9)
    ref_ids = _drain_ids(ref)
    ref.close()

    ldr = mxdata.StreamLoader(pat, batch_size=4, seed=9)
    it = iter(ldr)
    got = []
    for _ in range(6):
        next(it)
        got.append(ldr.last_ids.tolist())
    cursor = ldr.state_dict()
    assert cursor["batch"] == 6 and cursor["epoch"] == 0
    ldr.close()

    res = mxdata.StreamLoader(pat, batch_size=4, seed=9)
    res.load_state_dict(cursor)
    rest = _drain_ids(res)
    assert got + rest == ref_ids     # the exact remaining sample order
    res.close()


def test_cursor_counts_consumed_not_staged(shard_dir):
    """Batches staged in the ring but never handed to the loop must be
    re-read after a restore — the cursor moves at consumption."""
    pat = _write_shards(shard_dir)
    ldr = mxdata.StreamLoader(pat, batch_size=4, seed=2, prefetch=4,
                              num_workers=2)
    it = iter(ldr)
    next(it)                          # consume ONE; ring holds more
    cursor = ldr.state_dict()
    assert cursor["batch"] == 1
    ldr.close()


def test_break_mid_epoch_tears_down_and_resumes(shard_dir):
    """Abandoning the epoch iterator (GeneratorExit) must stop the
    reader/stager threads and leave the cursor at the break point."""
    import threading

    pat = _write_shards(shard_dir)
    before = threading.active_count()
    ldr = mxdata.StreamLoader(pat, batch_size=4, seed=4, num_workers=2)
    got = []
    for _ in ldr:                     # break out mid-epoch
        got.append(ldr.last_ids.tolist())
        if len(got) == 3:
            break
    deadline = __import__("time").time() + 5
    while threading.active_count() > before and \
            __import__("time").time() < deadline:
        __import__("time").sleep(0.05)
    assert threading.active_count() <= before, "loader threads leaked"
    assert ldr.state_dict()["batch"] == 3
    rest = _drain_ids(ldr)            # later iter() continues exactly
    ref = mxdata.StreamLoader(pat, batch_size=4, seed=4)
    assert got + rest == _drain_ids(ref)
    ldr.close(), ref.close()


def test_explicit_zero_prefetch_or_workers_rejected(shard_dir):
    pat = _write_shards(shard_dir)
    with pytest.raises(MXNetError, match="prefetch"):
        mxdata.StreamLoader(pat, batch_size=4, num_workers=2, prefetch=0)
    with pytest.raises(MXNetError, match="num_workers"):
        mxdata.StreamLoader(pat, batch_size=4, num_workers=0, prefetch=2)


def test_del_removes_preempt_hook(shard_dir):
    from mxnet_tpu.resilience import preempt

    pat = _write_shards(shard_dir)
    ldr = mxdata.StreamLoader(pat, batch_size=4, seed=0)
    name = ldr._preempt_hook
    assert name in preempt.state()["hooks"]
    del ldr
    import gc

    gc.collect()
    assert name not in preempt.state()["hooks"]


def test_cursor_geometry_mismatch_raises(shard_dir):
    pat = _write_shards(shard_dir)
    ldr = mxdata.StreamLoader(pat, batch_size=4, seed=1)
    cur = ldr.state_dict()
    other = mxdata.StreamLoader(pat, batch_size=4, seed=2)
    with pytest.raises(MXNetError, match="seed/shuffle"):
        other.load_state_dict(cur)
    bad = dict(cur, num_hosts=2, host=1)
    with pytest.raises(MXNetError, match="host"):
        ldr.load_state_dict(bad)
    ldr.close(), other.close()


def test_two_host_slices_disjoint_and_deterministic(shard_dir):
    pat = _write_shards(shard_dir, n_shards=4, per_shard=10)
    h0 = mxdata.StreamLoader(pat, batch_size=8, seed=11, num_hosts=2,
                             host=0)
    h1 = mxdata.StreamLoader(pat, batch_size=8, seed=11, num_hosts=2,
                             host=1)
    assert h0.local_batch == 4 and h1.local_batch == 4
    assert h0.batches_per_epoch == h1.batches_per_epoch == 5
    i0 = [i for b in _drain_ids(h0) for i in b]
    i1 = [i for b in _drain_ids(h1) for i in b]
    assert set(i0).isdisjoint(i1)
    h0.close(), h1.close()


def test_global_batch_must_divide_hosts(shard_dir):
    pat = _write_shards(shard_dir)
    with pytest.raises(MXNetError, match="divide"):
        mxdata.StreamLoader(pat, batch_size=5, num_hosts=2, host=0)


# ---------------------------------------------------------------------------
# ring behavior + telemetry
# ---------------------------------------------------------------------------

def test_ring_occupancy_and_families(shard_dir):
    pat = _write_shards(shard_dir)
    telemetry.reset()
    ldr = mxdata.StreamLoader(pat, batch_size=6, seed=0, prefetch=3,
                              num_workers=2)
    seen_occ = 0
    import time

    it = iter(ldr)
    next(it)
    time.sleep(0.3)                   # let the stager refill
    seen_occ = max(seen_occ, ldr.stats()["ring_occupancy"])
    for _ in it:
        pass
    assert seen_occ >= 1              # the ring ran AHEAD of the loop
    tot = telemetry.totals(nonzero=True)
    assert tot.get("data_batches_total", 0) >= ldr.batches_per_epoch
    assert tot.get("data_records_total", 0) >= 6 * ldr.batches_per_epoch
    prom = telemetry.prometheus()
    for fam in ("data_ring_occupancy", "data_ring_depth",
                "data_ring_stalls_total", "data_read_seconds",
                "data_decode_seconds", "data_stage_seconds",
                "data_batches_total"):
        assert fam in prom, fam
    ldr.close()


def test_slow_consumer_keeps_ring_full_slow_producer_stalls(shard_dir):
    pat = _write_shards(shard_dir, per_shard=8)
    import time

    def slow_decode(raw):
        time.sleep(0.05)
        return mxdata.default_decode(raw)

    ldr = mxdata.StreamLoader(pat, batch_size=8, seed=0, prefetch=2,
                              num_workers=1, decode_fn=slow_decode)
    list(iter(ldr))
    assert ldr.stats()["ring_stalls"] >= 1
    ldr.close()


# ---------------------------------------------------------------------------
# faults + preemption
# ---------------------------------------------------------------------------

def test_data_read_io_fault_retried(shard_dir):
    from mxnet_tpu import resilience

    pat = _write_shards(shard_dir)
    telemetry.reset()
    resilience.plan("data_read@2:io")
    try:
        ldr = mxdata.StreamLoader(pat, batch_size=6, seed=3,
                                  num_workers=1, prefetch=2)
        ref = mxdata.StreamLoader(pat, batch_size=6, seed=3,
                                  num_workers=1, prefetch=2)
        with_fault = _drain_ids(ldr)
        resilience.clear()
        clean = _drain_ids(ref)
        assert with_fault == clean    # retry recovered, stream intact
        assert telemetry.totals().get("data_read_retries_total", 0) >= 1
        ldr.close(), ref.close()
    finally:
        resilience.clear()


def test_data_read_transient_fault_surfaces(shard_dir):
    from mxnet_tpu import resilience
    from mxnet_tpu.resilience.inject import InjectedFault

    pat = _write_shards(shard_dir)
    resilience.plan("data_read@1:transient")
    try:
        ldr = mxdata.StreamLoader(pat, batch_size=6, seed=3,
                                  num_workers=1, prefetch=2)
        with pytest.raises(InjectedFault):
            _drain_ids(ldr)
        ldr.close()
    finally:
        resilience.clear()


def test_stream_loader_preempt_drain(shard_dir):
    from mxnet_tpu.resilience import preempt

    pat = _write_shards(shard_dir)
    ldr = mxdata.StreamLoader(pat, batch_size=6, seed=0, num_workers=2)
    it = iter(ldr)
    next(it)
    hooks = preempt.state()["hooks"]
    assert any(h.startswith("data_loader-") for h in hooks)
    results = preempt.graceful_shutdown()
    name = [h for h in results if h.startswith("data_loader-")][0]
    assert results[name] == "ok"
    assert ldr.stats()["ring_occupancy"] == 0
    # the hook is gone after close() — no leak into later shutdowns
    ldr.close()
    assert not any(h.startswith("data_loader-")
                   for h in preempt.state()["hooks"])


def test_gluon_dataloader_preempt_drains_workers(shard_dir):
    """SIGTERM mid-epoch: the _MultiWorkerIter's preempt hook shuts
    worker PROCESSES down instead of leaking them (ISSUE 15 satellite)."""
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.gluon.data.dataset import ArrayDataset
    from mxnet_tpu.resilience import preempt

    ds = ArrayDataset(np.arange(64, dtype=np.float32).reshape(32, 2),
                      np.arange(32, dtype=np.float32))
    loader = DataLoader(ds, batch_size=4, num_workers=2)
    it = iter(loader)
    next(it)
    # the live iterator registered a drain hook
    hooks = preempt.state()["hooks"]
    assert any(h.startswith("gluon_dataloader-") for h in hooks)
    results = preempt.graceful_shutdown()
    name = [h for h in results if h.startswith("gluon_dataloader-")][0]
    assert results[name] == "ok"
    # hook deregistered and worker processes reaped by shutdown()
    assert not any(h.startswith("gluon_dataloader-")
                   for h in preempt.state()["hooks"])
    del it


# ---------------------------------------------------------------------------
# trainer + checkpoint integration
# ---------------------------------------------------------------------------

def _tiny_trainer(dim=8):
    net = nn.Dense(4, in_units=dim)
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1})
    return net, tr


def test_trainer_state_dict_carries_cursor(shard_dir):
    pat = _write_shards(shard_dir)
    _net, tr = _tiny_trainer()
    ldr = mxdata.StreamLoader(pat, batch_size=4, seed=7)
    tr.attach_loader(ldr)
    it = iter(ldr)
    next(it), next(it)
    tree = tr.state_dict()
    assert tree["data"]["batch"] == 2
    assert tree["data"]["seed"] == 7
    ldr.close()


def test_trainer_checkpoint_roundtrip_resumes_stream(shard_dir):
    pat = _write_shards(shard_dir)
    ref = mxdata.StreamLoader(pat, batch_size=4, seed=7)
    ref_ids = _drain_ids(ref)
    ref.close()

    _net, tr = _tiny_trainer()
    ldr = mxdata.StreamLoader(pat, batch_size=4, seed=7)
    tr.attach_loader(ldr)
    it = iter(ldr)
    got = []
    for _ in range(5):
        next(it)
        got.append(ldr.last_ids.tolist())
    root = os.path.join(shard_dir, "ck")
    tr.save_checkpoint(root)
    ldr.close()

    _net2, tr2 = _tiny_trainer()
    ldr2 = mxdata.StreamLoader(pat, batch_size=4, seed=7)
    tr2.attach_loader(ldr2)
    tr2.load_checkpoint(root)
    assert ldr2.state_dict()["batch"] == 5
    rest = _drain_ids(ldr2)
    assert got + rest == ref_ids
    ldr2.close()


def test_restore_before_attach_is_held_pending(shard_dir):
    pat = _write_shards(shard_dir)
    _net, tr = _tiny_trainer()
    ldr = mxdata.StreamLoader(pat, batch_size=4, seed=7)
    tr.attach_loader(ldr)
    it = iter(ldr)
    next(it), next(it), next(it)
    root = os.path.join(shard_dir, "ck2")
    tr.save_checkpoint(root)
    ldr.close()

    _net2, tr2 = _tiny_trainer()
    tr2.load_checkpoint(root)     # no loader attached yet
    late = mxdata.StreamLoader(pat, batch_size=4, seed=7)
    tr2.attach_loader(late)       # pending cursor applies HERE
    assert late.state_dict()["batch"] == 3
    late.close()


def test_checkpoint_without_cursor_still_loads(shard_dir):
    _net, tr = _tiny_trainer()
    root = os.path.join(shard_dir, "ck3")
    tr.save_checkpoint(root)      # no loader attached: no data key
    _net2, tr2 = _tiny_trainer()
    ldr = mxdata.StreamLoader(_write_shards(shard_dir, name="u"),
                              batch_size=4)
    tr2.attach_loader(ldr)
    tr2.load_checkpoint(root)     # old tree: loader cursor untouched
    assert ldr.state_dict()["batch"] == 0
    ldr.close()


# ---------------------------------------------------------------------------
# mesh staging + captured step
# ---------------------------------------------------------------------------

def test_mesh_staged_batches_feed_captured_step(shard_dir):
    import jax

    from mxnet_tpu import shard

    if len(jax.devices()) < 2:
        pytest.skip("needs virtual devices")
    pat = _write_shards(shard_dir, dim=8)
    mesh = shard.GlobalMesh(dp=2, devices=jax.devices()[:2])
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu", in_units=8),
            nn.Dense(1, in_units=8))
    net.initialize()
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, mesh=mesh)
    prog = tr.capture(net, gluon.loss.L2Loss())
    ldr = mxdata.StreamLoader(pat, batch_size=4, seed=0, mesh=mesh,
                              num_workers=1, prefetch=2)
    it = iter(ldr)
    x, y = next(it)
    # the ring staged onto the mesh's dp batch sharding — the exact
    # placement the captured program pins, so dispatch re-puts nothing
    assert x._data.sharding == mesh.batch_sharding(x.shape)
    loss = prog(x, y.reshape((4, 1)))
    assert np.isfinite(float(loss.asnumpy().sum()))
    assert prog.report()["paths"]["captured"] == 1
    ldr.close()


# ---------------------------------------------------------------------------
# ring depth and workers: explicit, else the environment; guards
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("env,kw,want", [
    ({}, {}, (2, 2)),                                   # the defaults
    ({"MXNET_DATA_PREFETCH": "5", "MXNET_DATA_WORKERS": "3"}, {}, (5, 3)),
    ({"MXNET_DATA_PREFETCH": "5", "MXNET_DATA_WORKERS": "3"},
     {"prefetch": 2, "num_workers": 1}, (2, 1)),        # explicit wins
    ({"MXNET_DATA_PREFETCH": "5", "MXNET_DATA_WORKERS": "3"},
     {"prefetch": 4}, (4, 3)),                          # each on its own
    ({"MXNET_DATA_PREFETCH": "0", "MXNET_DATA_WORKERS": "0"}, {}, (1, 1)),
])
def test_stream_loader_depth_and_workers(shard_dir, monkeypatch, env, kw,
                                         want):
    pat = _write_shards(shard_dir)
    for name in ("MXNET_DATA_PREFETCH", "MXNET_DATA_WORKERS"):
        monkeypatch.delenv(name, raising=False)
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    ldr = mxdata.StreamLoader(pat, batch_size=6, seed=0, **kw)
    assert (ldr.prefetch, ldr.num_workers) == want
    ldr.close()


def test_unsharded_iterators_guarded(shard_dir, monkeypatch):
    pat = _write_shards(shard_dir, n_shards=1)
    rec = pat.replace("*", "0")
    monkeypatch.setenv("MXNET_DIST_NUM_WORKERS", "2")
    monkeypatch.setenv("MXNET_DIST_RANK", "0")
    from mxnet_tpu import io as mxio
    from mxnet_tpu.contrib.io import DataLoaderIter

    with pytest.raises(MXNetError, match="StreamLoader"):
        mxio.ImageRecordIter(path_imgrec=rec, data_shape=(8,),
                             batch_size=2)
    with pytest.raises(MXNetError, match="StreamLoader"):
        DataLoaderIter(loader=None)
    # the deliberate escape hatch
    monkeypatch.setenv("MXNET_DATA_ALLOW_UNSHARDED", "1")
    it = mxio.ImageRecordIter(path_imgrec=rec, data_shape=(8,),
                              batch_size=2)
    assert it is not None
    # single-host worlds are never guarded
    monkeypatch.delenv("MXNET_DATA_ALLOW_UNSHARDED")
    monkeypatch.setenv("MXNET_DIST_NUM_WORKERS", "1")
    assert mxdata.world_coords()[0] == 1


def test_diagnose_data_section_runs(shard_dir):
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "diagnose.py"),
         "--data"],
        capture_output=True, text=True, timeout=240,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "Data Pipeline" in proc.stdout
    assert "ring depth" in proc.stdout
