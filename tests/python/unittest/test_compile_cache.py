"""mx.compile tests: store durability (corrupt/truncated artifacts
quarantined, never loaded), LRU size-cap eviction, fingerprint hygiene
(env/version drift is a clean miss, never a wrong artifact), benign
concurrent commit races, the in-process hit/commit path through
``_get_cached_op``, cross-block ``warm_start`` round-trips, graceful
degradation on every cache failure, and the jax.export capability
probe."""
import json
import os
import shutil
import threading
import zlib

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile as mxcompile
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.compile import cache as cache_mod
from mxnet_tpu.compile.cache import ARTIFACT, COMMITTED, META, CompileCache
from mxnet_tpu.gluon import nn


@pytest.fixture(autouse=True)
def _clean_state(tmp_path):
    """Every test gets a private cache dir, an enabled subsystem, and a
    reset telemetry registry; globals restored afterwards."""
    telemetry.enable()
    telemetry.reset()
    mxcompile.configure(dir=str(tmp_path / "cc"))
    mxcompile.enable()
    yield
    mxcompile.disable()
    mxcompile._CACHE = None
    telemetry.enable()
    telemetry.reset()


def _dense(seed=0, in_units=16, units=4):
    blk = nn.Dense(units, flatten=False, in_units=in_units)
    blk.initialize()
    rs = np.random.RandomState(seed)
    for p in blk.collect_params().values():
        p.set_data(mx.nd.array(rs.rand(*p.shape).astype("float32")))
    blk.hybridize()
    return blk


def _artifact_paths(cache):
    out = []
    for _fp, d, _n, _m in cache.entries():
        out.append(os.path.join(d, ARTIFACT))
    return sorted(out)


# ---------------------------------------------------------------------------
# raw store: commit / load / quarantine
# ---------------------------------------------------------------------------

def test_commit_then_load_roundtrip(tmp_path):
    c = CompileCache(root=str(tmp_path / "s"))
    fp = c.fingerprint("module @m {}")
    payload = b"x" * 1000
    d = c.commit(fp, payload, {"block_sig": "sig"})
    assert d is not None
    names = sorted(os.listdir(d))
    assert names == [ARTIFACT, COMMITTED, META]
    raw, meta = c.load(fp)
    assert raw == payload
    assert meta["fingerprint"] == fp
    assert meta["artifact_crc32"] == (zlib.crc32(payload) & 0xFFFFFFFF)
    assert meta["block_sig"] == "sig"
    assert c.stats()["entries"] == 1


def test_uncommitted_entry_is_a_miss(tmp_path):
    c = CompileCache(root=str(tmp_path / "s"))
    fp = c.fingerprint("p")
    d = c.commit(fp, b"data", {})
    os.remove(os.path.join(d, COMMITTED))  # simulate a torn commit
    assert c.load(fp) is None
    assert c.stats()["entries"] == 0  # enumeration skips it too


def test_corrupt_artifact_quarantined_not_loaded(tmp_path):
    c = CompileCache(root=str(tmp_path / "s"))
    fp = c.fingerprint("p")
    d = c.commit(fp, b"A" * 512, {})
    with open(os.path.join(d, ARTIFACT), "r+b") as f:
        f.seek(10)
        f.write(b"\xde\xad")
    assert c.load(fp) is None
    assert telemetry.value("compile_cache_quarantine_total") == 1
    q = c.quarantined()
    assert len(q) == 1 and q[0].endswith(".corrupt")
    # the quarantined dir is invisible to every future lookup
    assert c.load(fp) is None
    assert c.entries() == []


def test_truncated_artifact_quarantined(tmp_path):
    c = CompileCache(root=str(tmp_path / "s"))
    fp = c.fingerprint("p")
    d = c.commit(fp, b"B" * 512, {})
    with open(os.path.join(d, ARTIFACT), "r+b") as f:
        f.truncate(100)
    assert c.load(fp) is None  # nbytes mismatch, no CRC needed
    assert len(c.quarantined()) == 1


def test_committed_entry_missing_file_quarantined(tmp_path):
    """A COMMITTED entry that lost META/ARTIFACT must be quarantined,
    not treated as a plain miss: commit() discards re-commits when the
    entry dir already exists, so a mere miss would leave the broken
    dir blocking that fingerprint forever."""
    c = CompileCache(root=str(tmp_path / "s"))
    fp = c.fingerprint("p")
    d = c.commit(fp, b"I" * 128, {})
    os.remove(os.path.join(d, META))
    assert c.load(fp) is None
    assert len(c.quarantined()) == 1
    # the fingerprint is committable again after the quarantine
    assert c.commit(fp, b"I" * 128, {}) is not None
    raw, _meta = c.load(fp)
    assert raw == b"I" * 128


def test_unreadable_meta_quarantined(tmp_path):
    c = CompileCache(root=str(tmp_path / "s"))
    fp = c.fingerprint("p")
    d = c.commit(fp, b"C", {})
    with open(os.path.join(d, META), "w") as f:
        f.write("{not json")
    assert c.load(fp) is None
    assert len(c.quarantined()) == 1


def test_repeated_quarantine_never_collides(tmp_path):
    c = CompileCache(root=str(tmp_path / "s"))
    fp = c.fingerprint("p")
    for _ in range(3):
        d = c.commit(fp, b"D" * 64, {})
        with open(os.path.join(d, ARTIFACT), "r+b") as f:
            f.write(b"\xff" * 8)
        assert c.load(fp) is None
    assert len(c.quarantined()) == 3


def test_load_io_failure_is_plain_miss(tmp_path):
    c = CompileCache(root=str(tmp_path / "s"))
    assert c.load(c.fingerprint("never committed")) is None
    assert telemetry.value("compile_cache_quarantine_total") == 0


def test_torn_entry_dir_does_not_block_recommit(tmp_path):
    """A crash mid shutil.rmtree (eviction/clear) can leave the entry
    dir with files but no COMMITTED marker.  That dir must not make the
    fingerprint permanently uncacheable: commit() parks it and lands a
    fresh entry instead of treating bare dir existence as 'already
    committed'."""
    c = CompileCache(root=str(tmp_path / "s"))
    fp = c.fingerprint("p")
    d = c.commit(fp, b"T" * 128, {})
    os.remove(os.path.join(d, COMMITTED))  # torn mid-delete
    assert c.commit(fp, b"T" * 128, {}) is not None
    raw, _meta = c.load(fp)
    assert raw == b"T" * 128
    assert len(c.quarantined()) == 1  # the torn remains were parked


def test_torn_entry_dir_parked_on_load(tmp_path):
    """load() quarantines a marker-less dir so its bytes count against
    the size cap instead of staying invisible to entries()/_evict."""
    c = CompileCache(root=str(tmp_path / "s"))
    fp = c.fingerprint("p")
    d = c.commit(fp, b"U" * 128, {})
    os.remove(os.path.join(d, COMMITTED))
    assert c.load(fp) is None
    assert not os.path.isdir(d)
    assert len(c.quarantined()) == 1


def test_transient_io_error_is_miss_not_quarantine(tmp_path,
                                                   monkeypatch):
    """An environmental OSError (fd exhaustion, EACCES, EIO) while
    reading a healthy entry must be a plain miss — quarantining would
    permanently discard a perfectly loadable artifact."""
    import builtins
    import errno

    c = CompileCache(root=str(tmp_path / "s"))
    fp = c.fingerprint("p")
    c.commit(fp, b"V" * 128, {})
    real_open = builtins.open

    def exhausted(path, *a, **kw):
        if str(path).endswith(META):
            raise OSError(errno.EMFILE, "too many open files")
        return real_open(path, *a, **kw)

    monkeypatch.setattr(builtins, "open", exhausted)
    assert c.load(fp) is None
    monkeypatch.undo()
    assert c.quarantined() == []
    raw, _meta = c.load(fp)  # healthy entry still loads afterwards
    assert raw == b"V" * 128


def test_unknown_signature_scan_amortized(tmp_path, monkeypatch):
    """A block with no committed entries pays at most ONE whole-cache
    scan: the scan leaves an (empty) index dir behind, so every later
    warm-start of that model against the shared cache is O(1)."""
    c = CompileCache(root=str(tmp_path / "s"))
    c.commit(c.fingerprint("p"), b"W" * 64, {"block_sig": "sigA"})
    assert c.entries_for_block("never-committed-sig") == []
    monkeypatch.setattr(
        c, "entries",
        lambda: pytest.fail("negative result was not indexed"))
    assert c.entries_for_block("never-committed-sig") == []


def test_failed_index_marker_repaired_by_scan(tmp_path, monkeypatch):
    """A commit whose best-effort by-block marker write failed must
    still be findable: the one-time scan repairs the index."""
    c = CompileCache(root=str(tmp_path / "s"))
    c.commit(c.fingerprint("other"), b"o" * 64, {"block_sig": "sigB"})
    monkeypatch.setattr(c, "_index_add", lambda *a: None)  # ENOSPC etc.
    fp = c.fingerprint("p")
    c.commit(fp, b"Y" * 64, {"block_sig": "sigA"})
    monkeypatch.undo()
    assert [f for f, _ in c.entries_for_block("sigA")] == [fp]
    assert os.listdir(c._index_dir("sigA")) == [fp]  # repaired


def test_fallback_scan_repairs_index(tmp_path):
    """A pre-index cache (no by-block root) pays the full scan once;
    the scan rebuilds the markers so the next lookup is indexed."""
    c = CompileCache(root=str(tmp_path / "s"))
    fp = c.fingerprint("p")
    c.commit(fp, b"X" * 64, {"block_sig": "sigA"})
    shutil.rmtree(os.path.join(c.root, cache_mod.BY_BLOCK))
    assert [f for f, _ in c.entries_for_block("sigA")] == [fp]
    assert os.listdir(c._index_dir("sigA")) == [fp]


def test_entries_for_block_served_from_index(tmp_path):
    c = CompileCache(root=str(tmp_path / "s"))
    fps = [c.fingerprint("p%d" % i) for i in range(3)]
    for fp in fps[:2]:
        c.commit(fp, b"a" * 64, {"block_sig": "sigA"})
    c.commit(fps[2], b"b" * 64, {"block_sig": "sigB"})
    idx = c._index_dir("sigA")
    assert sorted(os.listdir(idx)) == sorted(fps[:2])
    assert sorted(fp for fp, _ in c.entries_for_block("sigA")) \
        == sorted(fps[:2])
    # a dangling marker (its entry evicted/quarantined meanwhile) is
    # pruned on sight, never served
    shutil.rmtree(c._entry_dir(fps[0]))
    assert [fp for fp, _ in c.entries_for_block("sigA")] == [fps[1]]
    assert os.listdir(idx) == [fps[1]]
    # signatures with no index dir fall back to the full META scan
    shutil.rmtree(os.path.join(c.root, cache_mod.BY_BLOCK))
    assert [fp for fp, _ in c.entries_for_block("sigB")] == [fps[2]]


# ---------------------------------------------------------------------------
# fingerprint hygiene
# ---------------------------------------------------------------------------

def test_fingerprint_covers_program_and_environment(tmp_path):
    c = CompileCache(root=str(tmp_path / "s"))
    assert c.fingerprint("module A") != c.fingerprint("module B")
    assert c.fingerprint("module A") == c.fingerprint("module A")
    # any environment drift (versions, topology, XLA flags...) rotates
    # every key -> old artifacts become clean misses, never wrong loads
    c2 = CompileCache(root=str(tmp_path / "s"))
    c2._env_fp = c._env_parts() + "\njax=some.other.version"
    assert c2.fingerprint("module A") != c.fingerprint("module A")
    fp_old = c.fingerprint("module A")
    c.commit(fp_old, b"artifact", {})
    assert c2.load(c2.fingerprint("module A")) is None
    assert c.load(fp_old) is not None


def test_fingerprint_covers_jaxlib_version(tmp_path):
    """jaxlib ships the XLA runtime and versions independently of jax;
    an executable serialized by an older compiler must be a clean miss
    after a jaxlib-only upgrade."""
    c = CompileCache(root=str(tmp_path / "s"))
    assert "\njaxlib=" in c._env_parts()


def test_env_opt_out_beats_dir(monkeypatch):
    """MXNET_COMPILE_CACHE=0 must win even when a fleet-wide
    MXNET_COMPILE_CACHE_DIR is exported; _DIR implies enablement only
    while the boolean knob is unset."""
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", "/tmp/somewhere")
    monkeypatch.delenv("MXNET_COMPILE_CACHE", raising=False)
    assert mxcompile._env_enabled() is True
    monkeypatch.setenv("MXNET_COMPILE_CACHE", "0")
    assert mxcompile._env_enabled() is False
    monkeypatch.setenv("MXNET_COMPILE_CACHE", "1")
    monkeypatch.delenv("MXNET_COMPILE_CACHE_DIR")
    assert mxcompile._env_enabled() is True
    monkeypatch.delenv("MXNET_COMPILE_CACHE")
    assert mxcompile._env_enabled() is False


def test_fingerprint_covers_xla_flags(tmp_path, monkeypatch):
    c = CompileCache(root=str(tmp_path / "s"))
    fp0 = c.fingerprint("m")
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
    c2 = CompileCache(root=str(tmp_path / "s"))
    assert c2.fingerprint("m") != fp0


# ---------------------------------------------------------------------------
# LRU eviction
# ---------------------------------------------------------------------------

def test_lru_eviction_respects_size_cap(tmp_path):
    c = CompileCache(root=str(tmp_path / "s"), max_bytes=1 << 20)
    payload = b"E" * 1200
    fps = [c.fingerprint("prog-%d" % i) for i in range(4)]
    c.commit(fps[0], payload, {})
    entry_bytes = c.stats()["total_bytes"]  # payload + META + COMMITTED
    cap = entry_bytes * 3 + entry_bytes // 2  # room for 3, not 4
    c._max_bytes = cap
    for i, fp in enumerate(fps[:3]):
        c.commit(fp, payload, {})
        os.utime(c._entry_dir(fp), (1000.0 + i, 1000.0 + i))
    assert c.stats()["entries"] == 3
    # loading fps[0] refreshes its LRU clock, so fps[1] is now oldest
    assert c.load(fps[0]) is not None
    c.commit(fps[3], payload, {})
    live = {e[0] for e in c.entries()}
    assert fps[3] in live, "just-committed entry must survive"
    assert fps[1] not in live, "least-recently-loaded entry evicted"
    assert c.stats()["total_bytes"] <= cap
    assert telemetry.value("compile_cache_evict_total") >= 1


def test_oversized_commit_does_not_wipe_cache(tmp_path):
    """An artifact bigger than the whole cap can never fit, so _evict
    drops IT — not every healthy entry in a doomed attempt to make
    room."""
    c = CompileCache(root=str(tmp_path / "s"), max_bytes=1 << 20)
    small = b"s" * 256
    fps = [c.fingerprint("small-%d" % i) for i in range(3)]
    for fp in fps:
        c.commit(fp, small, {})
    entry_bytes = c.stats()["total_bytes"] // 3
    c._max_bytes = entry_bytes * 4
    big_fp = c.fingerprint("huge")
    c.commit(big_fp, b"H" * (entry_bytes * 10), {})
    live = {e[0] for e in c.entries()}
    assert big_fp not in live, "oversized artifact must be dropped"
    assert live == set(fps), "healthy entries must survive"
    assert c.stats()["total_bytes"] <= c._max_bytes


def test_eviction_drops_quarantined_remains_first(tmp_path):
    """*.corrupt dirs count against the cap and are reclaimed before
    any live entry — otherwise they'd accumulate unboundedly."""
    c = CompileCache(root=str(tmp_path / "s"), max_bytes=1 << 20)
    payload = b"Q" * 1200
    fp0 = c.fingerprint("p0")
    c.commit(fp0, payload, {})
    entry_bytes = c.stats()["total_bytes"]
    with open(os.path.join(c._entry_dir(fp0), ARTIFACT), "r+b") as f:
        f.write(b"\x00" * 8)
    assert c.load(fp0) is None  # quarantined, still on disk
    c._max_bytes = entry_bytes * 2 + entry_bytes // 2
    c.commit(c.fingerprint("p1"), payload, {})
    c.commit(c.fingerprint("p2"), payload, {})  # over cap with remains
    assert c.quarantined() == [], "quarantined dir reclaimed first"
    assert c.stats()["entries"] == 2, "live entries untouched"


def test_no_eviction_when_uncapped(tmp_path):
    c = CompileCache(root=str(tmp_path / "s"), max_bytes=0)
    for i in range(5):
        c.commit(c.fingerprint("p%d" % i), b"F" * 4096, {})
    assert c.stats()["entries"] == 5


# ---------------------------------------------------------------------------
# concurrency
# ---------------------------------------------------------------------------

def test_concurrent_commit_race_is_benign(tmp_path):
    c = CompileCache(root=str(tmp_path / "s"))
    fp = c.fingerprint("shared program")
    payload = b"G" * 2048
    errs = []

    def worker():
        try:
            for _ in range(10):
                c.commit(fp, payload, {"block_sig": "s"})
                got = c.load(fp)
                assert got is None or got[0] == payload
        except Exception as exc:  # pragma: no cover - failure detail
            errs.append(exc)

    ts = [threading.Thread(target=worker) for _ in range(6)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    raw, _meta = c.load(fp)
    assert raw == payload
    assert c.stats()["entries"] == 1  # one content-keyed entry survives
    assert not [n for n in os.listdir(c.root)
                if n.startswith(".committing-")], "no leaked temp dirs"
    # only the publish that actually landed on disk counts as a commit
    assert telemetry.value("compile_cache_commit_total") == 1


def test_concurrent_load_during_quarantine(tmp_path):
    c = CompileCache(root=str(tmp_path / "s"))
    fp = c.fingerprint("p")
    d = c.commit(fp, b"H" * 256, {})
    with open(os.path.join(d, ARTIFACT), "r+b") as f:
        f.write(b"\x00" * 16)
    results, errs = [], []

    def loader():
        try:
            results.append(c.load(fp))
        except Exception as exc:  # pragma: no cover
            errs.append(exc)

    ts = [threading.Thread(target=loader) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    assert all(r is None for r in results)  # corrupt: nobody loads it


# ---------------------------------------------------------------------------
# the live path: _get_cached_op consults + commits
# ---------------------------------------------------------------------------

def test_first_build_commits_second_block_hits(tmp_path):
    x = mx.nd.ones((2, 3, 16))
    a = _dense(seed=1)
    ya = a(x).asnumpy()
    assert telemetry.value("compile_cache_miss_total") == 1
    assert telemetry.value("compile_cache_commit_total") == 1
    assert mxcompile.stats()["entries"] == 1

    # an identical block in the same process: its in-memory hybridize
    # cache is empty, so the disk cache serves the compiled executable
    b = _dense(seed=1)
    yb = b(x).asnumpy()
    np.testing.assert_allclose(ya, yb, rtol=1e-6)
    assert telemetry.value("compile_cache_hit_total") == 1
    # the disk hit is NOT a fresh build: only block a's compile counted
    assert telemetry.value("cachedop_build_total", {"block": "Dense"}) == 1


def test_different_shapes_get_distinct_entries(tmp_path):
    blk = _dense()
    blk(mx.nd.ones((2, 3, 16)))
    blk(mx.nd.ones((4, 5, 16)))
    assert mxcompile.stats()["entries"] == 2
    assert telemetry.value("compile_cache_commit_total") == 2


def test_disabled_cache_never_touches_disk(tmp_path):
    mxcompile.disable()
    blk = _dense()
    blk(mx.nd.ones((2, 3, 16)))
    assert mxcompile.stats()["entries"] == 0
    assert telemetry.value("compile_cache_miss_total") == 0


def test_cache_failure_degrades_to_inmemory_compile(tmp_path, monkeypatch):
    # every store operation explodes: the forward pass must still work
    monkeypatch.setattr(CompileCache, "load",
                        lambda self, fp: (_ for _ in ()).throw(OSError()))
    monkeypatch.setattr(CompileCache, "commit",
                        lambda self, fp, a, m: (_ for _ in ()).throw(
                            OSError()))
    blk = _dense(seed=3)
    y = blk(mx.nd.ones((2, 3, 16))).asnumpy()
    assert y.shape == (2, 3, 4)


def test_recording_calls_skip_the_persistent_cache(tmp_path):
    """Training (recording) calls only ever run the traceable jfn, so
    the live path must not pay an eager XLA compile + disk commit for
    an executable the recording branch never uses."""
    from mxnet_tpu import autograd

    blk = _dense(seed=2)
    x = mx.nd.ones((2, 3, 16))
    with autograd.record():
        y = blk(x)
    y.backward()
    assert telemetry.value("compile_cache_miss_total") == 0
    assert telemetry.value("compile_cache_commit_total") == 0
    assert mxcompile.stats()["entries"] == 0


def test_disk_hit_skips_build_metrics(tmp_path):
    """A persistent-cache hit is not a build: neither the build counter
    nor the build-latency histogram may record one."""
    x = mx.nd.ones((2, 3, 16))
    _dense(seed=13)(x)
    builds0 = telemetry.value("cachedop_build_total", {"block": "Dense"})
    samples0 = telemetry.value("cachedop_build_seconds")
    b = _dense(seed=13)
    b(x)  # in-memory miss -> disk hit
    assert telemetry.value("compile_cache_hit_total") == 1
    assert telemetry.value("cachedop_build_total",
                           {"block": "Dense"}) == builds0
    assert telemetry.value("cachedop_build_seconds") == samples0
    centry = next(iter(b._cached_ops.values()))
    assert centry.provenance == "cache"


def test_aot_call_failure_falls_back_to_jit(tmp_path):
    blk = _dense(seed=4)
    x = mx.nd.ones((2, 3, 16))
    y0 = blk(x).asnumpy()
    centry = next(iter(blk._cached_ops.values()))

    def boom(*a, **k):
        raise RuntimeError("aval drift")

    centry.cfn = boom
    centry.cfn_ok = False  # simulate a warm-started entry failing its
    #                        FIRST call (never served successfully)
    y1 = blk(x).asnumpy()
    np.testing.assert_allclose(y0, y1, rtol=1e-6)
    assert centry.cfn is None  # entry dropped to the jit path for good
    assert telemetry.value("compile_cache_fallback_total") == 1
    # the DISK entry is parked too: otherwise every future process
    # would warm_start the same failing artifact forever
    assert len(mxcompile.get_cache().quarantined()) == 1
    fresh = _dense(seed=4)
    assert mxcompile.warm_start(fresh) == 0


def test_served_artifact_survives_one_bad_call(tmp_path):
    """An artifact that already served calls successfully must NOT be
    quarantined by one anomalous request (e.g. an input placement the
    AOT executable rejects while jit just recompiles): the disk entry
    may be shared fleet-wide, and poisoning it would cost every
    process its warm start."""
    blk = _dense(seed=4)
    x = mx.nd.ones((2, 3, 16))
    blk(x).asnumpy()  # cfn served successfully -> cfn_ok
    centry = next(iter(blk._cached_ops.values()))
    assert centry.cfn_ok

    def boom(*a, **k):
        raise RuntimeError("placement mismatch")

    centry.cfn = boom
    blk(x).asnumpy()  # jfn fallback succeeds
    assert centry.cfn is None  # dropped in-memory...
    assert mxcompile.get_cache().quarantined() == []  # ...but not on disk


def test_transient_call_failure_keeps_disk_entry(tmp_path):
    """When the traceable fallback fails on the same inputs too, the
    failure implicates the RUNTIME (device OOM, EIO), not the
    artifact: the disk entry must survive for the next process."""
    blk = _dense(seed=4)
    x = mx.nd.ones((2, 3, 16))
    blk(x).asnumpy()
    centry = next(iter(blk._cached_ops.values()))

    def boom(*a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED: device OOM")

    centry.cfn = boom
    centry.jfn = boom
    with pytest.raises(RuntimeError):
        blk(x)
    assert mxcompile.get_cache().quarantined() == []


# ---------------------------------------------------------------------------
# warm_start / precompile
# ---------------------------------------------------------------------------

def test_warm_start_scoped_to_signatures(tmp_path):
    """signatures= restores only the wanted buckets: a shared cache
    holding other deployments' batch sizes must not have every entry
    deserialized and device-loaded by a server that needs a few."""
    x2, x4 = mx.nd.ones((2, 3, 16)), mx.nd.ones((4, 5, 16))
    a = _dense(seed=11)
    a(x2), a(x4)  # two committed signatures

    b = _dense(seed=11)
    got = mxcompile.warm_start(
        b, signatures=[[((2, 3, 16), "float32")]])
    assert got == 1
    assert len(b._cached_ops) == 1
    key, centry = b.find_cached_entry([((2, 3, 16), "float32")])
    assert centry is not None and centry.provenance == "cache"

    c = _dense(seed=11)  # no filter -> everything installs
    assert mxcompile.warm_start(c) == 2

    # warm_up-style spellings work too (precompile's docstring promises
    # symmetry): a bare shape tuple must not silently filter everything
    d = _dense(seed=11)
    assert mxcompile.warm_start(d, signatures=[(2, 3, 16)]) == 1
    e = _dense(seed=11)
    assert mxcompile.warm_start(
        e, signatures=[((4, 5, 16), "float32")]) == 1


def test_rewarm_skips_expensive_reload(tmp_path, monkeypatch):
    """Re-warming an already-warm block must not re-pay unpickle +
    executable device-load per entry just to discard it at the
    in-memory dedup check."""
    from mxnet_tpu.compile import aot as aot_mod

    x = mx.nd.ones((2, 3, 16))
    a = _dense(seed=21)
    a(x)
    b = _dense(seed=21)
    assert mxcompile.warm_start(b) == 1
    calls = []
    real = aot_mod._deserialize
    monkeypatch.setattr(
        aot_mod, "_deserialize",
        lambda se, raw: (calls.append(1), real(se, raw))[1])
    assert mxcompile.warm_start(b) == 0
    assert calls == [], "already-installed entry was deserialized again"


def test_warm_start_installs_without_fresh_builds(tmp_path):
    x2, x4 = mx.nd.ones((2, 3, 16)), mx.nd.ones((4, 5, 16))
    a = _dense(seed=5)
    ya2, ya4 = a(x2).asnumpy(), a(x4).asnumpy()

    b = _dense(seed=5)  # fresh block, identical class + params
    installed = mxcompile.warm_start(b)
    assert installed == 2
    builds0 = telemetry.value("cachedop_build_total", {"block": "Dense"})
    yb2, yb4 = b(x2).asnumpy(), b(x4).asnumpy()
    np.testing.assert_allclose(ya2, yb2, rtol=1e-6)
    np.testing.assert_allclose(ya4, yb4, rtol=1e-6)
    assert telemetry.value("cachedop_build_total",
                           {"block": "Dense"}) == builds0, \
        "warm-started signatures must not trigger fresh builds"


def test_warm_start_verify_accepts_matching_program(tmp_path):
    a = _dense(seed=6)
    a(mx.nd.ones((2, 3, 16)))
    b = _dense(seed=6)
    assert mxcompile.warm_start(b, verify=True) == 1


def test_warm_start_rejects_foreign_environment(tmp_path):
    """warm_start never re-lowers, so it must check the environment half
    of the fingerprint explicitly: an entry built under different
    platform/versions/XLA flags is a clean miss, not a silent install."""
    a = _dense(seed=15)
    a(mx.nd.ones((2, 3, 16)))
    cache = mxcompile.get_cache()
    (fp, meta), = cache.entries_for_block(cache_mod.block_signature(a))
    assert meta["env_fingerprint"] == cache.env_fingerprint()
    mpath = os.path.join(cache._entry_dir(fp), META)
    meta["env_fingerprint"] = "0" * 64
    with open(mpath, "w") as f:
        json.dump(meta, f)
    assert mxcompile.warm_start(_dense(seed=15)) == 0
    meta["env_fingerprint"] = cache.env_fingerprint()
    with open(mpath, "w") as f:
        json.dump(meta, f)
    assert mxcompile.warm_start(_dense(seed=15)) == 1


def test_warm_start_is_block_signature_scoped(tmp_path):
    a = _dense(seed=7)
    a(mx.nd.ones((2, 3, 16)))
    other = nn.Dense(8, flatten=False, in_units=16)  # different shape
    other.initialize()
    other.hybridize()
    assert mxcompile.warm_start(other) == 0


def test_warm_start_uninitialized_block_is_zero(tmp_path):
    blk = nn.Dense(4, flatten=False)
    assert mxcompile.warm_start(blk) == 0


def test_warm_start_disabled_is_zero(tmp_path):
    a = _dense(seed=8)
    a(mx.nd.ones((2, 3, 16)))
    mxcompile.disable()
    assert mxcompile.warm_start(_dense(seed=8)) == 0


def test_precompile_requires_enable(tmp_path):
    mxcompile.disable()
    with pytest.raises(RuntimeError, match="disabled"):
        mxcompile.precompile(_dense(), [(2, 3, 16)])


def test_precompile_then_warm_start_roundtrip(tmp_path):
    a = _dense(seed=9)
    n = mxcompile.precompile(a, [(2, 3, 16), (4, 3, 16)])
    assert n == 2
    assert mxcompile.stats()["entries"] == 2
    # a second block precompiling the same signatures restores them
    # from disk: 0 fresh builds, per the documented return contract
    assert mxcompile.precompile(_dense(seed=9),
                                [(2, 3, 16), (4, 3, 16)]) == 0
    b = _dense(seed=9)
    assert mxcompile.warm_start(b) == 2
    y = b(mx.nd.ones((2, 3, 16))).asnumpy()
    np.testing.assert_allclose(y, a(mx.nd.ones((2, 3, 16))).asnumpy(),
                               rtol=1e-6)


def test_warm_start_state_writeback_by_name(tmp_path):
    """AOT-restored executables update running stats through structured
    param names (portable), not process-local ids."""
    def make():
        blk = nn.BatchNorm(in_channels=4)
        blk.initialize()
        blk.hybridize()
        return blk

    x = mx.nd.array(np.random.RandomState(0)
                    .rand(2, 4).astype("float32"))
    a = make()
    a(x)  # inference-mode trace still carries the state plumbing
    b = make()
    if mxcompile.warm_start(b) < 1:
        pytest.skip("BatchNorm signature not portable on this backend")
    b(x)
    np.testing.assert_allclose(
        a.running_mean.data().asnumpy(),
        b.running_mean.data().asnumpy(), rtol=1e-6)


def test_block_signature_tracks_params():
    a, b = _dense(seed=10), _dense(seed=11)
    assert cache_mod.block_signature(a) == cache_mod.block_signature(b)
    wide = nn.Dense(8, flatten=False, in_units=16)
    wide.initialize()
    assert cache_mod.block_signature(wide) != cache_mod.block_signature(a)
    lazy = nn.Dense(4, flatten=False)
    assert cache_mod.block_signature(lazy) is None


# ---------------------------------------------------------------------------
# integration surfaces: feature flag, stats, serve provenance, probe
# ---------------------------------------------------------------------------

def test_runtime_feature_flag_tracks_enablement():
    from mxnet_tpu.runtime import Features

    assert Features()["COMPILE_CACHE"].enabled  # detection is per-build
    mxcompile.disable()
    assert not Features()["COMPILE_CACHE"].enabled


def test_configure_preserves_existing_settings(tmp_path):
    c1 = mxcompile.configure(dir=str(tmp_path / "explicit"),
                             max_bytes=123)
    c2 = mxcompile.configure(max_bytes=456)
    assert c2.root == c1.root, \
        "configure(max_bytes=...) must not repoint the cache dir"
    assert c2.max_bytes == 456
    c3 = mxcompile.configure(dir=str(tmp_path / "other"))
    assert c3.max_bytes == 456
    mxcompile.enable(max_bytes=789)
    assert mxcompile.get_cache().root == c3.root
    assert mxcompile.get_cache().max_bytes == 789


def test_stats_shape_and_clear(tmp_path):
    blk = _dense()
    blk(mx.nd.ones((2, 3, 16)))
    st = mxcompile.stats()
    assert st["entries"] == 1 and st["total_bytes"] > 0
    assert st["dir"] == mxcompile.cache_dir()
    assert json.dumps(st)  # JSON-safe for /statz and diagnose
    mxcompile.clear()
    assert mxcompile.stats()["entries"] == 0


def test_serve_runner_reports_warm_provenance(tmp_path):
    from mxnet_tpu import serve

    blk = _dense(seed=12)
    root = str(tmp_path / "ckpt")
    blk.save_checkpoint(root, step=1)

    def make():
        return nn.Dense(4, flatten=False, in_units=16)

    r1 = serve.ModelRunner(make, root=root, batch_sizes=(2,),
                           sample_shapes=[(3, 16)])
    prov1 = r1.stats()["warm_provenance"]
    assert prov1 and all(v == "fresh" for v in prov1.values())

    # a "restarted server": a new runner over the same checkpoint must
    # reach readiness from the persistent cache, not fresh compiles
    r2 = serve.ModelRunner(make, root=root, batch_sizes=(2,),
                           sample_shapes=[(3, 16)])
    prov2 = r2.stats()["warm_provenance"]
    assert set(prov2) == set(prov1)
    assert all(v in ("warm-start", "cache") for v in prov2.values()), prov2


def test_serve_runner_reports_cache_failed_provenance(tmp_path,
                                                      monkeypatch):
    """A restored executable that fails at call time during warm_up
    must surface as 'cache-failed', not 'warm-start': the jit fallback
    compiled fresh, and /statz claiming a zero-compile restart here
    would be the exact false positive provenance exists to catch."""
    from mxnet_tpu import serve
    from mxnet_tpu.compile import aot as aot_mod

    blk = _dense(seed=15)
    root = str(tmp_path / "ckpt")
    blk.save_checkpoint(root, step=1)

    def make():
        return nn.Dense(4, flatten=False, in_units=16)

    serve.ModelRunner(make, root=root, batch_sizes=(2,),
                      sample_shapes=[(3, 16)])  # populates the cache

    real = aot_mod._deserialize

    def sabotaged(se, raw):
        _cfn, key = real(se, raw)

        def boom(*a, **k):
            raise RuntimeError("rejects inputs")

        return boom, key

    monkeypatch.setattr(aot_mod, "_deserialize", sabotaged)
    r2 = serve.ModelRunner(make, root=root, batch_sizes=(2,),
                           sample_shapes=[(3, 16)])
    prov2 = r2.stats()["warm_provenance"]
    assert prov2 and all(v == "cache-failed" for v in prov2.values()), \
        prov2


def test_warm_provenance_survives_disabled_telemetry(tmp_path):
    """Provenance is read off the cache entries themselves, so /statz
    stays truthful even with telemetry off."""
    from mxnet_tpu import serve

    blk = _dense(seed=14)
    root = str(tmp_path / "ckpt")
    blk.save_checkpoint(root, step=1)
    telemetry.disable()

    def make():
        return nn.Dense(4, flatten=False, in_units=16)

    r1 = serve.ModelRunner(make, root=root, batch_sizes=(2,),
                           sample_shapes=[(3, 16)])
    assert set(r1.stats()["warm_provenance"].values()) == {"fresh"}
    r2 = serve.ModelRunner(make, root=root, batch_sizes=(2,),
                           sample_shapes=[(3, 16)])
    assert all(v in ("warm-start", "cache")
               for v in r2.stats()["warm_provenance"].values())


def test_jax_export_probe_reports_missing_api(monkeypatch):
    from jax import export as jax_export

    from mxnet_tpu.gluon import block as block_mod

    assert block_mod._require_jax_export() is jax_export
    monkeypatch.delattr(jax_export, "symbolic_shape")
    with pytest.raises(MXNetError, match="symbolic_shape"):
        block_mod._require_jax_export()


def test_diagnose_compile_cache_runs(tmp_path, capsys):
    blk = _dense()
    blk(mx.nd.ones((2, 3, 16)))
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    "..", "..", "..", "tools"))
    try:
        import diagnose
    finally:
        sys.path.pop(0)
    diagnose.compile_cache_info()
    out = capsys.readouterr().out
    assert "Compile Cache" in out and "entries" in out
    assert "compile_cache_commit_total" in out


def test_diagnose_section_flags_compose(tmp_path, capsys, monkeypatch):
    """--compile-cache --serve must print BOTH requested sections, not
    silently drop the second one."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    "..", "..", "..", "tools"))
    try:
        import diagnose
    finally:
        sys.path.pop(0)
    snap = tmp_path / "snap.json"
    snap.write_text(json.dumps({"metrics": {}}))
    monkeypatch.setattr(sys, "argv", ["diagnose.py", "--compile-cache",
                                      "--serve", str(snap)])
    diagnose.main()
    out = capsys.readouterr().out
    assert "Compile Cache" in out and "Serving" in out


def test_jax_cache_dir_env_wins_else_fixed_path_in_checkout(monkeypatch):
    """JAX's persistent cache: where JAX_COMPILATION_CACHE_DIR is set no
    other path is named; where it is not, one fixed directory inside the
    checkout (the path is part of every entry's key)."""
    import os

    import jax

    was_dir = jax.config.jax_compilation_cache_dir
    was_min = jax.config.jax_persistent_cache_min_compile_time_secs
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert mxcompile.jax_cache_dir() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == was_dir  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = mxcompile.jax_cache_dir()
        assert fixed == os.path.join(repo, ".jax_cache")
        assert mxcompile.jax_cache_dir() == fixed               # no pid/time
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", was_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          was_min)
