"""mx.serve.decode tests: PagePool invariants (exact accounting, OOM
fast-reject, zero leaked pages after deadline-expired / poisoned /
drained / hot-swapped sequences), paged-decode bit-parity against an
unpaged incremental reference, continuous batching (sequences join and
leave the RUNNING batch mid-flight), <=1 compile per (bucket,
page-config), streamed == collected token sequences, sequence-granular
poison isolation (injected and nonfinite), decode-bucket circuit
breakers, and the HTTP decode surface (collect + chunked streaming,
X-Request-Id echo, /statz decode block)."""
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serve, telemetry
from mxnet_tpu.resilience import inject
from mxnet_tpu.resilience.inject import InjectedFault
from mxnet_tpu.serve.kvcache import PageConfig, PagePool


@pytest.fixture(autouse=True)
def _clean(request):
    telemetry.enable()
    telemetry.reset()
    inject.clear()
    yield
    inject.clear()
    telemetry.enable()
    telemetry.reset()


def _decoder(vocab=32, layers=2, heads=2, dim=4, seed=0, eos_id=None):
    mx.random.seed(seed)
    blk = serve.TinyDecoder(vocab_size=vocab, num_layers=layers,
                            num_heads=heads, head_dim=dim, eos_id=eos_id)
    blk.initialize()
    return blk


def _config(**kw):
    kw.setdefault("page_size", 4)
    kw.setdefault("pool_pages", 32)
    kw.setdefault("max_live", 2)
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("max_context", 16)
    kw.setdefault("prefill_lengths", (8,))
    kw.setdefault("batch_sizes", (1, 2))
    return serve.DecodeConfig(**kw)


class _Gated(serve.DecodeRunner):
    """Real decode runner with deterministic failure/latency knobs."""

    def __init__(self, *a, **k):
        self.step_delay = 0.0
        self.fail_decode = 0
        self.fail_prefill = 0
        super().__init__(*a, **k)

    def decode_step(self, seqs):
        if self.step_delay:
            time.sleep(self.step_delay)
        if self.fail_decode > 0:
            self.fail_decode -= 1
            raise RuntimeError("injected decode failure")
        return super().decode_step(seqs)

    def prefill(self, seq):
        if self.fail_prefill > 0:
            self.fail_prefill -= 1
            raise RuntimeError("injected prefill failure")
        return super().prefill(seq)


# ---------------------------------------------------------------------------
# PagePool invariants
# ---------------------------------------------------------------------------

def _pool(pages=8, page_size=4, max_context=16):
    return PagePool(PageConfig(page_size, pages, 2, 2, 4, max_context))


def test_page_pool_exact_accounting():
    pool = _pool()
    assert pool.capacity == 8 and pool.available == 8 and pool.in_use == 0
    a = pool.alloc("a", 3)
    b = pool.alloc("b", 2)
    assert len(a) == 3 and len(b) == 2
    assert not set(a) & set(b), "pages double-assigned"
    assert pool.in_use == 5 and pool.available == 3
    assert pool.high_water == 5
    assert pool.release("a") == 3
    assert pool.in_use == 2 and pool.available == 6
    assert pool.high_water == 5            # high water sticks
    pool.check()
    pool.release("b")
    assert pool.in_use == 0
    pool.check()


def test_page_pool_oom_fast_reject_is_all_or_nothing():
    pool = _pool(pages=4)
    pool.alloc("a", 3)
    with pytest.raises(serve.PagePoolExhausted):
        pool.alloc("b", 2)                 # only 1 free
    assert pool.in_use == 3 and pool.available == 1
    assert pool.oom_rejects == 1
    assert "b" not in pool.owners()        # nothing partially reserved
    pool.check()


def test_page_pool_double_free_and_unknown_owner_raise():
    pool = _pool()
    pool.alloc("a", 2)
    pool.release("a")
    with pytest.raises(serve.ServeError):
        pool.release("a")
    with pytest.raises(serve.ServeError):
        pool.release("never-allocated")
    with pytest.raises(serve.ServeError):
        pool.alloc("b", 2) and pool.alloc("b", 1)   # duplicate owner


@pytest.mark.parametrize("max_live,want", [
    (1, (1,)), (3, (1, 2, 3)), (8, (1, 2, 4, 8)), (12, (1, 2, 4, 8, 12)),
    (32, (1, 2, 4, 8, 16, 32))])
def test_default_batch_buckets_are_powers_of_two_closed_by_max_live(
        max_live, want):
    cfg = serve.DecodeConfig(max_live=max_live, max_context=16,
                             prefill_lengths=(8,))
    assert cfg.batch_sizes == want


def test_explicit_batch_buckets_must_cover_max_live():
    cfg = serve.DecodeConfig(max_live=4, max_context=16,
                             prefill_lengths=(8,), batch_sizes=(4, 2, 4))
    assert cfg.batch_sizes == (2, 4)       # taken as given, sorted
    with pytest.raises(ValueError, match="max_live=8"):
        serve.DecodeConfig(max_live=8, max_context=16,
                           prefill_lengths=(8,), batch_sizes=(2, 4))


def test_page_config_limits():
    cfg = PageConfig(4, 8, 2, 2, 4, 16)
    assert cfg.pages_per_seq == 4
    assert cfg.pages_for(1) == 1 and cfg.pages_for(4) == 1
    assert cfg.pages_for(5) == 2 and cfg.pages_for(16) == 4
    with pytest.raises(ValueError):
        PageConfig(4, 2, 2, 2, 4, 16)      # max_context > pool


def test_runner_given_ctx_keeps_weights_probe_and_pool_there():
    """A runner given a context lives on that device — not on JAX's
    default one (virtual device 2 here stands in for a chip that is not
    the default)."""
    import jax

    ctx = mx.cpu(2)
    dev = ctx.jax_device
    assert dev != jax.devices()[0]
    mx.random.seed(0)
    blk = serve.TinyDecoder(vocab_size=32, num_layers=2, num_heads=2,
                            head_dim=4)
    blk.initialize(ctx=ctx)
    runner = serve.DecodeRunner(blk, ctx=ctx, config=_config())
    for a in list(runner._params.values()) + [runner.pool.k, runner.pool.v]:
        assert a.devices() == {dev}
    sched = serve.DecodeScheduler(runner)
    try:
        got = sched.submit([1, 2, 3], max_new_tokens=4).result(timeout=60)
    finally:
        sched.stop()
    assert len(got["tokens"]) == 4
    assert runner.pool.k.devices() == {dev}          # donated and re-bound
    runner.pool.reset_storage()
    assert runner.pool.k.devices() == {dev}


# ---------------------------------------------------------------------------
# correctness: paged continuous decode == unpaged incremental reference
# ---------------------------------------------------------------------------

def _reference_decode(blk, prompt, n):
    """Greedy decode WITHOUT paging: contiguous cache, one block call
    per token through the plain gluon path."""
    from mxnet_tpu import nd

    L, H, D = blk.num_layers, blk.num_kv_heads, blk.head_dim
    zero = nd.zeros((1, L, 0, H, D))
    logits, kn, vn = blk(
        nd.array(np.array([prompt], np.int32)), zero, zero,
        nd.array(np.array([0], np.int32)),
        nd.array(np.array([len(prompt)], np.int32)))
    ks, vs = kn.asnumpy(), vn.asnumpy()        # [1, T, L, H, D]
    out = [int(np.argmax(logits.asnumpy()[0]))]
    for _ in range(n - 1):
        kc = nd.array(ks.transpose(0, 2, 1, 3, 4))
        vc = nd.array(vs.transpose(0, 2, 1, 3, 4))
        logits, kn, vn = blk(
            nd.array(np.array([[out[-1]]], np.int32)), kc, vc,
            nd.array(np.array([ks.shape[1]], np.int32)),
            nd.array(np.array([1], np.int32)))
        ks = np.concatenate([ks, kn.asnumpy()], axis=1)
        vs = np.concatenate([vs, vn.asnumpy()], axis=1)
        out.append(int(np.argmax(logits.asnumpy()[0])))
    return out


def test_paged_decode_matches_unpaged_reference():
    blk = _decoder()
    runner = serve.DecodeRunner(blk, config=_config())
    sched = serve.DecodeScheduler(runner)
    try:
        for prompt in ([1, 2, 3], [5], [7, 8, 9, 10, 11]):
            got = sched.submit(prompt, max_new_tokens=6).result(timeout=60)
            assert got["tokens"] == _reference_decode(blk, prompt, 6)
            assert got["finish_reason"] == "length"
    finally:
        sched.stop()
    assert runner.pool.in_use == 0
    runner.pool.check()


def test_concurrent_sequences_are_independent():
    """Two sequences decoding in one batch must produce exactly what
    each produces alone (slot padding / page gathers don't leak)."""
    blk = _decoder(seed=3)
    runner = serve.DecodeRunner(blk, config=_config())
    sched = serve.DecodeScheduler(runner)
    try:
        f1 = sched.submit([1, 2, 3], max_new_tokens=6)
        f2 = sched.submit([9, 4], max_new_tokens=6)
        got1 = f1.result(timeout=60)["tokens"]
        got2 = f2.result(timeout=60)["tokens"]
    finally:
        sched.stop()
    assert got1 == _reference_decode(blk, [1, 2, 3], 6)
    assert got2 == _reference_decode(blk, [9, 4], 6)


def test_eos_stops_generation():
    blk = _decoder()
    runner = serve.DecodeRunner(blk, config=_config())
    sched = serve.DecodeScheduler(runner)
    try:
        ref = sched.submit([1, 2, 3], max_new_tokens=6).result(60)
        eos = ref["tokens"][2]
        got = sched.submit([1, 2, 3], max_new_tokens=6,
                           eos_id=eos).result(60)
        assert got["finish_reason"] == "eos"
        assert got["tokens"] == ref["tokens"][:3]
    finally:
        sched.stop()


# ---------------------------------------------------------------------------
# admission validation
# ---------------------------------------------------------------------------

def test_submit_validation():
    runner = serve.DecodeRunner(_decoder(), config=_config())
    sched = serve.DecodeScheduler(runner)
    try:
        with pytest.raises(serve.DecodeError):
            sched.submit([])                           # empty prompt
        with pytest.raises(serve.DecodeError):
            sched.submit([99])                         # out of vocab
        with pytest.raises(serve.DecodeError):
            sched.submit([1], max_new_tokens=0)
        with pytest.raises(serve.DecodeError):
            sched.submit([1] * 9)          # beyond largest prefill bucket
        with pytest.raises(serve.DecodeError):
            sched.submit([1] * 12, max_new_tokens=6)   # > max_context
    finally:
        sched.stop()


def test_admission_queue_backpressure():
    runner = _Gated(_decoder(), config=_config(max_live=1, queue_depth=1,
                                               batch_sizes=(1,)))
    runner.step_delay = 0.02
    sched = serve.DecodeScheduler(runner)
    try:
        a = sched.submit([1, 2], max_new_tokens=6)
        # wait until A is admitted (occupies the only slot)
        for _ in range(200):
            if sched.stats()["live"]:
                break
            time.sleep(0.005)
        b = sched.submit([1, 2], max_new_tokens=6)     # waits (depth 1)
        with pytest.raises(serve.ServerOverloaded):
            sched.submit([1, 2], max_new_tokens=6)
        assert a.result(60) and b.result(60)
    finally:
        sched.stop()
    assert runner.pool.in_use == 0


# ---------------------------------------------------------------------------
# compile-once per bucket
# ---------------------------------------------------------------------------

def test_at_most_one_compile_per_bucket_and_none_on_the_hot_path():
    runner = serve.DecodeRunner(_decoder(), config=_config())
    labels = list(runner.provenance())
    assert sorted(labels) == ["decode:b1", "decode:b2", "prefill:t8"]
    for label in labels:
        n = telemetry.value("serve_decode_compile_total",
                            labels={"bucket": label})
        assert n <= 1, "bucket %s compiled %d times in warm-up" % (label,
                                                                   n)
    before = telemetry.value("serve_decode_compile_total")
    sched = serve.DecodeScheduler(runner)
    try:
        futs = [sched.submit([1 + i, 2], max_new_tokens=6)
                for i in range(4)]
        for f in futs:
            f.result(timeout=60)
    finally:
        sched.stop()
    assert telemetry.value("serve_decode_compile_total") == before, \
        "compile escaped onto the decode hot path"


# ---------------------------------------------------------------------------
# continuous batching: join/leave mid-flight
# ---------------------------------------------------------------------------

def test_sequences_join_and_leave_the_running_batch():
    runner = _Gated(_decoder(), config=_config(
        max_new_tokens=40, pool_pages=32, max_context=48,
        prefill_lengths=(8,), batch_sizes=(1, 2), max_live=2))
    runner.step_delay = 0.005
    sched = serve.DecodeScheduler(runner)
    try:
        a = sched.submit([1, 2, 3], max_new_tokens=30, request_id="A")
        for _ in range(400):                 # A mid-generation
            live = sched.stats()["live"]
            if live and live[0]["generated"] >= 3:
                break
            time.sleep(0.005)
        else:
            raise AssertionError("A never started generating")
        b = sched.submit([4, 5], max_new_tokens=3, request_id="B")
        a.result(timeout=60)
        b.result(timeout=60)
    finally:
        sched.stop()
    rec = {r["request_id"]: r for r in sched.recent()}
    ra, rb = rec["A"], rec["B"]
    # B joined the RUNNING batch strictly between A's join and leave,
    # and left while A was still decoding: iteration-level scheduling,
    # asserted from the scheduler's own step ledger
    assert ra["joined_step"] < rb["joined_step"] < ra["left_step"]
    assert rb["left_step"] < ra["left_step"]
    assert runner.pool.in_use == 0
    runner.pool.check()


# ---------------------------------------------------------------------------
# streaming
# ---------------------------------------------------------------------------

def test_streamed_tokens_bit_identical_to_collected():
    runner = serve.DecodeRunner(_decoder(), config=_config())
    sched = serve.DecodeScheduler(runner)
    try:
        collected = sched.submit([1, 2, 3],
                                 max_new_tokens=6).result(60)["tokens"]
        streamed = []
        fut = sched.submit([1, 2, 3], max_new_tokens=6,
                           on_token=lambda t, i: streamed.append((i, t)))
        final = fut.result(timeout=60)["tokens"]
    finally:
        sched.stop()
    assert [t for _i, t in streamed] == final == collected
    assert [i for i, _t in streamed] == list(range(len(final)))


def test_sick_stream_consumer_does_not_stall_decode():
    runner = serve.DecodeRunner(_decoder(), config=_config())
    sched = serve.DecodeScheduler(runner)
    try:
        def bad_cb(tok, i):
            raise RuntimeError("consumer died")

        got = sched.submit([1, 2, 3], max_new_tokens=6,
                           on_token=bad_cb).result(timeout=60)
        assert len(got["tokens"]) == 6
    finally:
        sched.stop()


# ---------------------------------------------------------------------------
# deadlines / drain / shutdown — zero pages leaked
# ---------------------------------------------------------------------------

def test_deadline_expires_mid_generation_pages_reclaimed():
    runner = _Gated(_decoder(), config=_config(
        max_new_tokens=60, max_context=64, pool_pages=32))
    runner.step_delay = 0.05
    sched = serve.DecodeScheduler(runner)
    try:
        fut = sched.submit([1, 2, 3], max_new_tokens=50, timeout_ms=150)
        with pytest.raises(serve.RequestTimeout):
            fut.result(timeout=60)
    finally:
        sched.stop()
    assert runner.pool.in_use == 0, "expired sequence leaked pages"
    runner.pool.check()
    assert sched.evictions.get("timeout") == 1
    assert telemetry.value("serve_requests_total",
                           labels={"result": "timeout"}) == 1


def test_drain_serves_queued_then_stops_and_rejects_after_close():
    runner = serve.DecodeRunner(_decoder(), config=_config())
    sched = serve.DecodeScheduler(runner)
    futs = [sched.submit([1 + i], max_new_tokens=4) for i in range(4)]
    assert sched.stop(drain=True, timeout=60)
    for f in futs:
        assert len(f.result(timeout=1)["tokens"]) == 4
    with pytest.raises(serve.ServerClosed):
        sched.submit([1])
    assert runner.pool.in_use == 0


def test_abort_shutdown_cancels_and_reclaims():
    runner = _Gated(_decoder(), config=_config(max_new_tokens=60,
                                               max_context=64))
    runner.step_delay = 0.02
    sched = serve.DecodeScheduler(runner)
    fut = sched.submit([1, 2], max_new_tokens=50)
    for _ in range(200):
        if sched.stats()["live"]:
            break
        time.sleep(0.005)
    assert sched.stop(drain=False, timeout=60)
    with pytest.raises(serve.ServerClosed):
        fut.result(timeout=1)
    assert runner.pool.in_use == 0, "cancelled sequence leaked pages"
    runner.pool.check()


# ---------------------------------------------------------------------------
# poison isolation at sequence granularity
# ---------------------------------------------------------------------------

def test_injected_poison_sequence_fails_alone_pages_reclaimed():
    inject.plan("serve_poison@poison-x")
    runner = serve.DecodeRunner(_decoder(), config=_config(max_live=2))
    sched = serve.DecodeScheduler(runner)
    try:
        good1 = sched.submit([1, 2], max_new_tokens=6, request_id="ok-1")
        bad = sched.submit([3, 4], max_new_tokens=6,
                           request_id="poison-x")
        good2 = sched.submit([5, 6], max_new_tokens=6, request_id="ok-2")
        with pytest.raises(InjectedFault):
            bad.result(timeout=60)
        assert len(good1.result(timeout=60)["tokens"]) == 6
        assert len(good2.result(timeout=60)["tokens"]) == 6
    finally:
        sched.stop()
    assert telemetry.value("serve_poison_requests_total") >= 1
    assert telemetry.value("serve_requests_total",
                           labels={"result": "poisoned"}) == 1
    assert runner.pool.in_use == 0, "poisoned sequence leaked pages"
    runner.pool.check()


def test_nonfinite_sequence_evicted_alone_batchmates_complete():
    blk = _decoder(seed=1)
    # poison ONE embedding row: any prompt containing token 9 goes NaN
    w = blk.embed.weight
    data = np.array(w.data().asnumpy())
    data[9] = np.nan
    w.set_data(mx.nd.array(data))
    runner = serve.DecodeRunner(blk, config=_config(max_live=2))
    sched = serve.DecodeScheduler(runner)
    try:
        bad = sched.submit([9, 1], max_new_tokens=6, request_id="nan-1")
        good = sched.submit([1, 2], max_new_tokens=6, request_id="ok-1")
        with pytest.raises(serve.DecodeError, match="nonfinite"):
            bad.result(timeout=60)
        got = good.result(timeout=60)
        assert got["tokens"] == _reference_decode(blk, [1, 2], 6)
    finally:
        sched.stop()
    assert telemetry.value("serve_nonfinite_outputs_total") > 0
    assert telemetry.value("serve_poison_requests_total") >= 1
    assert runner.pool.in_use == 0
    runner.pool.check()


def test_injected_dispatch_fault_is_transient_nobody_evicted():
    inject.plan("serve_dispatch@*:transient")
    runner = serve.DecodeRunner(_decoder(), config=_config())
    sched = serve.DecodeScheduler(runner)
    try:
        got = sched.submit([1, 2], max_new_tokens=6).result(timeout=60)
        assert len(got["tokens"]) == 6      # retried next iteration
    finally:
        sched.stop()
    assert telemetry.value("resilience_faults_injected_total",
                           labels={"site": "serve_dispatch"}) == 1


def test_real_decode_failure_bisects_to_the_failing_half():
    """A decode-step failure while 2 sequences are live retries
    bisected: both singles succeed (the failure was batch-level
    transient), nobody is evicted."""
    runner = _Gated(_decoder(), config=_config(max_live=2))
    sched = serve.DecodeScheduler(runner, start=False)
    f = []
    orig = serve.DecodeRunner.decode_step

    def flaky(self, seqs):
        if len(seqs) > 1 and not f:
            f.append(1)
            raise RuntimeError("batch-level glitch")
        return orig(self, seqs)

    runner.decode_step = flaky.__get__(runner)
    sched.start()
    try:
        a = sched.submit([1, 2], max_new_tokens=6)
        b = sched.submit([3, 4], max_new_tokens=6)
        assert len(a.result(60)["tokens"]) == 6
        assert len(b.result(60)["tokens"]) == 6
    finally:
        sched.stop()
    assert telemetry.value("serve_bisect_splits_total") >= 1
    assert runner.pool.in_use == 0


# ---------------------------------------------------------------------------
# circuit breakers on decode buckets
# ---------------------------------------------------------------------------

def test_prefill_breaker_quarantines_after_repeated_failures():
    from mxnet_tpu.serve.breaker import BreakerBoard

    runner = _Gated(_decoder(), config=_config())
    runner.fail_prefill = 99
    board = BreakerBoard(threshold=2, cooldown=60.0)
    sched = serve.DecodeScheduler(runner, breakers=board)
    try:
        for _ in range(2):
            with pytest.raises(RuntimeError):
                sched.submit([1, 2], max_new_tokens=4).result(timeout=60)
        assert board.snapshot()["('prefill', 8)"]["state"] == "open"
        with pytest.raises(serve.BucketQuarantined):
            sched.submit([1, 2], max_new_tokens=4)
    finally:
        sched.stop()
    assert runner.pool.in_use == 0, "failed prefills leaked pages"


def test_decode_bucket_breaker_trips_and_bisect_isolates_one():
    """Two live sequences; the batch dispatch AND the first bisected
    single fail (2 planned failures): the failing sequence is evicted
    alone as poisoned, its batch-mate keeps decoding to completion,
    and the 2-bucket's breaker records the strike."""
    from mxnet_tpu.serve.breaker import BreakerBoard

    runner = _Gated(_decoder(), config=_config(
        batch_sizes=(1, 2), max_new_tokens=20, max_context=32))
    runner.step_delay = 0.02          # keep the batch alive while arming
    board = BreakerBoard(threshold=1, cooldown=0.05)
    sched = serve.DecodeScheduler(runner, breakers=board)
    try:
        a = sched.submit([1, 2], max_new_tokens=12, request_id="A")
        b = sched.submit([3, 4], max_new_tokens=12, request_id="B")
        # arm once both are admitted so the failures hit a 2-batch
        for _ in range(400):
            if len(sched.stats()["live"]) == 2:
                break
            time.sleep(0.005)
        runner.fail_decode = 2
        results = []
        for fut in (a, b):
            try:
                results.append(fut.result(timeout=60)["tokens"])
            except RuntimeError:
                results.append(None)
        assert sorted(r is None for r in results) == [False, True], \
            "exactly one sequence must fail, its mate completes"
        done = next(r for r in results if r is not None)
        assert len(done) == 12
        snap = sched.stats()["breakers"]
        assert snap["('decode', 2)"]["trips"] >= 1
    finally:
        sched.stop()
    assert telemetry.value("serve_poison_requests_total") >= 1
    assert runner.pool.in_use == 0
    runner.pool.check()


def test_quarantined_largest_bucket_chunks_with_rotation():
    """With the largest decode bucket quarantined, the live set steps
    in smaller chunks and ROTATES so every sequence keeps progressing
    (no starvation of the tail for the whole cooldown)."""
    from mxnet_tpu.serve.breaker import BreakerBoard

    runner = serve.DecodeRunner(_decoder(), config=_config(
        max_live=3, batch_sizes=(1, 2, 4), pool_pages=32))
    board = BreakerBoard(threshold=1, cooldown=300.0)
    board.failure(("decode", 4))          # largest bucket: open
    board.failure(("decode", 3))          # (not a bucket; harmless)
    sched = serve.DecodeScheduler(runner, breakers=board)
    try:
        futs = [sched.submit([1 + i, 2], max_new_tokens=6)
                for i in range(3)]
        for f in futs:
            assert len(f.result(timeout=60)["tokens"]) == 6, \
                "a sequence starved behind the quarantined bucket"
    finally:
        sched.stop()
    assert runner.pool.in_use == 0


def test_dropped_scheduler_thread_winds_down():
    """A scheduler dropped without stop() must not be pinned forever
    by its own daemon thread (the device-resident KV pool rides on
    it); the weak loop ref lets GC take it and the thread exit."""
    import gc
    import weakref

    runner = serve.DecodeRunner(_decoder(), config=_config())
    sched = serve.DecodeScheduler(runner)
    assert len(sched.submit([1, 2], max_new_tokens=4)
               .result(timeout=60)["tokens"]) == 4
    t = sched._thread
    wr = weakref.ref(sched)
    del sched, runner
    gc.collect()
    t.join(timeout=5.0)
    assert not t.is_alive(), "decode loop thread pinned a dead scheduler"
    gc.collect()
    assert wr() is None, "scheduler (and its KV pool) leaked"


# ---------------------------------------------------------------------------
# hot swap
# ---------------------------------------------------------------------------

def test_hot_swap_finishes_live_on_old_runner_no_leaks():
    blk_a, blk_b = _decoder(seed=0), _decoder(seed=7)
    ra = _Gated(blk_a, config=_config(max_new_tokens=20, max_context=32))
    ra.step_delay = 0.01
    rb = serve.DecodeRunner(blk_b, config=_config())
    sched = serve.DecodeScheduler(ra)
    try:
        a = sched.submit([1, 2], max_new_tokens=15)
        for _ in range(400):
            if sched.stats()["live"]:
                break
            time.sleep(0.005)
        sched.swap(rb)
        b = sched.submit([1, 2], max_new_tokens=6)   # admitted on B
        got_a = a.result(timeout=60)["tokens"]
        got_b = b.result(timeout=60)["tokens"]
    finally:
        sched.stop()
    assert got_a == _reference_decode(blk_a, [1, 2], 15), \
        "live sequence must finish on the OLD model"
    assert got_b == _reference_decode(blk_b, [1, 2], 6), \
        "post-swap admission must run on the NEW model"
    assert ra.pool.in_use == 0 and rb.pool.in_use == 0
    ra.pool.check()
    rb.pool.check()
    assert sched.runner is rb


# ---------------------------------------------------------------------------
# Server integration + HTTP surface
# ---------------------------------------------------------------------------

def test_server_decode_only_http_collect_stream_and_statz():
    runner = serve.DecodeRunner(_decoder(), config=_config())
    srv = serve.Server(decode=runner)
    try:
        assert srv.ready() and srv.healthy()
        host, port = srv.start_http()
        base = "http://%s:%d" % (host, port)
        with urllib.request.urlopen(base + "/readyz", timeout=10) as r:
            assert json.load(r)["ready"]
        req = urllib.request.Request(
            base + "/predict",
            data=json.dumps({"tokens": [1, 2, 3],
                             "max_new_tokens": 5}).encode(),
            headers={"X-Request-Id": "http-1"})
        with urllib.request.urlopen(req, timeout=30) as r:
            collected = json.load(r)
            assert r.headers.get("X-Request-Id") == "http-1"
        req = urllib.request.Request(
            base + "/predict?stream=1",
            data=json.dumps({"tokens": [1, 2, 3],
                             "max_new_tokens": 5}).encode(),
            headers={"X-Request-Id": "http-2"})
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.headers.get("X-Request-Id") == "http-2"
            events = [json.loads(line) for line in r.read().splitlines()]
        tokens = [e["token"] for e in events if "token" in e]
        done = events[-1]
        assert done["done"] and done["finish_reason"] == "length"
        assert tokens == done["tokens"] == collected["tokens"]
        # bad request mapping: static limits are 400s
        req = urllib.request.Request(
            base + "/predict",
            data=json.dumps({"tokens": [1] * 50}).encode())
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=30)
        assert err.value.code == 400
        with urllib.request.urlopen(base + "/statz", timeout=10) as r:
            stats = json.load(r)
        dec = stats["decode"]
        assert dec["runner"]["pool"]["in_use_pages"] == 0
        assert dec["runner"]["pool"]["high_water_pages"] > 0
        assert set(dec["runner"]["buckets"]) == {
            "decode:b1", "decode:b2", "prefill:t8"}
        assert stats["runner"] is None     # decode-only server
    finally:
        srv.shutdown()


def test_server_with_both_planes():
    from mxnet_tpu.gluon import nn

    def vision_factory():
        return nn.Dense(4, flatten=False, in_units=16)

    vb = vision_factory()
    vb.initialize()
    vb(mx.nd.zeros((1, 2, 16)))
    import tempfile

    root = tempfile.mkdtemp(prefix="mx-decode-test-")
    vb.save_checkpoint(root, step=1)
    cfg = serve.ServeConfig(max_batch_size=4, batch_sizes=(4,),
                            sample_shapes=[(8, 16)])
    runner = serve.DecodeRunner(_decoder(), config=_config())
    srv = serve.Server(vision_factory, root=root, config=cfg,
                       decode=runner)
    try:
        assert srv.ready()
        x = np.random.RandomState(0).rand(3, 16).astype("float32")
        np.testing.assert_allclose(
            srv.submit(x), vb(mx.nd.array(x[None])).asnumpy()[0],
            rtol=2e-5, atol=1e-6)
        got = srv.submit_decode([1, 2], max_new_tokens=4).result(60)
        assert len(got["tokens"]) == 4
        stats = srv.stats()
        assert stats["runner"] is not None and stats["decode"] is not None
    finally:
        srv.shutdown()
    assert runner.pool.in_use == 0


def test_shared_config_not_mutated_by_runner_eos():
    cfg = _config()
    blk = _decoder(eos_id=2)
    runner = serve.DecodeRunner(blk, config=cfg)
    assert runner.eos_id == 2          # model default adopted
    assert cfg.eos_id is None, \
        "runner absorbed its model's eos_id into the SHARED config"
    other = serve.DecodeRunner(_decoder(), config=cfg)
    assert other.eos_id is None        # second model: no leaked eos


def test_prebuilt_runner_with_decode_config_raises():
    runner = serve.DecodeRunner(_decoder(), config=_config())
    with pytest.raises(ValueError, match="decode_config"):
        serve.Server(decode=runner, decode_config=_config())


def test_decode_env_vars_registered():
    from mxnet_tpu import config

    for var in ("MXNET_SERVE_DECODE_PAGE_SIZE",
                "MXNET_SERVE_DECODE_POOL_PAGES",
                "MXNET_SERVE_DECODE_MAX_LIVE",
                "MXNET_SERVE_DECODE_MAX_NEW",
                "MXNET_SERVE_DECODE_STREAM"):
        assert var in config.ENV_VARS, var


def test_decode_telemetry_families_in_prometheus_export():
    runner = serve.DecodeRunner(_decoder(), config=_config())
    sched = serve.DecodeScheduler(runner)
    try:
        sched.submit([1, 2], max_new_tokens=4).result(timeout=60)
    finally:
        sched.stop()
    prom = telemetry.prometheus()
    for fam in ("serve_decode_tokens_total", "serve_decode_steps_total",
                "serve_decode_batch_size", "serve_decode_ttft_seconds",
                "serve_decode_token_seconds", "serve_decode_compile_total",
                "serve_decode_evictions_total", "serve_kv_pages_in_use"):
        assert "# TYPE %s" % fam in prom, fam
    assert telemetry.value("serve_decode_tokens_total") == 4
    assert telemetry.value("serve_decode_prefills_total") == 1


def test_shutdown_drain_finishes_inflight_stream():
    # regression: shutdown(drain=True) used to close the HTTP listener
    # before the daemon stream threads finished writing, so a client
    # mid-stream saw its socket die with tokens still owed.  Drain must
    # hold the listener open until every in-flight stream has written
    # its terminal event.
    runner = serve.DecodeRunner(_decoder(),
                                config=_config(max_new_tokens=8,
                                               max_context=24))
    slow = runner.decode_step

    def _slow(seqs):
        time.sleep(0.1)
        return slow(seqs)

    runner.decode_step = _slow
    srv = serve.Server(decode=runner)
    ref = srv.submit_decode([1, 2, 3], max_new_tokens=8).result(60)
    host, port = srv.start_http()
    got = {}

    def client():
        req = urllib.request.Request(
            "http://%s:%d/predict?stream=1" % (host, port),
            data=json.dumps({"tokens": [1, 2, 3],
                             "max_new_tokens": 8}).encode())
        with urllib.request.urlopen(req, timeout=60) as r:
            got["events"] = [json.loads(line)
                             for line in r.read().splitlines()]

    t = threading.Thread(target=client)
    t.start()
    # wait until the stream is genuinely in flight, then drain
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and not srv._streams:
        time.sleep(0.01)
    assert srv._streams, "stream never started"
    t0 = time.monotonic()
    srv.shutdown(drain=True)
    t.join(timeout=60)
    assert not t.is_alive(), "client still blocked after drain"
    events = got.get("events")
    assert events, "client saw no events (socket closed under it)"
    tokens = [e["token"] for e in events if "token" in e]
    assert events[-1].get("done"), events[-1]
    assert tokens == ref["tokens"], (tokens, ref["tokens"])
    # and the drain actually waited for the stream, not just raced it
    assert srv._streams == 0
    assert time.monotonic() - t0 >= 0.0
