"""mx.serve.cache / mx.serve.spec tests: radix prefix-trie refcount
exactness under insert/match/evict churn (PagePool.check() stays
green), copy-on-write fork on mid-prefix divergence, shared-segment
double-free guards, LRU eviction that never strands a live reader,
cached-prefix decode bit-parity against a cold prefill, greedy
speculative decoding bit-parity against single-step decode, the
``serve_cache`` / ``spec_verify`` fault drills (a poisoned draft
degrades that sequence ALONE), and the cache-labelled TTFT split."""
import random

import pytest

import mxnet_tpu as mx
from mxnet_tpu import serve, telemetry
from mxnet_tpu.resilience import inject
from mxnet_tpu.serve.batching import ServeError
from mxnet_tpu.serve.cache import PrefixCache, prefix_digest
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
    kw.setdefault("max_context", 24)
    kw.setdefault("prefill_lengths", (8, 20))
    kw.setdefault("batch_sizes", (1, 2))
    return serve.DecodeConfig(**kw)


def _pool(pages=16, page_size=4, max_context=64):
    return PagePool(PageConfig(page_size, pages, 2, 2, 4, max_context))


# ---------------------------------------------------------------------------
# trie mechanics on a raw pool (no jax programs involved)
# ---------------------------------------------------------------------------

def test_trie_insert_match_acquire_release_exact_refcounts():
    pool = _pool()
    cache = PrefixCache(pool)
    prompt = list(range(9))              # 2 cacheable blocks + 1 tail
    assert cache.match(prompt) == ([], 0)

    own = pool.alloc("s1", 3)            # 2 prefix pages + 1 private
    adopted = cache.insert(prompt, "s1", list(own), 0)
    assert adopted == 2
    assert cache.stats()["nodes"] == 2
    # adoption MOVED the prefix pages: s1 now owns only the tail page,
    # the trie pages live in the shared segment at refcount 2
    # (trie + the inserting reader)
    assert pool.owners()["s1"] == [own[2]]
    assert pool.shared_refs() == {own[0]: 2, own[1]: 2}
    cache.check()

    # a second reader attaches: refcounts 3, matched_tokens == 8
    shared, hit, cls = cache.acquire(prompt)
    assert (shared, hit, cls) == ([own[0], own[1]], 8, "hit")
    assert pool.shared_refs() == {own[0]: 3, own[1]: 3}

    # readers detach; the trie's own reference keeps the pages shared
    cache.release(shared)
    cache.release([own[0], own[1]])      # the inserting reader's refs
    assert pool.shared_refs() == {own[0]: 1, own[1]: 1}
    pool.release("s1")
    cache.check()

    # final unref (eviction) actually frees
    assert cache.evict(2) == 2
    assert pool.shared_pages == 0 and pool.available == pool.capacity
    pool.check()


def test_trie_cow_fork_on_mid_prefix_divergence():
    pool = _pool()
    cache = PrefixCache(pool)
    a = [1, 2, 3, 4, 5, 6, 7, 8, 9]      # blocks (1..4), (5..8)
    b = [1, 2, 3, 4, 9, 9, 9, 9, 9]      # shares block 0, diverges

    pa = pool.alloc("a", 3)
    assert cache.insert(a, "a", list(pa), 0) == 2
    sh, hit, cls = cache.acquire(b)
    assert hit == 4 and cls == "partial" and sh == [pa[0]]
    pb = pool.alloc("b", 2)              # divergent block + tail
    assert cache.insert(b, "b", [sh[0]] + list(pb), hit) == 1
    # the fork shares the common root: 3 nodes, root page refcount
    # 2 (trie + b's reader — a's insert reference was on it too)
    assert cache.stats()["nodes"] == 3
    refs = pool.shared_refs()
    assert refs[pa[0]] == 3              # trie + a-reader + b-reader
    assert refs[pa[1]] == 2 and refs[pb[0]] == 2
    cache.check()
    # both tails decode off private pages: a's writes can never touch
    # b's view of the shared root
    assert pool.owners() == {"a": [pa[2]], "b": [pb[1]]}
    cache.release([pa[0], pa[1]])
    cache.release([sh[0], pb[0]])
    pool.release("a")
    pool.release("b")
    cache.clear()
    assert pool.available == pool.capacity
    pool.check()


def test_evict_lru_skips_pages_with_live_readers():
    pool = _pool()
    cache = PrefixCache(pool)
    hot = [1] * 9
    cold = [2] * 9
    ph = pool.alloc("h", 3)
    cache.insert(hot, "h", list(ph), 0)
    pc = pool.alloc("c", 3)
    cache.insert(cold, "c", list(pc), 0)
    cache.release([pc[0], pc[1]])        # cold's reader leaves
    pool.release("c")
    # hot still has a live reader (refcount 2): only cold's leaf-up
    # chain is evictable, and eviction frees exactly those 2 pages
    assert cache.evict(100) == 2
    st = cache.stats()
    assert st["nodes"] == 2 and st["evictions"] == 2
    assert set(pool.shared_refs()) == {ph[0], ph[1]}
    cache.check()
    cache.release([ph[0], ph[1]])
    pool.release("h")
    cache.clear()
    pool.check()


def test_invalidate_drops_subtree_but_live_readers_keep_storage():
    pool = _pool()
    cache = PrefixCache(pool)
    prompt = list(range(9))
    pp = pool.alloc("s", 3)
    cache.insert(prompt, "s", list(pp), 0)
    assert cache.invalidate(prompt) == 2
    assert cache.stats()["nodes"] == 0
    assert cache.match(prompt) == ([], 0)
    # the reader's references survive the invalidation: storage only
    # returns to the free list when the LAST reference drops
    assert pool.shared_refs() == {pp[0]: 1, pp[1]: 1}
    assert cache.release([pp[0], pp[1]]) == 2
    pool.release("s")
    assert pool.available == pool.capacity
    pool.check()


def test_shared_segment_double_free_raises():
    pool = _pool()
    cache = PrefixCache(pool)
    pp = pool.alloc("s", 2)
    cache.insert([7] * 5, "s", list(pp), 0)     # one block adopted
    cache.release([pp[0]])               # the inserting reader's ref
    assert cache.evict(1) == 1           # the trie's ref: page freed
    with pytest.raises(ServeError, match="double-free"):
        pool.shared_unref([pp[0]])
    pool.release("s")
    pool.check()


def test_trie_property_churn_keeps_accounting_exact():
    # randomized insert/acquire/release/evict churn over a heavily
    # shared token space; every step must keep the trie audit AND the
    # pool audit green, and teardown must return every page
    rng = random.Random(7)
    pool = _pool(pages=48)
    cache = PrefixCache(pool)
    readers, next_id = [], [0]
    for _ in range(250):
        op = rng.random()
        if op < 0.55:
            n = rng.randrange(5, 20)
            prompt = [rng.randrange(3) for _ in range(n)]
            shared, hit, _cls = cache.acquire(prompt)
            blocks = max(0, (n - 1) // 4)
            own = blocks - len(shared) + 2     # uncached + private
            if not pool.can_alloc(own):
                cache.release(shared)
                cache.evict(own)
                continue
            oid = "s%d" % next_id[0]
            next_id[0] += 1
            table = list(shared) + list(pool.alloc(oid, own))
            adopted = cache.insert(prompt, oid, table, hit)
            readers.append((oid, table[:len(shared) + adopted]))
        elif readers and op < 0.85:
            oid, shared = readers.pop(rng.randrange(len(readers)))
            if shared:
                cache.release(shared)
            pool.release(oid)
        else:
            cache.evict(rng.randrange(1, 4))
        cache.check()                    # trie + pool audit together
    for oid, shared in readers:
        if shared:
            cache.release(shared)
        pool.release(oid)
    cache.clear()
    assert pool.in_use == 0 and pool.shared_pages == 0
    assert pool.available == pool.capacity
    pool.check()


def test_prefix_digest_stability_and_block_sensitivity():
    assert prefix_digest([1, 2, 3]) == prefix_digest((1, 2, 3))
    assert prefix_digest([1, 2, 3]) != prefix_digest([1, 2, 4])
    assert len(prefix_digest(range(64))) == 12


# ---------------------------------------------------------------------------
# cached-prefix decode: bit-parity + accounting end to end
# ---------------------------------------------------------------------------

def _run(runner, prompt, mnt=6, request_id=None):
    sched = serve.DecodeScheduler(runner)
    try:
        return sched.submit(list(prompt), max_new_tokens=mnt,
                            request_id=request_id).result(timeout=60)
    finally:
        sched.stop()


def test_cached_prefix_decode_bit_identical_to_cold():
    prompt = [(i * 7 + 3) % 31 for i in range(17)]   # 4 cacheable blocks
    cold = serve.DecodeRunner(_decoder(seed=0), config=_config())
    ref = _run(cold, prompt)["tokens"]

    runner = serve.DecodeRunner(_decoder(seed=0),
                                config=_config(prefix_cache=True))
    sched = serve.DecodeScheduler(runner)
    try:
        first = sched.submit(list(prompt),
                             max_new_tokens=6).result(timeout=60)
        second = sched.submit(list(prompt),
                              max_new_tokens=6).result(timeout=60)
    finally:
        sched.stop()
    assert first["tokens"] == ref        # cold populate: full prefill
    assert second["tokens"] == ref       # hit: suffix-only prefill
    st = runner.cache.stats()
    assert st["misses"] == 1 and st["hits"] == 1
    assert st["inserted_pages"] == 4 and st["hit_tokens_total"] == 16
    # the hit charged only the suffix (1 token): reference run 17 +
    # cold populate 17 + hit suffix 1
    assert telemetry.value("serve_decode_prefill_tokens_total") == 35
    # TTFT is split by cache class in the Prometheus export
    prom = telemetry.prometheus()
    assert 'serve_decode_ttft_seconds_count{cache="miss"}' in prom
    assert 'serve_decode_ttft_seconds_count{cache="hit"}' in prom
    # drained scheduler released every reader (no owned pages left);
    # only the trie's 4 shared pages remain until clear()
    assert runner.pool.owners() == {}
    assert runner.pool.shared_pages == 4
    assert all(n == 1 for n in runner.pool.shared_refs().values())
    runner.cache.check()
    runner.cache.clear()
    assert runner.pool.available == runner.pool.capacity
    runner.pool.check()


def test_partial_hit_forks_cow_and_stays_correct():
    base = [(i * 5 + 1) % 29 for i in range(17)]
    fork = list(base[:8]) + [(i * 11 + 2) % 29 for i in range(9)]
    cold = serve.DecodeRunner(_decoder(seed=0), config=_config())
    ref = _run(cold, fork)["tokens"]

    runner = serve.DecodeRunner(_decoder(seed=0),
                                config=_config(prefix_cache=True))
    sched = serve.DecodeScheduler(runner)
    try:
        sched.submit(list(base), max_new_tokens=6).result(timeout=60)
        out = sched.submit(list(fork),
                           max_new_tokens=6).result(timeout=60)
    finally:
        sched.stop()
    assert out["tokens"] == ref
    st = runner.cache.stats()
    assert st["partials"] == 1           # 2 of 4 blocks matched
    assert st["nodes"] == 6              # 4 base + 2 divergent-tail
    runner.cache.check()


def test_serve_cache_drill_invalidates_and_reprefills_cold():
    prompt = [(i * 3 + 2) % 31 for i in range(17)]
    runner = serve.DecodeRunner(_decoder(seed=0),
                                config=_config(prefix_cache=True))
    sched = serve.DecodeScheduler(runner)
    try:
        warm = sched.submit(list(prompt),
                            max_new_tokens=6).result(timeout=60)
        inject.plan("serve_cache@drill-1")
        out = sched.submit(list(prompt), max_new_tokens=6,
                           request_id="drill-1").result(timeout=60)
    finally:
        sched.stop()
    # the drilled admission dropped the poisoned prefix, prefilled
    # cold, and REPOPULATED the trie — output identical either way
    assert out["tokens"] == warm["tokens"]
    st = runner.cache.stats()
    assert st["evictions"] >= 4 and st["misses"] == 2
    assert st["nodes"] == 4              # repopulated by the re-prefill
    runner.cache.check()
    runner.cache.clear()
    runner.pool.check()


# ---------------------------------------------------------------------------
# speculative decoding: bit-parity + containment
# ---------------------------------------------------------------------------

def test_speculative_decode_bit_identical_to_single_step():
    prompt = [3, 1, 4, 1, 5]
    vanilla = serve.DecodeRunner(_decoder(seed=0), config=_config())
    ref = _run(vanilla, prompt)["tokens"]

    spec = serve.DecodeRunner(_decoder(seed=0), config=_config(),
                              draft=_decoder(seed=1))
    out = _run(spec, prompt)
    assert out["tokens"] == ref
    st = spec.spec.stats()
    assert st["enabled"] and st["verify_steps"] >= 1
    assert spec.spec.draft.pool.in_use == 0      # draft pages reclaimed


def test_self_speculation_accepts_more_than_one_token_per_step():
    # identical draft == target: every greedy proposal is accepted, so
    # K+... tokens land per verify step — the per-token-cost win
    spec = serve.DecodeRunner(_decoder(seed=0), config=_config(),
                              draft=_decoder(seed=0))
    vanilla = serve.DecodeRunner(_decoder(seed=0), config=_config())
    prompt = [7, 2, 9]
    assert _run(spec, prompt)["tokens"] == \
        _run(vanilla, prompt)["tokens"]
    st = spec.spec.stats()
    assert st["acceptance_rate"] == 1.0
    assert st["accepted_per_step"] > 1.0
    assert st["verify_steps"] < 6        # 6 tokens in < 6 target steps


def test_spec_verify_drill_degrades_one_sequence_alone():
    inject.plan("spec_verify@bad-seq")
    cfg = _config()
    vanilla = serve.DecodeRunner(_decoder(seed=0), config=cfg)
    ref_bad = _run(vanilla, [5, 6, 7])["tokens"]
    ref_good = _run(vanilla, [8, 9, 10, 11])["tokens"]

    spec = serve.DecodeRunner(_decoder(seed=0), config=cfg,
                              draft=_decoder(seed=0))
    sched = serve.DecodeScheduler(spec)
    try:
        fb = sched.submit([5, 6, 7], max_new_tokens=6,
                          request_id="bad-seq")
        fg = sched.submit([8, 9, 10, 11], max_new_tokens=6,
                          request_id="good-seq")
        bad = fb.result(timeout=60)
        good = fg.result(timeout=60)
    finally:
        sched.stop()
    # the poisoned draft cost the drilled sequence its speculation —
    # never its tokens — and its batch-mate kept speculating
    assert bad["tokens"] == ref_bad
    assert good["tokens"] == ref_good
    st = spec.spec.stats()
    assert st["fallbacks"].get("injected") == 1
    assert st["accepted"] > 0            # good-seq still speculated
    assert spec.spec.draft.pool.in_use == 0
    spec.pool.check()


@pytest.mark.parametrize("k,env,want", [
    (None, None, 4),        # the built-in default
    (None, "0", 4),         # 0 in the environment means "not set"
    (None, "6", 6),         # the environment over the default
    (3, "6", 3),            # an explicit K over the environment
    (0, None, 1),           # clamped from below
    (99, None, 16),         # clamped from above
    (None, "40", 16),       # the environment's value is clamped too
])
def test_spec_k_explicit_then_environment_then_four(k, env, want,
                                                    monkeypatch):
    from mxnet_tpu.serve.spec import resolve_k

    if env is None:
        monkeypatch.delenv("MXNET_SERVE_SPEC_K", raising=False)
    else:
        monkeypatch.setenv("MXNET_SERVE_SPEC_K", env)
    assert resolve_k(k) == want


def test_spec_stats_surface_in_runner_stats():
    spec = serve.DecodeRunner(_decoder(seed=0), config=_config(),
                              draft=_decoder(seed=1))
    doc = spec.stats()
    assert doc["spec"]["enabled"] and doc["spec"]["k"] >= 1
    assert doc["cache"] == {"enabled": False}
    plain = serve.DecodeRunner(_decoder(seed=0),
                               config=_config(prefix_cache=True))
    doc = plain.stats()
    assert doc["cache"]["enabled"] and doc["spec"] == {"enabled": False}
