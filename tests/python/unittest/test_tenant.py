"""mx.tenant tests: batched multi-adapter LoRA banks (one compiled
decode program serves a mixed 8-adapter batch; hot add/remove swaps
slots with ZERO recompiles, telemetry-asserted), per-adapter
bit-parity against the dense-merged per-tenant reference, WFQ
virtual-time fairness (weight ratios + deterministic admission
order), per-tenant quota backpressure (503-shaped TenantQuotaExceeded
that never head-of-line blocks), poisoned-adapter quarantine leaving
batch-mates byte-identical, adapter checkpoint save/load, and the
/statz + env-var + runtime-feature surfaces."""
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serve, telemetry, tenant
from mxnet_tpu.serve.breaker import BreakerBoard
from mxnet_tpu.tenant import (AdapterBank, AdapterError, AdapterSpec,
                              FairQueue, QuotaLedger, TenantConfig,
                              TenantPlane, TenantQuota,
                              TenantQuotaExceeded, UnknownTenant)

UNITS = 8          # TinyDecoder num_heads=2 * head_dim=4
TARGETS = ("q0", "v0", "q1", "v1")


@pytest.fixture(autouse=True)
def _clean():
    telemetry.enable()
    telemetry.reset()
    yield
    telemetry.enable()
    telemetry.reset()


def _decoder(seed=0, vocab=32):
    mx.random.seed(seed)
    blk = serve.TinyDecoder(vocab_size=vocab, num_layers=2,
                            num_heads=2, head_dim=4)
    blk.initialize()
    return blk


def _config(**kw):
    kw.setdefault("page_size", 4)
    kw.setdefault("pool_pages", 64)
    kw.setdefault("max_live", 2)
    kw.setdefault("max_new_tokens", 4)
    kw.setdefault("max_context", 16)
    kw.setdefault("prefill_lengths", (8,))
    kw.setdefault("batch_sizes", (2,))
    return serve.DecodeConfig(**kw)


def _spec(name, rank=2, alpha=4.0, seed=0, amp=0.5):
    rs = np.random.RandomState(seed)
    targets = {t: (rs.randn(UNITS, rank).astype(np.float32) * amp,
                   rs.randn(rank, UNITS).astype(np.float32) * amp)
               for t in TARGETS}
    return AdapterSpec(name, rank, alpha, targets)


# ---------------------------------------------------------------------------
# AdapterSpec / checkpoint roundtrip
# ---------------------------------------------------------------------------

def test_adapter_spec_validation():
    spec = _spec("a", rank=2, alpha=4.0)
    assert spec.scale == 2.0
    with pytest.raises(AdapterError, match="rank"):
        AdapterSpec("bad", 0, 1.0,
                    {"q0": (np.zeros((8, 1)), np.zeros((1, 8)))})
    with pytest.raises(AdapterError, match="rank mismatch"):
        AdapterSpec("bad", 4, 1.0,
                    {"q0": (np.zeros((8, 2)), np.zeros((2, 8)))})
    with pytest.raises(AdapterError, match="2-D"):
        AdapterSpec("bad", 2, 1.0,
                    {"q0": (np.zeros((8, 2, 1)), np.zeros((2, 8)))})
    with pytest.raises(AdapterError, match="targets no matrices"):
        AdapterSpec("bad", 2, 1.0, {})


def test_save_load_adapter_roundtrip(tmp_path):
    root = str(tmp_path / "adapter")
    spec = _spec("acme", rank=3, alpha=6.0, seed=5)
    tenant.save_adapter(root, spec, step=2)
    got = tenant.load_adapter(root, name="acme")
    assert got.rank == 3 and got.alpha == 6.0 and got.scale == 2.0
    assert sorted(got.targets) == sorted(TARGETS)
    for t in TARGETS:
        np.testing.assert_array_equal(got.targets[t][0],
                                      spec.targets[t][0])
        np.testing.assert_array_equal(got.targets[t][1],
                                      spec.targets[t][1])
    # a non-adapter checkpoint root is rejected up-front
    plain = str(tmp_path / "plain")
    mx.checkpoint.CheckpointManager(plain).save(
        0, {"w": np.zeros((2, 2), np.float32)})
    with pytest.raises(AdapterError, match="not an adapter root"):
        tenant.load_adapter(plain)


# ---------------------------------------------------------------------------
# WFQ + quota unit behaviour
# ---------------------------------------------------------------------------

class _Req:
    def __init__(self, t):
        self.tenant = t


def test_fair_queue_weight_ratio():
    """Under constant two-tenant backlog with unit cost, a weight-3
    tenant is admitted three times per weight-1 admission."""
    fq = FairQueue()
    weights = {"small": 1.0, "big": 3.0}
    waiting = [_Req("small"), _Req("big")]
    fq.observe_arrival("small")
    fq.observe_arrival("big")
    picks = {"small": 0, "big": 0}
    for _ in range(40):
        t, _req = fq.pick(waiting, lambda r: r.tenant,
                          lambda tn, r: True)
        fq.charge(t, 1.0, weights[t])
        picks[t] += 1
    assert abs(picks["big"] - 3 * picks["small"]) <= 2, picks


def test_fair_queue_idle_clamp_and_skip():
    fq = FairQueue()
    fq.charge("busy", 10.0, 1.0)
    fq.charge("busy", 10.0, 1.0)       # clock advances to 10.0
    assert fq.snapshot()["clock"] == 10.0
    # an idle tenant arriving later starts AT the clock, not at 0 --
    # sleeping banks no credit
    fq.observe_arrival("lazy")
    assert fq.snapshot()["vtime"]["lazy"] == 10.0
    # a tenant at quota is skipped, never waited on
    waiting = [_Req("blocked"), _Req("ok")]
    t, req = fq.pick(waiting, lambda r: r.tenant,
                     lambda tn, r: tn != "blocked")
    assert t == "ok" and req.tenant == "ok"
    assert fq.pick([_Req("blocked")], lambda r: r.tenant,
                   lambda tn, r: False) is None


def test_quota_ledger():
    led = QuotaLedger()
    q = TenantQuota(max_live=1, max_pages=4, queue_depth=2)
    with pytest.raises(TenantQuotaExceeded) as ei:
        led.check_request("a", q, 5)       # bigger than the whole quota
    assert ei.value.reason == "pages" and ei.value.tenant == "a"
    assert isinstance(ei.value, serve.ServerOverloaded)   # -> HTTP 503
    led.enqueue("a")
    led.enqueue("a")
    with pytest.raises(TenantQuotaExceeded) as ei:
        led.check_queue("a", q)
    assert ei.value.reason == "queue"
    led.dequeue("a")
    led.check_queue("a", q)                # below depth again
    assert led.admissible("a", q, 2)
    led.reserve("a", 2)
    assert not led.admissible("a", q, 2)   # max_live=1 reached
    led.release("a", 2)
    assert led.admissible("a", q, 2)
    led.dequeue("a")
    led.dequeue("a")                       # over-dequeue clamps at 0
    assert led.row("a")["waiting"] == 0


def test_tenant_config_env(monkeypatch):
    monkeypatch.setenv("MXNET_TENANT_SLOTS", "4")
    monkeypatch.setenv("MXNET_TENANT_MAX_RANK", "16")
    monkeypatch.setenv("MXNET_TENANT_DEFAULT_WEIGHT", "2.5")
    monkeypatch.setenv("MXNET_TENANT_QUEUE_DEPTH", "3")
    cfg = TenantConfig()
    assert cfg.slots == 4 and cfg.max_rank == 16
    assert cfg.default_weight == 2.5
    assert cfg.default_quota().queue_depth == 3
    explicit = TenantConfig(slots=2, max_rank=8)
    assert explicit.slots == 2 and explicit.max_rank == 8
    with pytest.raises(ValueError):
        TenantConfig(slots=0)


@pytest.mark.parametrize("slots,env,want", [
    (None, None, 8),        # the built-in default
    (None, "4", 4),         # the environment over the default
    (2, "4", 2),            # an explicit bank size over the environment
    (0, None, ValueError),  # a bank needs a slot, wherever the 0 came from
    (None, "0", ValueError),
])
def test_tenant_slots_explicit_then_environment_then_eight(
        slots, env, want, monkeypatch):
    if env is None:
        monkeypatch.delenv("MXNET_TENANT_SLOTS", raising=False)
    else:
        monkeypatch.setenv("MXNET_TENANT_SLOTS", env)
    if want is ValueError:
        with pytest.raises(ValueError, match="slots >= 1"):
            TenantConfig(slots=slots)
    else:
        assert TenantConfig(slots=slots).slots == want


def test_registry_register_get_unknown():
    plane = TenantPlane(TenantConfig(slots=2, max_rank=4))
    t = plane.register("acme", weight=2.0)
    assert t.weight == 2.0
    plane.register("acme", weight=3.0)     # re-register re-weights
    assert plane.get("acme").weight == 3.0
    with pytest.raises(UnknownTenant):
        plane.get("nobody")
    assert plane.slot_for("acme") == -1    # no bank, no adapter yet
    with pytest.raises(ValueError):
        plane.register("zero", weight=0.0)


# ---------------------------------------------------------------------------
# tentpole: one program, eight adapters, zero hot-path recompiles
# ---------------------------------------------------------------------------

def test_eight_adapters_one_program_compile_flat_across_hot_swap():
    plane = TenantPlane(TenantConfig(slots=8, max_rank=4))
    runner = serve.DecodeRunner(
        _decoder(), tenant=plane,
        config=_config(max_live=8, batch_sizes=(8,)))
    # ONE decode program (bucket 8) + one prefill program, period
    assert sorted(runner.provenance()) == ["decode:b8", "prefill:t8"]
    names = ["t%d" % i for i in range(8)]
    for i, name in enumerate(names):
        plane.register(name)
        plane.load_adapter(name, spec=_spec("a-%s" % name, seed=i))
    assert plane.bank.stats()["resident"] == 8
    compiles = telemetry.value("serve_decode_compile_total")
    sched = serve.DecodeScheduler(runner)
    try:
        futs = [sched.submit([1 + i, 2], max_new_tokens=4, tenant=n)
                for i, n in enumerate(names)]
        got = [f.result(timeout=120) for f in futs]
        assert all(len(g["tokens"]) == 4 for g in got)
        # hot remove + hot add while the server is live: pure slot
        # data swaps, the program table is untouched
        plane.unload_adapter("t0")
        plane.load_adapter("t0", spec=_spec("a-t0-v2", seed=99))
        plane.unload_adapter("t3")
        futs = [sched.submit([3, 4], max_new_tokens=4, tenant="t0"),
                sched.submit([5, 6], max_new_tokens=4, tenant="t3"),
                sched.submit([7, 8], max_new_tokens=4)]   # base row too
        for f in futs:
            assert len(f.result(timeout=120)["tokens"]) == 4
    finally:
        sched.stop()
    assert telemetry.value("serve_decode_compile_total") == compiles, \
        "adapter churn recompiled a decode program"
    assert runner.pool.in_use == 0
    runner.pool.check()
    assert plane.bank.stats()["swaps"] >= 10
    assert telemetry.value("tenant_adapter_swaps_total") >= 10


def test_adapter_output_matches_dense_merged_reference():
    """The batched gather path must emit the SAME token stream the
    per-tenant dense-merged weights emit — and a base (idx=-1) row in
    the same batch must match the unmerged model exactly."""
    spec = _spec("acme-a", rank=4, alpha=8.0, seed=11)
    prompt = [1, 2, 3]

    plane = TenantPlane(TenantConfig(slots=4, max_rank=4))
    runner = serve.DecodeRunner(_decoder(seed=7), tenant=plane,
                                config=_config())
    plane.register("acme")
    plane.load_adapter("acme", spec=spec)
    sched = serve.DecodeScheduler(runner)
    try:
        adapter_toks = sched.submit(
            prompt, max_new_tokens=4, tenant="acme").result(60)["tokens"]
        base_toks = sched.submit(
            prompt, max_new_tokens=4).result(60)["tokens"]
    finally:
        sched.stop()

    # dense-merged reference: identical init, W += scale * (A@B).T
    merged = AdapterBank.merge_into(_decoder(seed=7), spec)
    ref = serve.DecodeRunner(merged, config=_config())
    sref = serve.DecodeScheduler(ref)
    try:
        merged_toks = sref.submit(
            prompt, max_new_tokens=4).result(60)["tokens"]
    finally:
        sref.stop()

    plain = serve.DecodeRunner(_decoder(seed=7), config=_config())
    splain = serve.DecodeScheduler(plain)
    try:
        plain_toks = splain.submit(
            prompt, max_new_tokens=4).result(60)["tokens"]
    finally:
        splain.stop()

    assert adapter_toks == merged_toks
    assert base_toks == plain_toks
    assert adapter_toks != plain_toks, \
        "adapter did not change the stream — parity check is vacuous"


def test_wfq_admission_order_honours_weights():
    """Pre-queued backlog, serialized admission (max_live=1): WFQ must
    interleave deterministically — the weight-3 tenant drains all its
    requests ahead of the weight-1 tenant's second one."""
    plane = TenantPlane(TenantConfig(slots=2, max_rank=4))
    plane.register("small", weight=1.0)
    plane.register("big", weight=3.0)
    runner = serve.DecodeRunner(
        _decoder(), tenant=plane,
        config=_config(max_live=1, batch_sizes=(1,), queue_depth=16))
    sched = serve.DecodeScheduler(runner, start=False)
    order = []
    try:
        for i in range(3):
            f = sched.submit([1, 2], max_new_tokens=2, tenant="small")
            f.add_done_callback(
                lambda _f, n="small%d" % i: order.append(n))
        for i in range(3):
            f = sched.submit([1, 2], max_new_tokens=2, tenant="big")
            f.add_done_callback(
                lambda _f, n="big%d" % i: order.append(n))
        sched.start()
        deadline = time.time() + 60
        while len(order) < 6 and time.time() < deadline:
            time.sleep(0.01)
    finally:
        sched.stop()
    # first pick is the earliest arrival (both vtimes 0), then the
    # weight-3 tenant's smaller per-admission charge wins 3 in a row
    assert order == ["small0", "big0", "big1", "big2",
                     "small1", "small2"], order
    snap = plane.fair.snapshot()
    assert snap["picks"] == {"small": 3, "big": 3}
    # equal cost, 3x weight -> one third the virtual charge
    assert abs(snap["charged"]["small"] / snap["charged"]["big"]
               - 3.0) < 1e-6


def test_tenant_quota_rejects_and_never_blocks_neighbours():
    plane = TenantPlane(TenantConfig(slots=2, max_rank=4))
    plane.register("capped", quota={"max_live": 1, "queue_depth": 2})
    plane.register("free")
    runner = serve.DecodeRunner(
        _decoder(), tenant=plane,
        config=_config(max_live=2, batch_sizes=(1, 2), queue_depth=16))
    sched = serve.DecodeScheduler(runner, start=False)
    order = []

    def _track(fut, name):
        fut.add_done_callback(lambda _f, n=name: order.append(n))
        return fut

    try:
        # single request larger than the tenant's whole page quota:
        # immediate per-tenant 503, nothing enqueued
        plane.register("tiny", quota={"max_pages": 1})
        with pytest.raises(TenantQuotaExceeded) as ei:
            sched.submit([1] * 8, max_new_tokens=4, tenant="tiny")
        assert ei.value.reason == "pages"
        # backlog: capped live-quota holds its 2nd request WAITING
        # while the other tenant (submitted later) sails past it
        a1 = _track(sched.submit([1, 2], max_new_tokens=4,
                                 tenant="capped"), "a1")
        a2 = _track(sched.submit([1, 2], max_new_tokens=4,
                                 tenant="capped"), "a2")
        # capped's queue_depth=2 is now full -> per-tenant reject
        with pytest.raises(TenantQuotaExceeded) as ei:
            sched.submit([1, 2], max_new_tokens=4, tenant="capped")
        assert ei.value.reason == "queue"
        b1 = _track(sched.submit([1, 2], max_new_tokens=4,
                                 tenant="free"), "b1")
        sched.start()
        for f in (a1, a2, b1):
            assert len(f.result(timeout=60)["tokens"]) == 4
    finally:
        sched.stop()
    # no head-of-line blocking: free's request finished before
    # capped's quota-held second sequence
    assert order.index("b1") < order.index("a2"), order
    assert telemetry.value("tenant_quota_rejects_total") == 2
    assert plane.stats()["rejects"] == {"pages": 1, "queue": 1}
    row = plane.ledger.row("capped")
    assert row["live"] == 0 and row["waiting"] == 0


def test_unknown_tenant_and_missing_plane_are_client_errors():
    runner = serve.DecodeRunner(_decoder(), config=_config())
    sched = serve.DecodeScheduler(runner)
    try:
        with pytest.raises(serve.DecodeError, match="no tenant plane"):
            sched.submit([1, 2], tenant="acme")
    finally:
        sched.stop()
    plane = TenantPlane(TenantConfig(slots=2, max_rank=4))
    runner = serve.DecodeRunner(_decoder(), tenant=plane,
                                config=_config())
    sched = serve.DecodeScheduler(runner)
    try:
        with pytest.raises(serve.DecodeError, match="unknown tenant"):
            sched.submit([1, 2], tenant="nobody")
    finally:
        sched.stop()


def test_poisoned_adapter_quarantined_batchmates_byte_identical():
    """A NaN'ing adapter takes down ONLY its own sequences: the
    batch-mate's stream is byte-identical to an undisturbed run, the
    ("adapter", tenant) breaker opens, and follow-up submissions for
    the poisoned tenant fast-reject while others keep flowing."""
    good_spec = _spec("good-a", seed=21)
    prompt = [1, 2]

    def build(with_evil):
        plane = TenantPlane(TenantConfig(slots=4, max_rank=4))
        plane.register("good")
        runner = serve.DecodeRunner(_decoder(seed=13), tenant=plane,
                                    config=_config(max_live=2,
                                                   batch_sizes=(2,)))
        plane.load_adapter("good", spec=good_spec)
        if with_evil:
            bad = _spec("evil-a", seed=22)
            for t in bad.targets:
                bad.targets[t][0][0, 0] = np.nan
            plane.register("evil")
            plane.load_adapter("evil", spec=bad)
        return plane, runner

    # undisturbed reference run: good tenant alone
    _plane, runner = build(with_evil=False)
    sched = serve.DecodeScheduler(runner)
    try:
        ref = sched.submit(prompt, max_new_tokens=4,
                           tenant="good").result(60)["tokens"]
    finally:
        sched.stop()

    plane, runner = build(with_evil=True)
    board = BreakerBoard(threshold=1, cooldown=60.0)
    sched = serve.DecodeScheduler(runner, breakers=board, start=False)
    try:
        evil = sched.submit(prompt, max_new_tokens=4, tenant="evil")
        good = sched.submit(prompt, max_new_tokens=4, tenant="good")
        sched.start()
        with pytest.raises(serve.DecodeError, match="nonfinite"):
            evil.result(timeout=60)
        assert good.result(timeout=60)["tokens"] == ref
        # breaker open: the poisoned tenant fast-rejects at submit...
        with pytest.raises(serve.BucketQuarantined):
            sched.submit(prompt, max_new_tokens=4, tenant="evil")
        # ...while its neighbour keeps decoding on the same program
        again = sched.submit(prompt, max_new_tokens=4,
                             tenant="good").result(60)["tokens"]
        assert again == ref
    finally:
        sched.stop()
    assert telemetry.value("tenant_adapter_poison_total",
                           labels={"tenant": "evil"}) >= 1
    assert telemetry.value("tenant_requests_total",
                           labels={"tenant": "evil",
                                   "result": "quarantined"}) >= 1
    assert runner.pool.in_use == 0
    runner.pool.check()


# ---------------------------------------------------------------------------
# observability surfaces
# ---------------------------------------------------------------------------

def test_statz_tenants_block_and_residency_digest():
    plane = TenantPlane(TenantConfig(slots=4, max_rank=4))
    plane.register("acme", weight=2.0)
    runner = serve.DecodeRunner(_decoder(), tenant=plane,
                                config=_config())
    plane.load_adapter("acme", spec=_spec("acme-a"))
    srv = serve.Server(decode=runner)
    try:
        doc = srv.stats()
        ten = doc["tenants"]
        assert ten["enabled"] is True
        assert ten["config"]["slots"] == 4
        assert ten["tenants"]["acme"]["weight"] == 2.0
        assert ten["tenants"]["acme"]["adapter"] == "acme-a"
        assert ten["bank"]["resident"] == 1
        assert set(ten) >= {"enabled", "config", "tenants", "wfq",
                            "rejects", "bank"}
        # fleet load digest carries adapter residency for the router
        digest = srv.load_digest()
        assert digest["tenants"] == {"resident": ["acme"], "slots": 4}
        got = srv.submit_decode([1, 2], max_new_tokens=2,
                                tenant="acme").result(60)
        assert len(got["tokens"]) == 2
    finally:
        srv.shutdown()
    assert telemetry.value("tenant_tokens_total",
                           labels={"tenant": "acme"}) == 2
    assert telemetry.value("tenant_requests_total",
                           labels={"tenant": "acme",
                                   "result": "ok"}) == 1


def test_tenant_ttft_slo_registered_per_tenant():
    from mxnet_tpu.obs import slo_engine

    plane = TenantPlane(TenantConfig(slots=2, max_rank=4))
    plane.register("acme")
    plane.register("beta")
    try:
        names = plane.register_slos(ttft_target_s=0.5)
        assert sorted(names) == ["tenant_ttft:acme", "tenant_ttft:beta"]
        assert set(names) <= set(slo_engine.registered())
        res = slo_engine.evaluate()
        assert res["tenant_ttft:acme"]["state"] == "OK"
    finally:
        slo_engine.clear()


def test_pages_by_group_rollup():
    from mxnet_tpu.serve.kvcache import PageConfig, PagePool

    pool = PagePool(PageConfig(page_size=4, num_pages=16, num_layers=1,
                               num_kv_heads=1, head_dim=4,
                               max_context=16))
    pool.alloc("s1", 2)
    pool.alloc("s2", 3)
    pool.alloc("s3", 1)
    groups = {"s1": "acme", "s2": "acme", "s3": None}
    assert pool.pages_by_group(groups.get) == {"acme": 5, None: 1}


def test_tenant_prometheus_families_exported():
    plane = TenantPlane(TenantConfig(slots=2, max_rank=4))
    plane.register("acme")
    runner = serve.DecodeRunner(_decoder(), tenant=plane,
                                config=_config())
    plane.load_adapter("acme", spec=_spec("acme-a"))
    sched = serve.DecodeScheduler(runner)
    try:
        sched.submit([1, 2], max_new_tokens=2,
                     tenant="acme").result(60)
    finally:
        sched.stop()
    prom = telemetry.prometheus()
    for fam in ("tenant_requests_total", "tenant_ttft_seconds",
                "tenant_tokens_total", "tenant_adapter_swaps_total",
                "tenant_adapter_slots", "tenant_adapters_resident",
                "tenant_wfq_picks_total"):
        assert "# TYPE %s" % fam in prom, fam


def test_tenant_env_vars_registered_and_feature_flag(monkeypatch):
    from mxnet_tpu import config, runtime

    for var in ("MXNET_TENANT", "MXNET_TENANT_SLOTS",
                "MXNET_TENANT_MAX_RANK", "MXNET_TENANT_DEFAULT_WEIGHT",
                "MXNET_TENANT_MAX_LIVE", "MXNET_TENANT_MAX_PAGES",
                "MXNET_TENANT_QUEUE_DEPTH"):
        assert var in config.ENV_VARS, var
    monkeypatch.delenv("MXNET_TENANT", raising=False)
    assert not runtime.features.is_enabled("TENANT")
    monkeypatch.setenv("MXNET_TENANT", "1")
    assert runtime.features.is_enabled("TENANT")
