"""Headline benchmarks: ResNet-50 (fp32 + bf16) and BERT-base pretraining.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.
The headline metric is bf16 mixed-precision ResNet-50 training throughput
(the reference's flagship benchmark model, docs perf.md:243-252); "extra"
carries the secondary rows (fp32 ResNet, BERT-base pretraining) with
computed MFU so every BASELINE.md target config has a tracked number.

Baselines (BASELINE.md):
- ResNet-50 training fp32 batch 128, 1xV100 = 363.69 img/s (the reference's
  only published training number; it has no mixed-precision training row).
- BERT-base: no reference number exists (transformer kernels only,
  src/operator/contrib/transformer.cc); tracked as tokens/sec/chip + MFU
  against the >=45% MFU north star.

MFU accounting (honest *model* flops, not hardware-counted flops):
- ResNet-50: 4.089 GFLOP/img forward at 224x224 (conv+fc MACs x2), x3 for
  fwd+bwd -> 12.27 GFLOP/img trained.
- BERT-base: analytic per-token transformer flops (qkvo 8C^2 + attention
  4TC + ffn 4C*FF per layer, MLM transform, vocab decoder on the 15%
  masked slots), x3 for fwd+bwd.
- Peak: published bf16 matmul peak of the local chip, keyed by
  ``device_kind`` (``_PEAK_BF16_TFLOPS``); a kind without an entry is an
  error, not a default.  fp32 rows are reported without MFU (the MXU is a
  bf16 engine; fp32 runs are for continuity with rounds 1-2).

Methodology: batches staged on device before the timed loop (input
pipelining is the native loader's job, benchmarked by benchmark/data_bench
.py); the loop is hard-synced by a device->host transfer of the final
loss.

The run needs a TPU: no TPU, a ``device_kind`` without a published peak, or
a phase that raises ends it non-zero with a traceback and no headline.

Layout note: NCHW vs NHWC was measured within 2% on TPU for the same
program (XLA:TPU re-tiles layouts internally, unlike GPU) — models stay in
the reference's NCHW family; no layout plumbing is warranted.
"""
from __future__ import annotations

import json
import os
import sys
import time

RESNET_BASELINE_IMGS_PER_SEC = 363.69  # ResNet-50 train fp32 bs128, 1xV100
RESNET_FWD_GFLOP_PER_IMG = 4.089
WARMUP = 3
_T0 = time.time()


def _log(msg):
    print("[bench +%6.1fs] %s" % (time.time() - _T0, msg), file=sys.stderr,
          flush=True)


def _telemetry_totals():
    from mxnet_tpu import telemetry

    return telemetry.totals(nonzero=True)


def _monitor_summary(reset_peak=False):
    """mx.monitor run summary, or {} when the monitor plane is off."""
    from mxnet_tpu import monitor

    if not monitor.is_enabled():
        return {}
    monitor.flush(timeout=10.0)
    return monitor.summary(reset_peak=reset_peak)


def _obs_summary():
    """mx.obs fleet block (ranks seen, straggler flags, SLO states), or
    {} when the obs plane is off."""
    from mxnet_tpu import obs

    if not obs.is_enabled():
        return {}
    return obs.fleet_summary()


def _attach_telemetry(row, before, mon_before=None):
    """Attach the per-row delta of telemetry totals (and, when
    MXNET_MONITOR=1, the numeric-health columns) to a bench row."""
    after = _telemetry_totals()
    # union of key sets: a gauge dropping to exactly zero disappears from
    # the nonzero `after` view but must still show as a negative delta
    delta = {k: round(after.get(k, 0) - before.get(k, 0), 6)
             for k in set(before) | set(after)
             if after.get(k, 0) != before.get(k, 0)}
    if isinstance(row, dict) and delta:
        row["telemetry"] = delta
    # numeric health next to the throughput/mfu numbers: the run must
    # have stayed FINITE, not just fast.  reset_peak in the row's
    # "before" snapshot makes max per-row.
    mon = _monitor_summary()
    if isinstance(row, dict) and mon:
        mb = mon_before or {}
        row["grad_global_norm"] = {
            "last": round(mon.get("grad_global_norm_last", 0.0), 6),
            "max": round(mon.get("grad_global_norm_max", 0.0), 6)}
        row["nonfinite_steps"] = int(
            mon.get("nonfinite_steps", 0) - mb.get("nonfinite_steps", 0))
        skipped = int(mon.get("skipped_steps", 0)
                      - mb.get("skipped_steps", 0))
        if skipped:
            row["skipped_steps"] = skipped
    fleet = _obs_summary()
    if isinstance(row, dict) and fleet:
        row["fleet"] = fleet
    return row


# published bf16 matmul peaks, TFLOP/s per chip, keyed by the lower-cased
# jax device_kind (Google Cloud TPU documentation, "TPU v5e" / "TPU v4" /
# "TPU v5p" / "TPU v6e" system architecture pages)
_PEAK_BF16_TFLOPS = {
    "tpu v5 lite": 197.0,
    "tpu v5e": 197.0,
    "tpu v4": 275.0,
    "tpu v5p": 459.0,
    "tpu v5": 459.0,
    "tpu v6 lite": 918.0,
    "tpu v6e": 918.0,
}


def _peak_bf16_tflops():
    import jax

    kind = jax.devices()[0].device_kind.lower()
    try:
        return _PEAK_BF16_TFLOPS[kind]
    except KeyError:
        raise SystemExit(
            "bench: no published bf16 peak for device_kind %r; add it to "
            "_PEAK_BF16_TFLOPS with its source" % kind) from None


def _bench_resnet(dtype, batch, iters=20):
    import numpy as np

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    net = vision.resnet50_v1()
    net.initialize()
    trainer = parallel.FusedTrainer(
        net, loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
        dtype=None if dtype == "float32" else dtype)
    rs = np.random.RandomState(0)
    x = jax.device_put(rs.rand(batch, 3, 224, 224).astype(np.float32))
    y = jax.device_put(rs.randint(0, 1000, batch).astype(np.int32))

    _log("resnet50 %s: model built, compiling+warmup" % dtype)
    for _ in range(WARMUP):
        loss = trainer.step(x, y)
    float(loss.asnumpy())  # hard sync: device round-trip
    _log("resnet50 %s: warm, timing" % dtype)

    t0 = time.perf_counter()
    for _ in range(iters):
        loss = trainer.step(x, y)
    float(loss.asnumpy())
    dt = time.perf_counter() - t0
    imgs_per_sec = batch * iters / dt
    row = {"imgs_per_sec": round(imgs_per_sec, 2),
           "step_ms": round(1000 * dt / iters, 2),
           "batch": batch, "dtype": dtype}
    if dtype != "float32":
        tflops = imgs_per_sec * 3 * RESNET_FWD_GFLOP_PER_IMG / 1000.0
        row["model_tflops"] = round(tflops, 1)
        row["mfu"] = round(tflops / _peak_bf16_tflops(), 3)
    return row


def bert_train_flops_per_step(batch, seq, n_mask, layers=12, units=768,
                              ffn=3072, vocab=30522):
    """Analytic BERT train flops (MACs x2, fwd x3 for fwd+bwd+param-grads)."""
    c, ff = units, ffn
    per_tok = layers * (8 * c * c + 4 * seq * c + 4 * c * ff)
    # MLM transform + vocab decoder run on the masked slots only
    per_masked = 2 * c * c + 2 * c * vocab
    fwd = per_tok * batch * seq + per_masked * batch * n_mask
    return 3 * fwd


def _bench_bert(batch=16, seq=512, dropout=0.1, iters=10):
    import numpy as np

    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo import bert as bert_zoo

    vocab = 30522
    n_mask = max(1, int(seq * 0.15))

    class PretrainStep(HybridBlock):
        def __init__(self):
            super().__init__()
            self.model = bert_zoo.BERTForPretraining(
                vocab_size=vocab, units=768, hidden_size=3072,
                num_layers=12, num_heads=12, dropout=dropout)

        def forward(self, tokens, types, positions):
            return self.model(tokens, types, valid_length=None,
                              masked_positions=positions)

    def pretrain_loss(outs, masked_labels, nsp_labels):
        mlm_scores, nsp_scores = outs
        logp = jax.nn.log_softmax(mlm_scores.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(
            logp, masked_labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
        nlogp = jax.nn.log_softmax(nsp_scores.astype(jnp.float32), axis=-1)
        nsp = jnp.take_along_axis(
            nlogp, nsp_labels[:, None].astype(jnp.int32), axis=-1)[..., 0]
        return -jnp.mean(ll) - jnp.mean(nsp)

    mx.random.seed(0)
    net = PretrainStep()
    net.initialize()
    trainer = parallel.FusedTrainer(
        net, loss_fn=pretrain_loss, optimizer="adam",
        optimizer_params={"learning_rate": 1e-4}, dtype="bfloat16")

    rs = np.random.RandomState(0)
    x = tuple(jax.device_put(v) for v in (
        rs.randint(0, vocab, (batch, seq)).astype(np.int32),
        rs.randint(0, 2, (batch, seq)).astype(np.int32),
        np.sort(rs.choice(seq, (batch, n_mask)), axis=1).astype(np.int32)))
    y = tuple(jax.device_put(v) for v in (
        rs.randint(0, vocab, (batch, n_mask)).astype(np.int32),
        rs.randint(0, 2, batch).astype(np.int32)))

    _log("bert: model built, compiling+warmup")
    for _ in range(WARMUP):
        loss = trainer.step(x, y)
    float(loss.asnumpy())
    _log("bert: warm, timing")
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = trainer.step(x, y)
    float(loss.asnumpy())
    dt = time.perf_counter() - t0

    tok_s = batch * seq * iters / dt
    tflops = bert_train_flops_per_step(batch, seq, n_mask) * iters / dt / 1e12
    return {"tokens_per_sec": round(tok_s, 1),
            "step_ms": round(1000 * dt / iters, 2),
            "batch": batch, "seq": seq, "dropout": dropout,
            "dtype": "bfloat16", "model_tflops": round(tflops, 1),
            "mfu": round(tflops / _peak_bf16_tflops(), 3)}


def _bench_lstm_lm(batch=32, seq=64, vocab=10000, hidden=650, iters=10):
    """BASELINE config 5: LSTM language model (the fused-RNN replacement,
    reference rnn.cc:295 -> lax.scan)."""
    import numpy as np

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.model_zoo import language_model as lm

    mx.random.seed(0)
    net = lm.StandardRNNLM(vocab, embed_size=hidden, hidden_size=hidden,
                           num_layers=2, dropout=0.0)
    net.initialize()
    trainer = parallel.FusedTrainer(
        net, loss_fn=None, loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 1.0})
    rs = np.random.RandomState(0)
    x = jax.device_put(rs.randint(0, vocab, (batch, seq)).astype(np.int32))
    y = jax.device_put(rs.randint(0, vocab, (batch, seq)).astype(np.int32))

    for _ in range(WARMUP):
        loss = trainer.step(x, y)
    float(loss.asnumpy())
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = trainer.step(x, y)
    float(loss.asnumpy())
    dt = time.perf_counter() - t0
    return {"tokens_per_sec": round(batch * seq * iters / dt, 1),
            "step_ms": round(1000 * dt / iters, 2), "batch": batch,
            "seq": seq, "hidden": hidden, "dtype": "float32"}


def _bench_resnet_infer(dtype="bfloat16", batch=32, iters=30):
    """Inference row (reference perf.md:185-215: 1,076 img/s fp32 /
    2,085 img/s fp16 on V100, batch 32)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    net = vision.resnet50_v1()
    net.initialize()
    # one tiny forward resolves deferred-shape params before export_pure
    from mxnet_tpu import nd as _nd
    net(_nd.zeros((1, 3, 224, 224)))
    apply_fn, params = net.export_pure(training=False)
    if dtype != "float32":
        dt = jnp.dtype(dtype)
        params = {n: (v.astype(dt) if v.dtype == jnp.float32 else v)
                  for n, v in params.items()}

    @jax.jit
    def fwd(p, x):
        outs, _ = apply_fn(p, None, x)
        return outs[0]

    rs = np.random.RandomState(0)
    x = jax.device_put(rs.rand(batch, 3, 224, 224).astype(
        np.float32 if dtype == "float32" else dtype))
    for _ in range(WARMUP):
        out = fwd(params, x)
    float(out.sum().astype(jnp.float32))  # hard sync
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fwd(params, x)
    float(out.sum().astype(jnp.float32))
    dt_s = time.perf_counter() - t0
    return {"imgs_per_sec": round(batch * iters / dt_s, 2),
            "step_ms": round(1000 * dt_s / iters, 3),
            "batch": batch, "dtype": dtype}


def _bench_resnet_infer_int8(batch=32, iters=30):
    """Post-training-quantized int8 inference (reference perf.md int8
    rows; contrib/quantization quantize_net -> int8 MXU path)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.contrib.quantization import quantize_net
    from mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    net = vision.resnet50_v1()
    net.initialize()
    rs = np.random.RandomState(0)
    calib = nd.array(rs.rand(8, 3, 224, 224).astype(np.float32))
    net(calib[:1])     # resolve deferred shapes
    quantize_net(net, calib_data=[calib], calib_mode="naive")
    net.hybridize()

    x = nd.array(rs.rand(batch, 3, 224, 224).astype(np.float32))
    for _ in range(WARMUP):
        out = net(x)
    float(out.asnumpy().ravel()[0])  # hard sync
    t0 = time.perf_counter()
    for _ in range(iters):
        out = net(x)
    float(out.asnumpy().ravel()[0])
    dt_s = time.perf_counter() - t0
    return {"imgs_per_sec": round(batch * iters / dt_s, 2),
            "step_ms": round(1000 * dt_s / iters, 3),
            "batch": batch, "dtype": "int8"}


def _bench_serve_decode(clients=24, max_new=32):
    """mx.serve.decode row: paged KV-cache continuous batching under
    concurrent mixed load — tokens/s, time-to-first-token and
    per-token latency p50/p99, page-pool occupancy.  The telemetry
    histograms (serve_decode_ttft_seconds / _token_seconds) supply the
    quantiles; runs on whatever backend is live (CPU numbers still
    price the scheduler, not the matmuls)."""
    import threading

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import serve, telemetry

    mx.random.seed(0)
    blk = serve.TinyDecoder(vocab_size=256, num_layers=4, num_heads=4,
                            head_dim=16)
    blk.initialize()
    cfg = serve.DecodeConfig(page_size=16, pool_pages=256, max_live=8,
                             max_new_tokens=max_new, max_context=128,
                             prefill_lengths=(16, 32, 64),
                             batch_sizes=(1, 2, 4, 8))
    runner = serve.DecodeRunner(blk, config=cfg)
    sched = serve.DecodeScheduler(runner)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 256, size=rs.randint(4, 60)).tolist()
               for _ in range(clients)]
    futs = [None] * clients

    def fire(i):
        futs[i] = sched.submit(prompts[i], max_new_tokens=max_new,
                               request_id="bench-%d" % i)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=fire, args=(i,))
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tokens = sum(len(f.result(timeout=600)["tokens"]) for f in futs)
    dt_s = time.perf_counter() - t0
    sched.stop()
    pool = runner.pool.stats()
    assert pool["in_use_pages"] == 0, "bench leaked KV pages"
    ttft = telemetry.histogram_quantiles("serve_decode_ttft_seconds",
                                         qs=(0.5, 0.99))
    tok = telemetry.histogram_quantiles("serve_decode_token_seconds",
                                        qs=(0.5, 0.99))
    return {
        "tokens_per_sec": round(tokens / dt_s, 2),
        "tokens": tokens,
        "clients": clients,
        "max_live": cfg.max_live,
        "ttft_ms_p50": round(1e3 * ttft.get(0.5, 0.0), 3),
        "ttft_ms_p99": round(1e3 * ttft.get(0.99, 0.0), 3),
        "token_ms_p50": round(1e3 * tok.get(0.5, 0.0), 3),
        "token_ms_p99": round(1e3 * tok.get(0.99, 0.0), 3),
        "decode_steps": telemetry.value("serve_decode_steps_total"),
        "pool_high_water_pages": pool["high_water_pages"],
        "pool_capacity_pages": pool["capacity_pages"],
        "compiles": telemetry.value("serve_decode_compile_total"),
    }


def _bench_serve_cache(sessions=8, max_new=16):
    """mx.serve.cache row: the per-token-cost plane.  N sessions share
    one 2000-token system prompt (each with its own user suffix): the
    first prefills cold, every later one rides the radix prefix cache
    and charges only its suffix — the row reports the prefill-token
    reduction, measured TTFT cold vs hit, and that session churn adds
    ZERO compiles.  A second phase prices speculative decoding:
    accepted-tokens-per-target-step with a perfect (same-weights)
    draft — the structural upper bound K+1 — vs single-step decode."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import serve, telemetry

    mx.random.seed(0)
    blk = serve.TinyDecoder(vocab_size=256, num_layers=4, num_heads=4,
                            head_dim=16)
    blk.initialize()
    cfg = serve.DecodeConfig(page_size=16, pool_pages=384, max_live=2,
                             max_new_tokens=max_new, max_context=2112,
                             prefill_lengths=(64, 2048),
                             batch_sizes=(1, 2), prefix_cache=True)
    runner = serve.DecodeRunner(blk, config=cfg)
    sched = serve.DecodeScheduler(runner)
    rs = np.random.RandomState(0)
    system = rs.randint(0, 256, size=2000).tolist()
    compiles0 = telemetry.value("serve_decode_compile_total")
    ttfts = []
    try:
        for i in range(sessions):
            user = rs.randint(0, 256, size=32).tolist()
            t0 = time.perf_counter()
            first = []
            fut = sched.submit(
                system + user, max_new_tokens=max_new,
                request_id="cache-bench-%d" % i,
                on_token=lambda tok, idx, t=t0: first.append(
                    time.perf_counter() - t) if not first else None)
            fut.result(timeout=600)
            ttfts.append(first[0])
    finally:
        sched.stop()
    cache = runner.cache.stats()
    compile_delta = telemetry.value("serve_decode_compile_total") \
        - compiles0
    hit_ttft = sum(ttfts[1:]) / max(1, len(ttfts) - 1)

    # speculative decoding: perfect-draft acceptance upper bound
    mx.random.seed(0)
    blk2 = serve.TinyDecoder(vocab_size=256, num_layers=4, num_heads=4,
                             head_dim=16)
    blk2.initialize()
    scfg = serve.DecodeConfig(page_size=16, pool_pages=64, max_live=2,
                              max_new_tokens=max_new, max_context=128,
                              prefill_lengths=(64,), batch_sizes=(1, 2))
    prompt = rs.randint(0, 256, size=24).tolist()

    def timed(r):
        s = serve.DecodeScheduler(r)
        try:
            t0 = time.perf_counter()
            toks = s.submit(list(prompt), max_new_tokens=max_new) \
                .result(timeout=600)["tokens"]
            return toks, time.perf_counter() - t0
        finally:
            s.stop()

    single = serve.DecodeRunner(blk2, config=scfg)
    ref, dt_single = timed(single)
    spec = serve.DecodeRunner(blk2, config=scfg, draft=blk2)
    out, dt_spec = timed(spec)
    assert out == ref, "speculative decode diverged from single-step"
    sp = spec.spec.stats()
    return {
        "sessions": sessions,
        "system_tokens": len(system),
        "prefill_tokens_cold": len(system) + 32,
        "prefill_tokens_hit": 32,
        "prefill_token_reduction_x": round((len(system) + 32) / 32.0,
                                           1),
        "ttft_cold_ms": round(1e3 * ttfts[0], 1),
        "ttft_hit_ms": round(1e3 * hit_ttft, 1),
        "ttft_speedup_x": round(ttfts[0] / hit_ttft, 1),
        # warm sessions match the 125 shared system blocks but not
        # their own final (user-suffix) block -> class "partial"
        "cache_warm_sessions": cache["hits"] + cache["partials"],
        "cache_hit_tokens_total": cache["hit_tokens_total"],
        "cache_nodes": cache["nodes"],
        "compile_delta_during_churn": compile_delta,
        "spec_k": sp["k"],
        "spec_accepted_per_step": round(sp["accepted_per_step"], 2),
        "spec_acceptance_rate": round(sp["acceptance_rate"], 3),
        "spec_verify_steps": sp["verify_steps"],
        "tokens_per_sec_single_step": round(len(ref) / dt_single, 2),
        "tokens_per_sec_speculative": round(len(out) / dt_spec, 2),
    }


def _bench_fleet(requests=32, max_new=16):
    """mx.fleet row: what the router front-end costs on top of a
    replica — per-request routing overhead (refresh + p2c pick, the
    fleet_router_overhead_seconds histogram) and end-to-end request
    latency through discovery + dispatch + NDJSON streaming, plus the
    packed prefill->decode handoff blob size for one sequence.  Two
    in-process replicas over a MemKV, so the number prices the fleet
    plane itself, not the network."""
    from types import SimpleNamespace

    import mxnet_tpu as mx
    from mxnet_tpu import fleet, serve, telemetry
    from mxnet_tpu.dist.membership import MemKV

    mx.random.seed(0)
    kv = MemKV()
    servers = []
    for rank in range(2):
        blk = serve.TinyDecoder(vocab_size=64, num_layers=2,
                                num_heads=2, head_dim=8)
        blk.initialize()
        cfg = serve.DecodeConfig(page_size=8, pool_pages=64,
                                 max_live=4, max_new_tokens=max_new,
                                 max_context=64, prefill_lengths=(8,),
                                 batch_sizes=(1, 2, 4))
        srv = mx.serve.Server(decode=serve.DecodeRunner(blk,
                                                        config=cfg))
        srv.start_http()
        srv.register_fleet(
            SimpleNamespace(kv=kv, generation=1, rank=rank),
            role="both")
        servers.append(srv)
    try:
        router = fleet.Router(kv=kv, generation=1, seed=0)
        t0 = time.perf_counter()
        ok = 0
        for i in range(requests):
            ev = router.run_decode(
                {"tokens": [1, 2, 3], "max_new_tokens": max_new},
                request_id="bench-fleet-%d" % i)
            ok += 1 if "done" in ev else 0
        dt_s = time.perf_counter() - t0
        assert ok == requests, (ok, requests)
        blob = fleet.pack(servers[0].submit_decode_export(
            [1, 2, 3], max_new_tokens=max_new).result())
        router.shutdown()
    finally:
        for srv in servers:
            srv.shutdown(drain=False)
    over = telemetry.histogram_quantiles(
        "fleet_router_overhead_seconds", qs=(0.5, 0.99))
    req = telemetry.histogram_quantiles(
        "fleet_router_request_seconds", qs=(0.5, 0.99))
    return {
        "requests_per_sec": round(requests / dt_s, 2),
        "requests": requests,
        "replicas": len(servers),
        "router_overhead_us_p50": round(1e6 * over.get(0.5, 0.0), 1),
        "router_overhead_us_p99": round(1e6 * over.get(0.99, 0.0), 1),
        "request_ms_p50": round(1e3 * req.get(0.5, 0.0), 3),
        "request_ms_p99": round(1e3 * req.get(0.99, 0.0), 3),
        "handoff_blob_bytes": len(blob),
        "failovers": telemetry.value("fleet_failover_total"),
    }


def _bench_imperative_trainer(batch=64, iters=10, dtype="bfloat16"):
    """Imperative (gluon.Trainer) ResNet-50 training — the default
    MXNet-parity path: hybridized fwd+bwd under autograd.record, then
    ``trainer.step`` runs the multi-tensor fused optimizer apply
    (optimizer/multi_tensor.py) — O(groups) update programs per step
    instead of ~160 per-parameter eager chains.  Telemetry deltas
    attached by the caller carry trainer_fused_* / trainer_update_
    seconds so the fused-vs-eager split is visible in the row."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd, trace
    from mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    net = vision.resnet50_v1()
    net.initialize()
    if dtype != "float32":
        net.cast(dtype)
    net.hybridize()
    trainer = gluon.Trainer(
        net.collect_params(), "sgd",
        {"learning_rate": 0.05, "momentum": 0.9,
         "multi_precision": dtype != "float32"})
    rs = np.random.RandomState(0)
    x = nd.array(rs.rand(batch, 3, 224, 224).astype(np.float32)) \
        .astype(dtype)
    y = nd.array(rs.randint(0, 1000, batch).astype(np.int32))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def step():
        # full-step trace: forward / backward / (nested) trainer_step
        # share one trace id per iteration, so the run leaves a
        # phase-level flight record next to the row.
        # (no anomaly= here: the nested trainer_step span already feeds
        # the slow-step detector — a second feed from a different
        # duration distribution would skew its trailing p99)
        with trace.span("train_step", hist=False):
            with trace.span("forward", hist=False):
                with autograd.record():
                    loss = loss_fn(net(x), y).mean()
            with trace.span("backward", hist=False):
                loss.backward()
            trainer.step(batch)
        return loss

    _log("imperative trainer %s: compiling+warmup" % dtype)
    for _ in range(WARMUP):
        loss = step()
    float(loss.asnumpy())  # hard sync
    _log("imperative trainer %s: warm, timing" % dtype)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step()
    float(loss.asnumpy())
    dt = time.perf_counter() - t0
    from mxnet_tpu.optimizer import multi_tensor

    return {"imgs_per_sec": round(batch * iters / dt, 2),
            "step_ms": round(1000 * dt / iters, 2),
            "batch": batch, "dtype": dtype,
            "update_groups": multi_tensor.group_table(trainer)}


def _bench_captured_step(batch=64, iters=10, dtype="bfloat16",
                         fused_ref=None):
    """Whole-step captured ResNet-50 training (mx.step): the SAME
    model/data as the imperative-trainer row, but forward + loss +
    backward + allreduce + fused apply run as ONE donated XLA program
    per step.  Reports img/s for both the captured and the stitched
    path (same process, same weights-at-start discipline), the
    captured/stitched delta, the delta vs the FusedTrainer headline
    when available, and a bit-parity check of final params after
    PARITY_STEPS captured-vs-stitched steps on fresh models."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd, trace
    from mxnet_tpu.gluon.model_zoo import vision

    PARITY_STEPS = 3

    def build(seed=0):
        mx.random.seed(seed)
        net = vision.resnet50_v1()
        net.initialize()
        if dtype != "float32":
            net.cast(dtype)
        net.hybridize()
        trainer = gluon.Trainer(
            net.collect_params(), "sgd",
            {"learning_rate": 0.05, "momentum": 0.9,
             "multi_precision": dtype != "float32"})
        return net, trainer

    rs = np.random.RandomState(0)
    x = nd.array(rs.rand(batch, 3, 224, 224).astype(np.float32)) \
        .astype(dtype)
    y = nd.array(rs.randint(0, 1000, batch).astype(np.int32))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def time_loop(step_once):
        for _ in range(WARMUP):
            loss = step_once()
        float(loss.mean().asnumpy())  # hard sync
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step_once()
        float(loss.mean().asnumpy())
        return batch * iters / (time.perf_counter() - t0)

    _log("captured step %s: capture+warmup" % dtype)
    net_c, tr_c = build()
    program = tr_c.capture(net_c, gluon.loss.SoftmaxCrossEntropyLoss())
    captured_ips = time_loop(lambda: program(x, y))
    rep = program.report()
    if rep["paths"]["captured"] == 0:
        # a stitched timing must never be labelled captured
        raise RuntimeError("capture degraded: %s" % rep["fallbacks"][:1])

    _log("captured step %s: stitched reference timing" % dtype)
    net_s, tr_s = build()

    def stitched_step():
        with trace.span("train_step", hist=False):
            with autograd.record():
                loss = loss_fn(net_s(x), y)
            loss.backward()
            tr_s.step(batch)
        return loss

    stitched_ips = time_loop(stitched_step)

    _log("captured step %s: bit-parity check (%d steps)"
         % (dtype, PARITY_STEPS))
    net_p, tr_p = build(seed=1)
    prog_p = tr_p.capture(net_p, gluon.loss.SoftmaxCrossEntropyLoss())
    net_q, tr_q = build(seed=1)
    for _ in range(PARITY_STEPS):
        prog_p(x, y)
        with autograd.record():
            loss = loss_fn(net_q(x), y)
        loss.backward()
        tr_q.step(batch)
    worst = 0.0
    bitwise = True
    for k, p in net_q.collect_params().items():
        a = p.data().astype("float32").asnumpy()
        b = net_p.collect_params()[k].data().astype("float32").asnumpy()
        if not np.array_equal(a, b):
            bitwise = False
            denom = np.abs(a) + 1e-8
            worst = max(worst, float(np.max(np.abs(a - b) / denom)))

    row = {"imgs_per_sec": round(captured_ips, 2),
           "stitched_imgs_per_sec": round(stitched_ips, 2),
           "speedup_vs_stitched": round(captured_ips / stitched_ips, 3),
           "batch": batch, "dtype": dtype,
           "bit_parity": {"steps": PARITY_STEPS, "bitwise": bitwise,
                          "worst_rel_diff": worst},
           "capture": {"paths": rep["paths"],
                       "fallbacks": rep["fallbacks"],
                       "provenance": [p["provenance"]
                                      for p in rep["programs"]],
                       "segments": [s["segment"] for s in
                                    rep["programs"][0]["segments"]]}}
    if fused_ref and fused_ref.get("imgs_per_sec"):
        row["vs_fused_trainer"] = round(
            captured_ips / fused_ref["imgs_per_sec"], 3)
    return row


def _bench_zero3_captured(batch=64, iters=10, dtype="bfloat16"):
    """ZeRO-3 captured ResNet-50 on a dp=4 GlobalMesh (mx.shard): the
    whole-step program with dp-sharded params + optimizer state,
    reduce-scattered gradient buckets and on-demand param gathers,
    against the unsharded captured reference on the SAME mesh
    (replicated weight update — the arXiv 2004.13336 baseline).
    Reports per-device param+state bytes for replicated / ZeRO-1 /
    ZeRO-3, the step-time delta, the priced wire bytes (reduce-scatter
    vs all-reduce), and a 3-step bit-parity block (sharding must change
    layout, never math).  On the CPU drill the 4 'devices' are virtual;
    on a pod they are 4 real chips — same program either way."""
    import numpy as np

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, shard
    from mxnet_tpu.gluon.model_zoo import vision

    PARITY_STEPS = 3
    devs = jax.devices()
    if len(devs) < 4:
        return {"error": "needs >= 4 devices for the dp=4 mesh "
                         "(have %d)" % len(devs)}
    gm = shard.GlobalMesh(dp=4, devices=devs[:4])

    def build(zero, seed=0):
        mx.random.seed(seed)
        net = vision.resnet50_v1()
        net.initialize()
        if dtype != "float32":
            net.cast(dtype)
        net.hybridize()
        trainer = gluon.Trainer(
            net.collect_params(), "sgd",
            {"learning_rate": 0.05, "momentum": 0.9,
             "multi_precision": dtype != "float32"},
            zero=zero, mesh=gm)
        prog = trainer.capture(net,
                               gluon.loss.SoftmaxCrossEntropyLoss())
        return net, trainer, prog

    rs = np.random.RandomState(0)
    x = nd.array(rs.rand(batch, 3, 224, 224).astype(np.float32)) \
        .astype(dtype)
    y = nd.array(rs.randint(0, 1000, batch).astype(np.int32))

    def time_loop(prog):
        for _ in range(WARMUP):
            loss = prog(x, y)
        float(loss.mean().asnumpy())  # hard sync
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = prog(x, y)
        float(loss.mean().asnumpy())
        return batch * iters / (time.perf_counter() - t0)

    def device_bytes(net, trainer):
        return {
            "params": shard.device_bytes(
                [p.data() for p in net.collect_params().values()]),
            "state": shard.device_bytes(
                [trainer._states[i] for i in trainer._states]),
        }

    _log("zero3 captured %s: unsharded mesh reference" % dtype)
    net_u, tr_u, prog_u = build(0)
    unsharded_ips = time_loop(prog_u)
    rep_u = prog_u.report()
    if rep_u["paths"]["captured"] == 0:
        raise RuntimeError("capture degraded: %s" % rep_u["fallbacks"][:1])
    bytes_u = device_bytes(net_u, tr_u)

    _log("zero3 captured %s: ZeRO-3 timing" % dtype)
    net_z, tr_z, prog_z = build(3)
    z3_ips = time_loop(prog_z)
    rep_z = prog_z.report()
    if rep_z["paths"]["captured"] == 0:
        raise RuntimeError("zero3 capture degraded: %s"
                           % rep_z["fallbacks"][:1])
    bytes_z3 = device_bytes(net_z, tr_z)

    _log("zero3 captured %s: ZeRO-1 byte reference" % dtype)
    net_1, tr_1, prog_1 = build(1)
    prog_1(x, y)  # one placed step is enough for the residency numbers
    bytes_z1 = device_bytes(net_1, tr_1)

    _log("zero3 captured %s: bit-parity block (%d steps)"
         % (dtype, PARITY_STEPS))
    net_a, _, prog_a = build(3, seed=1)
    net_b, _, prog_b = build(0, seed=1)
    for _ in range(PARITY_STEPS):
        prog_a(x, y)
        prog_b(x, y)
    worst = 0.0
    bitwise = True
    for k, p in net_b.collect_params().items():
        a = p.data().astype("float32").asnumpy()
        b = net_a.collect_params()[k].data().astype("float32").asnumpy()
        if not np.array_equal(a, b):
            bitwise = False
            worst = max(worst, float(np.max(
                np.abs(a - b) / (np.abs(a) + 1e-8))))
    parity = {"steps": PARITY_STEPS, "bitwise": bitwise,
              "worst_rel_diff": worst}
    if not bitwise:
        # expected for deep conv residual nets: GSPMD keeps per-layer
        # partitioning freedom in multi-branch graphs, and the ulp-
        # level reduction-order differences BN statistics amplify over
        # ~50 layers.  Matmul-dominated forwards ARE bit-identical —
        # asserted in test_shard.py / make zero-smoke — so the drift
        # here measures conv/BN layout sensitivity, not update math.
        parity["note"] = ("non-bitwise drift is conv/BN layout "
                          "sensitivity (see test_shard.py for the "
                          "bitwise weight-update-sharding proof)")

    prog_row = rep_z["programs"][0]
    return {
        "imgs_per_sec": round(z3_ips, 2),
        "unsharded_captured_imgs_per_sec": round(unsharded_ips, 2),
        "step_time_vs_unsharded": round(unsharded_ips / z3_ips, 3),
        "batch": batch, "dtype": dtype, "dp": gm.dp,
        "device_bytes": {"replicated": bytes_u, "zero1": bytes_z1,
                         "zero3": bytes_z3},
        "state_bytes_vs_replicated": round(
            bytes_z3["state"] / max(1, bytes_u["state"]), 4),
        "param_bytes_vs_replicated": round(
            bytes_z3["params"] / max(1, bytes_u["params"]), 4),
        "wire_bytes_per_step": prog_row["wire"],
        "bit_parity": parity,
        "capture": {"paths": rep_z["paths"],
                    "fallbacks": rep_z["fallbacks"],
                    "collective": [s for s in prog_row["segments"]
                                   if s["segment"] == "allreduce"][0]},
    }


def _bench_shard_tp(batch=64, iters=10):
    """mx.shard phase 2 tensor-parallel rows on a dp=2 x mdl=2 mesh
    (4 devices, virtual on the CPU drill): the gather-mode captured
    step vs the mdl=1 captured reference at the same dp — step-time
    delta, per-device param+state residency (the ISSUE bar:
    < 60% of unsharded), a 3-step parity bit (gather mode must be
    bitwise), the priced mdl all-gather wire bytes, the tp x zero
    interaction row (ZeRO-3 composed with mdl=2 -> ~1/(dp*mdl)
    storage), and a sharded-decode block proving the per-bucket
    program table compiles once (serve_decode_compile_total delta 0)
    while KV pages live head-sharded at 1/mdl."""
    import numpy as np

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, serve, shard, telemetry
    from mxnet_tpu.gluon import nn

    PARITY_STEPS = 3
    DIN, HID, DOUT = 256, 512, 64
    devs = jax.devices()
    if len(devs) < 4:
        return {"error": "needs >= 4 devices for the dp=2 x mdl=2 "
                         "mesh (have %d)" % len(devs)}

    def build(mdl, zero=0, seed=0):
        mx.random.seed(seed)
        net = nn.HybridSequential()
        net.add(nn.Dense(HID, activation="relu", in_units=DIN),
                nn.Dense(HID, activation="relu", in_units=HID),
                nn.Dense(HID, activation="relu", in_units=HID),
                nn.Dense(DOUT, in_units=HID))
        net.initialize()
        net.hybridize()
        gm = shard.GlobalMesh(dp=2, mdl=mdl,
                              devices=devs[:2 * mdl])
        trainer = gluon.Trainer(net.collect_params(), "adam",
                                {"learning_rate": 1e-3},
                                zero=zero, mesh=gm)
        prog = trainer.capture(net, gluon.loss.L2Loss())
        return net, trainer, prog

    rs = np.random.RandomState(0)
    x = nd.array(rs.rand(batch, DIN).astype(np.float32))
    y = nd.array(rs.rand(batch, DOUT).astype(np.float32))

    def time_loop(prog):
        for _ in range(WARMUP):
            loss = prog(x, y)
        float(loss.mean().asnumpy())
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = prog(x, y)
        float(loss.mean().asnumpy())
        return iters / (time.perf_counter() - t0)

    def residency(net, trainer):
        return {"params": shard.device_bytes(
                    [p.data() for p in net.collect_params().values()]),
                "state": shard.device_bytes(
                    [trainer._states[i] for i in trainer._states])}

    _log("shard_tp: mdl=1 captured reference")
    net_r, tr_r, prog_r = build(1)
    ref_sps = time_loop(prog_r)
    bytes_r = residency(net_r, tr_r)

    _log("shard_tp: mdl=2 gather-mode timing")
    net_t, tr_t, prog_t = build(2)
    tp_sps = time_loop(prog_t)
    rep = prog_t.report()
    if rep["paths"]["captured"] == 0:
        raise RuntimeError("tp capture degraded: %s"
                           % rep["fallbacks"][:1])
    bytes_t = residency(net_t, tr_t)
    tp_ratio = (bytes_t["params"] + bytes_t["state"]) \
        / max(1, bytes_r["params"] + bytes_r["state"])

    _log("shard_tp: parity block (%d steps)" % PARITY_STEPS)
    net_a, _, prog_a = build(2, seed=1)
    net_b, _, prog_b = build(1, seed=1)
    for _ in range(PARITY_STEPS):
        prog_a(x, y)
        prog_b(x, y)
    bitwise = all(
        np.array_equal(net_a.collect_params()[k].data().asnumpy(),
                       net_b.collect_params()[k].data().asnumpy())
        for k in net_a.collect_params())

    _log("shard_tp: zero3 x mdl=2 interaction row")
    net_z, tr_z, prog_z = build(2, zero=3)
    z_sps = time_loop(prog_z)
    bytes_z = residency(net_z, tr_z)

    _log("shard_tp: sharded decode block")
    mx.random.seed(0)
    blk = serve.TinyDecoder(vocab_size=64, num_layers=2,
                            num_heads=2, head_dim=8)
    blk.initialize()
    gm1 = shard.GlobalMesh(dp=1, mdl=2, devices=devs[:2])
    runner = serve.DecodeRunner(
        blk, config=serve.DecodeConfig(
            page_size=4, pool_pages=32, max_live=2,
            max_new_tokens=8, max_context=16,
            prefill_lengths=(8,), batch_sizes=(1, 2)),
        mesh=gm1)
    runner.warm_up()
    before = telemetry.value("serve_decode_compile_total")
    sched = serve.DecodeScheduler(runner)
    try:
        futs = [sched.submit(p, max_new_tokens=8)
                for p in ([1, 2, 3], [4, 5], [6, 7, 8, 9])]
        toks = [f.result(timeout=120)["tokens"] for f in futs]
    finally:
        sched.stop()
    total_kv = runner.pool.k.nbytes + runner.pool.v.nbytes
    decode = {
        "tokens_emitted": sum(len(t) for t in toks),
        "compile_delta_after_warmup": telemetry.value(
            "serve_decode_compile_total") - before,
        "kv_sharding": runner.pool.stats()["kv_sharding"],
        "kv_device_bytes_vs_unsharded": round(
            runner.pool.device_bytes() / max(1, total_kv), 4),
    }

    prog_row = rep["programs"][0]
    return {
        "steps_per_sec": round(tp_sps, 2),
        "unsharded_steps_per_sec": round(ref_sps, 2),
        "step_time_vs_unsharded": round(ref_sps / tp_sps, 3),
        "batch": batch, "dp": 2, "mdl": 2,
        "tp_mode": prog_row["tp_mode"],
        "device_bytes": {"unsharded": bytes_r, "tp": bytes_t,
                         "tp_zero3": bytes_z},
        "residency_vs_unsharded": round(tp_ratio, 4),
        "residency_bar_060": tp_ratio < 0.60,
        "bit_parity": {"steps": PARITY_STEPS, "bitwise": bitwise},
        "wire_bytes_per_step": prog_row["wire"],
        "tp_x_zero3": {
            "steps_per_sec": round(z_sps, 2),
            "residency_vs_unsharded": round(
                (bytes_z["params"] + bytes_z["state"])
                / max(1, bytes_r["params"] + bytes_r["state"]), 4)},
        "sharded_decode": decode,
        "capture": {"paths": rep["paths"],
                    "fallbacks": rep["fallbacks"]},
    }


def _bench_shard_pipeline(iters=8):
    """mx.shard phase 2 pipeline row: 1F1B with per-stage CAPTURED
    programs (AOT-attached, donated dead buffers) on a pp=2 mesh vs
    the single-program FusedTrainer — step time, the schedule's
    simulated bubble fraction vs the measured peak in-flight bound,
    per-stage program provenance, and a loss-trajectory parity
    check."""
    import numpy as np

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.gluon import nn

    if len(jax.devices()) < 2:
        return {"error": "needs >= 2 devices for the pp=2 mesh"}
    mesh = parallel.make_mesh({"pp": 2})
    np.random.seed(0)
    X = np.random.rand(32, 64).astype(np.float32)
    Y = np.random.randint(0, 16, 32).astype(np.int32)

    def net(seed):
        mx.random.seed(seed)
        n = nn.HybridSequential()
        n.add(nn.Dense(128, activation="relu"),
              nn.Dense(128, activation="relu"),
              nn.Dense(128, activation="relu"), nn.Dense(16))
        n.initialize()
        return n

    pipe = parallel.PipelineTrainer(
        net(11), loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.05},
        mesh=mesh, num_microbatches=8, schedule="1f1b")
    ref = parallel.FusedTrainer(
        net(11), loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.05})

    def time_loop(step):
        for _ in range(WARMUP):
            loss = step(X, Y)
        float(loss.asscalar())
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step(X, Y)
        float(loss.asscalar())
        return iters / (time.perf_counter() - t0), float(loss.asscalar())

    _log("shard_pipeline: 1f1b captured stages")
    pipe_sps, pipe_loss = time_loop(pipe.step)
    _log("shard_pipeline: fused single-program reference")
    ref_sps, ref_loss = time_loop(ref.step)
    rep = pipe.report()
    return {
        "steps_per_sec": round(pipe_sps, 2),
        "fused_steps_per_sec": round(ref_sps, 2),
        "step_time_vs_fused": round(ref_sps / pipe_sps, 3),
        "stages": rep["stages"], "microbatches": rep["microbatches"],
        "schedule": rep["schedule"],
        "bubble_fraction_sim": round(rep["bubble_fraction"], 4),
        "peak_inflight": rep["peak_inflight"],
        "stage_provenance": rep["provenance"],
        "donation": rep["donation"],
        "loss_rel_diff": round(abs(pipe_loss - ref_loss)
                               / max(1e-8, abs(ref_loss)), 6),
    }


def main():
    extra = {}
    # JAX's persistent compilation cache, before anything compiles
    from mxnet_tpu.compile import jax_cache_dir

    _log("start; compile cache at %s" % jax_cache_dir())
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("bench: JAX found no TPU (platform %r); a CPU run "
                         "is not a benchmark" % dev.platform)
    extra["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}
    extra["peak_bf16_tflops"] = _peak_bf16_tflops()  # unknown kind: exit
    _log("backend ok: %s" % (jax.devices(),))
    before = _telemetry_totals()
    mon_before = _monitor_summary(reset_peak=True)
    bf16 = _attach_telemetry(_bench_resnet("bfloat16", 128), before,
                             mon_before)
    extra["resnet50_bf16"] = bf16
    _log("resnet50 bf16 done: %s img/s" % bf16["imgs_per_sec"])

    def _attn(T):
        import sys as _sys

        _sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "benchmark"))
        try:
            from attention_bench import bench_one
        finally:
            _sys.path.pop(0)
        return {"pallas": bench_one(T, "pallas", iters=5),
                "blockwise": bench_one(T, "blockwise", iters=5)}

    def _loader_fed_resnet():
        import argparse
        import sys as _sys

        _sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "benchmark"))
        try:
            import data_bench
        finally:
            _sys.path.pop(0)
        import tempfile

        ns = argparse.Namespace(images=1024, size=224, batch=128,
                                threads=min(8, os.cpu_count() or 1))
        with tempfile.TemporaryDirectory() as td:
            rec = os.path.join(td, "bench.rec")
            data_bench.make_recordio(rec, ns.images, ns.size)
            return data_bench.train_from_loader(rec, ns)

    for phase, fn, key in (
            ("resnet50_fp32", lambda: _bench_resnet("float32", 128),
             "resnet50_fp32"),
            ("bert", _bench_bert, "bert_base_pretrain_bf16"),
            ("lstm_lm", _bench_lstm_lm, "lstm_lm_650"),
            ("resnet50_infer_bf16", _bench_resnet_infer,
             "resnet50_infer_bf16_bs32"),
            # int8 post-training quantization (reference perf.md int8
            # inference rows; MXU int8 path)
            ("resnet50_infer_int8", _bench_resnet_infer_int8,
             "resnet50_infer_int8_bs32"),
            # larger batch fills the MXU better; tracked as a secondary
            # row (BASELINE's headline config stays bs128)
            ("resnet50_bf16_bs256",
             lambda: _bench_resnet("bfloat16", 256, iters=10),
             "resnet50_bf16_bs256"),
            # imperative gluon.Trainer path (multi-tensor fused apply:
            # O(groups) update programs/step vs ~160 eager chains)
            ("resnet50_imperative_trainer", _bench_imperative_trainer,
             "resnet50_imperative_trainer_bf16"),
            # mx.step whole-step capture: fwd+loss+bwd+allreduce+apply
            # as ONE donated XLA program/step; row carries the delta vs
            # the stitched imperative path AND the FusedTrainer
            # headline, plus a bit-parity check of final params
            ("resnet50_captured_step",
             lambda: _bench_captured_step(
                 fused_ref=extra.get("resnet50_bf16")),
             "resnet50_captured_step_bf16"),
            # mx.shard ZeRO-3 on a dp=4 mesh: sharded params/state
            # (~1/4 residency per device), reduce-scattered gradient
            # buckets, on-demand param gathers; bit-parity vs the
            # unsharded captured reference on the same mesh
            ("resnet50_zero3_captured", _bench_zero3_captured,
             "resnet50_zero3_captured_vdev"),
            # mx.shard phase 2: gather-mode tensor parallelism on a
            # dp=2 x mdl=2 mesh (step time + residency vs unsharded,
            # bitwise parity, tp x zero3 interaction, sharded-decode
            # compile flatness) and 1F1B captured pipeline stages
            ("shard_tp_step", _bench_shard_tp, "shard_tp_step"),
            ("shard_pipeline_step", _bench_shard_pipeline,
             "shard_pipeline_step"),
            # mx.serve.decode: paged KV-cache + continuous batching
            # under concurrent mixed load — tokens/s, TTFT and
            # per-token p50/p99, page-pool occupancy
            ("serve_decode", _bench_serve_decode,
             "serve_decode_continuous_batching"),
            # mx.fleet router front-end: per-request routing overhead
            # (refresh + p2c pick) + e2e latency through two local
            # replicas, and the prefill->decode handoff blob size
            ("fleet", _bench_fleet, "fleet_router"),
            # mx.serve.cache per-token-cost plane: radix prefix-cache
            # prefill savings on a shared 2k system prompt (TTFT cold
            # vs hit, zero compiles under session churn) + speculative
            # decoding accepted-tokens-per-target-step
            ("serve_cache", _bench_serve_cache,
             "serve_cache_per_token_cost"),
            # flash fwd+bwd kernel vs blockwise recompute
            ("attention_T2k", lambda: _attn(2048), "attention_T2k"),
            ("attention_T8k", lambda: _attn(8192), "attention_T8k"),
            # end-to-end loader-fed training: every batch
            # rides RecordIO -> decode workers -> device transfer
            ("resnet50_bf16_loader_fed", _loader_fed_resnet,
             "resnet50_bf16_loader_fed")):
        before = _telemetry_totals()
        mon_before = _monitor_summary(reset_peak=True)
        extra[key] = _attach_telemetry(fn(), before, mon_before)
        _log("%s done" % phase)
    print(json.dumps({
        "metric": "resnet50_train_bf16_bs128_imgs_per_sec",
        "value": bf16["imgs_per_sec"],
        "unit": "img/s",
        "vs_baseline": round(
            bf16["imgs_per_sec"] / RESNET_BASELINE_IMGS_PER_SEC, 3),
        "extra": extra,
    }))


if __name__ == "__main__":
    main()
