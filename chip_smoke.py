#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py               one TPU chip: phases 0-4 below
    python chip_smoke.py --chips 4     four chips: the mesh paths and what
                                       they are compared with, nothing else
    python chip_smoke.py --rehearse    the same phases at tiny sizes on
                                       whatever backend JAX has (the CPU
                                       here); reports the platform it ran on

One process, no child that needs the chip.  Any failure is a traceback and a
non-zero exit; no phase's exception is caught to carry on.  Without
``--rehearse`` a run that finds no TPU fails before it prints anything.

Phases (each prints one JSON line; the last line of stdout is the result):

0. device       jax.devices(), versions, compile-cache dir, native library
                rebuilt from src/native
1. kernels      ops.pallas_attention.flash_attention fwd+bwd, bf16, against
                multi_head_attention(impl="dense") in float32; in-kernel
                dropout: kept fraction, and fwd/dq/dkv regenerate one mask
2. train_bert   BERT-base bf16 (f32 masters), batch 16 x T=512, dropout 0.1,
                Adam, parallel.FusedTrainer: 5 steps on one seeded batch
3. train_resnet resnet50_v1 bf16 batch 128 at 224^2, SGD momentum,
                FusedTrainer 5 steps; then 3 steps through trainer.capture
4. serve_decode serve.Server(decode=DecodeRunner(TinyDecoder at d=2048 ...))
                answering 8 concurrent requests over HTTP; tokens equal the
                unpaged greedy reference

``--chips 4``: (i) BERT-base under FusedTrainer(mesh=make_mesh({"dp": 4}))
against the same seed and batch on a one-device mesh, then one dp=4 step with
dropout; (ii) the captured step at the same widths under
shard.GlobalMesh(dp=2, mdl=2) with ZeRO-3 against the unsharded captured step.
"""
from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

# bf16 has 8 bits of mantissa: a bf16 kernel against a float32 reference is
# held to 2% of the reference's largest magnitude, element by element
BF16_TOL = 2e-2
# two runs of one model that differ in how the batch is laid over devices
# (reduction order inside bf16 matmuls): relative difference of the losses
LOSS_TOL = 1e-2

REAL = {
    "kernels": {"bert": (16, 12, 512, 64), "t2k": (4, 12, 2048, 64),
                "t8k": (1, 12, 8192, 64), "tile_block": 128},
    "bert": {"vocab": 30522, "units": 768, "hidden": 3072, "layers": 12,
             "heads": 12, "batch": 16, "seq": 512},
    "resnet": {"model": "resnet50_v1", "batch": 128, "size": 224},
    # the widths ROADMAP R1 cuts OLMoE to for one chip
    "decode": {"layers": 8, "heads": 16, "head_dim": 128, "hidden": 8192,
               "vocab": 32000, "max_context": 4096, "page_size": 16,
               "pool_pages": 2304, "prefill": (128, 512), "clients": 8,
               "prompt": (32, 512), "new": 32, "min_pool_bytes": 2 << 30},
    "mesh_batch": 64,
}
TINY = {
    "kernels": {"bert": (2, 2, 256, 64), "t2k": (1, 2, 256, 64),
                "t8k": (1, 2, 512, 64), "tile_block": 128},
    "bert": {"vocab": 512, "units": 128, "hidden": 256, "layers": 2,
             "heads": 2, "batch": 4, "seq": 256},
    "resnet": {"model": "resnet18_v1", "batch": 4, "size": 32},
    "decode": {"layers": 2, "heads": 2, "head_dim": 8, "hidden": 32,
               "vocab": 64, "max_context": 64, "page_size": 4,
               "pool_pages": 96, "prefill": (8, 16), "clients": 8,
               "prompt": (2, 16), "new": 8, "min_pool_bytes": 0},
    "mesh_batch": 8,
}


class Compiles:
    """Seconds and count of XLA backend compiles (a persistent-cache hit
    counts, at its retrieval time), from JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


@contextlib.contextmanager
def not_cached():
    """Programs compiled in here are not WRITTEN to JAX's persistent cache.

    The cache is capped where JAX_COMPILATION_CACHE_MAX_SIZE is set (192 MiB
    on the chip machines) and evicts the least recently used entry, and the
    step programs of phases 2-4 already fill most of that (BERT-base 45 MiB,
    ResNet-50 25 MiB each way).  What only CHECKS — reference math, recovered
    masks, about 115 MiB of it — must not push out what a warm run is for:
    with it cached every phase of the second run missed."""
    import jax

    knob = "jax_persistent_cache_min_compile_time_secs"
    was = getattr(jax.config, knob)
    jax.config.update(knob, 1e9)
    try:
        yield
    finally:
        jax.config.update(knob, was)


def run_phase(name, fn, compiles, *args):
    t0, c0 = time.perf_counter(), compiles.seconds
    checked = fn(*args)
    line = {"phase": name, "seconds": round(time.perf_counter() - t0, 2),
            "compile_seconds": round(compiles.seconds - c0, 2),
            "checked": checked}
    print(json.dumps(line), flush=True)
    return line


def on_device(tree, device):
    """Every array leaf of ``tree`` lives on exactly ``device``."""
    import jax

    leaves = [a for a in jax.tree_util.tree_leaves(tree)
              if isinstance(a, jax.Array)]
    assert leaves, "nothing to check"
    for a in leaves:
        assert a.devices() == {device}, (a.shape, a.devices(), device)
    return len(leaves)


# ---------------------------------------------------------------------------
# phase 0
# ---------------------------------------------------------------------------
def phase_device(rehearse, chips, cache_dir):
    import jax
    import jaxlib

    import mxnet_tpu as mx
    from mxnet_tpu import native

    dev = jax.devices()
    # what runs must be built from committed files: build/ is git-ignored
    # but may be on disk
    for so in glob.glob(os.path.join(REPO, "build", "*.so")):
        os.remove(so)
    t0 = time.time()
    assert native.available(), "native host library did not build"
    so = os.path.join(REPO, "build", "libmxtpu_native.so")
    assert os.path.getmtime(so) >= t0 - 1, "native library was not rebuilt"
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 - not installed: report, don't guess
        libtpu = None
    # waitall() must fence on block_until_ready alone: everything enqueued
    # before it is ready when it returns
    import jax.numpy as jnp

    x = jnp.ones((2048, 2048), jnp.float32)
    for _ in range(8):
        x = x @ x * 1e-3
    mx.waitall()
    assert x.is_ready(), "waitall() returned before enqueued work finished"
    assert len(dev) >= chips, "need %d device(s), have %d" % (chips, len(dev))
    return {"platform": dev[0].platform, "device_kind": dev[0].device_kind,
            "count": len(dev), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu,
            "compile_cache_dir": cache_dir,
            "jax_compilation_cache_dir_env":
                os.environ.get("JAX_COMPILATION_CACHE_DIR"),
            "native_built_this_run": True, "waitall_fences": True,
            "default_context": str(mx.current_context()),
            "rehearsal": rehearse}


# ---------------------------------------------------------------------------
# phase 1
# ---------------------------------------------------------------------------
def _dense_ref(q, k, v, do, causal, keep=None, dropout_p=0.0):
    """float32 reference through ops.nn.multi_head_attention(impl="dense");
    with ``keep`` (the kernel's own recovered mask) the dropout branch of
    the same math.  Heads go through in chunks so the (T, T) scores of the
    long shapes stay small."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.nn import multi_head_attention

    dense = multi_head_attention.fn        # the op's pure jax function
    B, H, T, D = q.shape
    step = max(1, min(H, (1 << 28) // (B * T * T)))

    def fold(x):                       # (B, h, T, D) -> (B, T, h*D)
        return x.transpose(0, 2, 1, 3).reshape(B, T, -1)

    def unfold(x, h):
        return x.reshape(B, T, h, D).transpose(0, 2, 1, 3)

    @jax.jit
    def chunk(q, k, v, do, keep):
        h = q.shape[1]
        q, k, v, do = (x.astype(jnp.float32) for x in (q, k, v, do))

        def f(q, k, v):
            if keep is None:
                return unfold(dense(
                    fold(q), fold(k), fold(v), num_heads=h, causal=causal,
                    impl="dense"), h)
            # dense math with the given mask in place of a drawn one
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (D ** 0.5)
            w = jax.nn.softmax(s, -1) * keep / (1.0 - dropout_p)
            return jnp.einsum("bhqk,bhkd->bhqd", w, v)

        out, vjp = jax.vjp(f, q, k, v)
        return (out,) + vjp(do)

    parts = []
    with jax.default_matmul_precision("float32"):
        for h0 in range(0, H, step):
            sl = slice(h0, h0 + step)
            parts.append(chunk(q[:, sl], k[:, sl], v[:, sl], do[:, sl],
                               None if keep is None else keep[:, sl]))
    return [jnp.concatenate(p, axis=1) for p in zip(*parts)]


def _rel_err(got, want):
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _rows_call(pa, q, k, v, **kw):
    """``flash_attention`` on this check's head-major (B, H, T, D) arrays:
    the kernels take (B, T, H, D), so the transposes stand here."""
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    return pa.flash_attention(q, k, v, **kw).transpose(0, 2, 1, 3)


def _flash_case(shape, causal, rehearse, dropout_p=0.0, key=None,
                block=None):
    """Compile fwd+bwd, assert it is the Mosaic kernel, run it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import pallas_attention as pa

    rs = np.random.RandomState(shape[2])
    q, k, v, do = (jnp.asarray(rs.randn(*shape), jnp.bfloat16)
                   for _ in range(4))

    def fwd_bwd(q, k, v, do):
        out, vjp = jax.vjp(
            lambda q, k, v: _rows_call(
                pa, q, k, v, causal=causal, dropout_p=dropout_p,
                dropout_key=key, block_q=block, block_k=block), q, k, v)
        return (out,) + vjp(do)

    compiled = jax.jit(fwd_bwd).lower(q, k, v, do).compile()
    calls = compiled.as_text().count("tpu_custom_call")
    if not rehearse:
        # not the interpreter, not the blockwise scan
        assert calls >= 3, "expected 3 Mosaic kernels, found %d" % calls
    return (q, k, v, do), compiled(q, k, v, do), calls


def _recovered_mask(qkv, key, dropout_p, block):
    """The kernel's keep mask, recovered from its forward: with V a block
    of identity columns, out[:, d] is the dropped probability of key
    k0 + d — nonzero exactly where the mask kept it."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_attention as pa

    q, k = qkv[0], qkv[1]
    B, H, T, D = q.shape
    # small scores: every softmax probability is far from underflow
    q = q * 0.1

    @jax.jit
    def cols(q, k, k0):
        v = (jnp.arange(T)[:, None] == k0 + jnp.arange(D)[None, :])
        v = jnp.broadcast_to(v.astype(q.dtype), (B, H, T, D))
        return _rows_call(pa, q, k, v, dropout_p=dropout_p,
                          dropout_key=key, block_q=block,
                          block_k=block) > 0

    return jnp.concatenate([cols(q, k, k0) for k0 in range(0, T, D)],
                           axis=-1)


def phase_kernels(sizes, rehearse):
    with not_cached():
        return _check_kernels(sizes["kernels"], rehearse)


def _check_kernels(S, rehearse):
    import jax
    import jax.numpy as jnp

    out = {"tolerance_rel_to_ref_max": BF16_TOL, "cases": {}}
    for name, causal in (("bert", False), ("t2k", True), ("t8k", True)):
        args, got, calls = _flash_case(S[name], causal, rehearse)
        want = _dense_ref(*args, causal)
        errs = {n: _rel_err(g, w)
                for n, g, w in zip(("out", "dq", "dk", "dv"), got, want)}
        assert max(errs.values()) <= BF16_TOL, (name, errs)
        for g in got:
            assert g.shape == S[name] and g.dtype == jnp.bfloat16
            assert bool(jnp.isfinite(g.astype(jnp.float32)).all())
        out["cases"][name] = {"shape": list(S[name]), "causal": causal,
                              "tpu_custom_calls": calls,
                              "rel_err": {n: round(e, 5)
                                          for n, e in errs.items()}}

    # in-kernel dropout, BERT's shape and rate
    p, key = 0.1, jax.random.PRNGKey(7)
    args, got, calls = _flash_case(S["bert"], False, rehearse, p, key)
    keep = _recovered_mask(args, key, p, None)
    kept = float(jnp.mean(keep))
    assert abs(kept - (1 - p)) <= 0.01, kept
    # dq and dkv regenerate the forward's mask: gradients match the dense
    # math under the RECOVERED mask (a different mask in either kernel
    # would be an O(1) error, not a bf16 one)
    want = _dense_ref(*args, False, keep=keep, dropout_p=p)
    errs = {n: _rel_err(g, w)
            for n, g, w in zip(("out", "dq", "dk", "dv"), got, want)}
    assert max(errs.values()) <= BF16_TOL, errs
    # masks are drawn per (batch, head) and per tile: two heads, and two
    # tiles of one head, agree on a cell with probability keep^2 + p^2
    tiles = _recovered_mask(args, key, p, S["tile_block"])
    b = S["tile_block"]
    agree = {"heads": float(jnp.mean(keep[0, 0] == keep[0, 1])),
             "batch": float(jnp.mean(keep[0, 0] == keep[1, 0]))
             if S["bert"][0] > 1 else None,
             "tiles": float(jnp.mean(tiles[0, 0, :b, :b]
                                     == tiles[0, 0, :b, b:2 * b]))}
    expect = (1 - p) ** 2 + p ** 2
    for what, a in agree.items():
        assert a is None or abs(a - expect) <= 0.02, (what, a, expect)
    assert abs(float(jnp.mean(tiles)) - (1 - p)) <= 0.01
    out["dropout"] = {"p": p, "kept_fraction": round(kept, 5),
                      "tpu_custom_calls": calls,
                      "rel_err_vs_dense_with_recovered_mask":
                          {n: round(e, 5) for n, e in errs.items()},
                      "mask_agreement": agree,
                      "mask_agreement_expected": round(expect, 4)}
    return out


# ---------------------------------------------------------------------------
# phase 2 (and the --chips 4 BERT runs)
# ---------------------------------------------------------------------------
def _pretrain_net(S, dropout):
    """bench.py's PretrainStep: BERT with MLM + NSP heads, no padding mask
    (so attention takes the flash kernels at T=512)."""
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo import bert as bert_zoo

    class PretrainStep(HybridBlock):
        def __init__(self):
            super().__init__()
            self.model = bert_zoo.BERTForPretraining(
                vocab_size=S["vocab"], units=S["units"],
                hidden_size=S["hidden"], num_layers=S["layers"],
                num_heads=S["heads"], dropout=dropout)

        def forward(self, tokens, types, positions):
            return self.model(tokens, types, valid_length=None,
                              masked_positions=positions)

    return PretrainStep()


def _bert_trainer(S, dropout, mesh=None):
    """BERT pretraining exactly as bench.py builds it."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import parallel

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
    net = _pretrain_net(S, dropout)
    net.initialize()
    return net, parallel.FusedTrainer(
        net, loss_fn=pretrain_loss, optimizer="adam",
        optimizer_params={"learning_rate": 1e-4}, dtype="bfloat16",
        mesh=mesh)


def _bert_batch(S, batch):
    import numpy as np

    seq, vocab = S["seq"], S["vocab"]
    n_mask = max(1, int(seq * 0.15))
    rs = np.random.RandomState(0)
    x = (rs.randint(0, vocab, (batch, seq)).astype(np.int32),
         rs.randint(0, 2, (batch, seq)).astype(np.int32),
         np.sort(rs.choice(seq, (batch, n_mask)), axis=1).astype(np.int32))
    y = (rs.randint(0, vocab, (batch, n_mask)).astype(np.int32),
         rs.randint(0, 2, batch).astype(np.int32))
    return x, y


def _train(trainer, x, y, steps, compiles):
    """``steps`` steps on one batch: finite losses, and no compile after
    the first step."""
    import math

    losses, after_first = [], None
    for i in range(steps):
        losses.append(float(trainer.step(x, y).asnumpy()))
        if i == 0:
            after_first = compiles.count
    assert all(math.isfinite(v) for v in losses), losses
    assert compiles.count == after_first, \
        "%d compile(s) after step 1" % (compiles.count - after_first)
    return losses


def phase_train_bert(sizes, rehearse, compiles):
    import jax

    S = sizes["bert"]
    dev = jax.devices()[0]
    _net, trainer = _bert_trainer(S, dropout=0.1)
    x, y = _bert_batch(S, S["batch"])
    x, y = (tuple(jax.device_put(v) for v in t) for t in (x, y))
    losses = _train(trainer, x, y, 5, compiles)
    assert losses[4] < losses[0], losses
    n_params = on_device(trainer.params, dev)
    n_state = on_device(trainer.state_dict()["opt_state"], dev)
    # the three flash kernels of every layer are in the step program
    kernels = trainer._lower(x, y).as_text().count("tpu_custom_call")
    if not rehearse:
        assert kernels == 3 * S["layers"], kernels
    return {"config": S, "dropout": 0.1, "losses": [round(v, 4)
                                                    for v in losses],
            "fifth_below_first": True, "compiles_after_step_1": 0,
            "arrays_on_device": {"params": n_params, "opt_state": n_state,
                                 "device": str(dev)},
            "flash_kernels_in_step": kernels}


# ---------------------------------------------------------------------------
# phase 3
# ---------------------------------------------------------------------------
def phase_train_resnet(sizes, rehearse, compiles, ctx):
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, parallel, telemetry
    from mxnet_tpu.gluon.model_zoo import vision

    S = sizes["resnet"]
    dev = ctx.jax_device
    rs = np.random.RandomState(0)
    xh = rs.rand(S["batch"], 3, S["size"], S["size"]).astype(np.float32)
    yh = rs.randint(0, 1000, S["batch"]).astype(np.int32)

    # as bench.py builds it
    mx.random.seed(0)
    net = getattr(vision, S["model"])()
    net.initialize()
    trainer = parallel.FusedTrainer(
        net, loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
        dtype="bfloat16")
    losses = _train(trainer, jax.device_put(xh), jax.device_put(yh), 5,
                    compiles)
    assert losses[4] < losses[0], losses
    fused = {"losses": [round(v, 4) for v in losses],
             "params": on_device(trainer.params, dev),
             "opt_state": on_device(trainer.state_dict()["opt_state"], dev)}
    del trainer, net

    # the same model through the captured step, everything given ctx
    mx.random.seed(0)
    net = getattr(vision, S["model"])()
    net.initialize(ctx=ctx)
    net.cast("bfloat16")
    net.hybridize()
    gtrainer = gluon.Trainer(
        net.collect_params(), "sgd",
        {"learning_rate": 0.05, "momentum": 0.9, "multi_precision": True})
    program = gtrainer.capture(net, gluon.loss.SoftmaxCrossEntropyLoss())
    x = nd.array(xh, ctx=ctx).astype("bfloat16")
    y = nd.array(yh, ctx=ctx)
    eager0 = telemetry.value("trainer_eager_updates_total")
    closs = [float(program(x, y).mean().asnumpy()) for _ in range(3)]
    rep = program.report()
    assert rep["paths"] == {"captured": 3, "stitched": 0}, rep["paths"]
    assert rep["fallbacks"] == [], rep["fallbacks"]
    assert telemetry.value("trainer_eager_updates_total") == eager0
    assert all(np.isfinite(closs)), closs
    captured = {
        "losses": [round(v, 4) for v in closs], "paths": rep["paths"],
        "fallbacks": 0, "eager_updates_moved": False,
        "params": on_device([p.data()._data
                             for p in net.collect_params().values()], dev),
        "opt_state": on_device(
            jax.tree_util.tree_map(lambda s: getattr(s, "_data", s),
                                   list(gtrainer._states.values())), dev)}
    return {"config": S, "fused": fused, "captured": captured,
            "device": str(dev)}


# ---------------------------------------------------------------------------
# phase 4
# ---------------------------------------------------------------------------
def _reference_decode(blk, prompt, served, ctx, dtype):
    """Greedy decode WITHOUT paging, fed the SERVED tokens: a contiguous
    K/V cache kept on the host, one block call per token through the plain
    gluon path.  It is the bit-identity reference of
    tests/python/unittest/test_serve_decode.py with one change: the cache
    has a fixed capacity and the model's own ``ctx_lengths`` mask hides the
    unwritten tail, so the hybridized block compiles twice (prefill, decode
    step) and not once per context length — on the chip an exact-length
    cache costs a compile of every op at every step.

    Returns the reference's argmax at every step.  While the served tokens
    equal it this is the reference's free-running decode; where one does
    not, ``gaps`` says by how much of the step's largest logit magnitude it
    trails the argmax and ``ranks`` how many of the vocabulary's logits lie
    above it (diagnostics for the failure message)."""
    import numpy as np

    from mxnet_tpu import nd

    def arr(a):
        return nd.array(np.asarray(a), ctx=ctx)

    def judge(logits, token):
        row = logits.asnumpy()[0].astype(np.float32)
        return (int(np.argmax(row)),
                float((row.max() - row[token]) / np.abs(row).max()),
                int((row > row[token]).sum()))

    L, H, D = blk.num_layers, blk.num_kv_heads, blk.head_dim
    blk.hybridize()
    n = len(prompt)
    zero = nd.zeros((1, L, 0, H, D), ctx=ctx, dtype=dtype)
    logits, kn, vn = blk(
        arr(np.array([prompt], np.int32)), zero, zero,
        arr(np.array([0], np.int32)), arr(np.array([n], np.int32)))
    picks = [judge(logits, served[0])]
    kn, vn = kn.asnumpy(), vn.asnumpy()                # [1, T, L, H, D]
    ks = np.zeros((1, L, n + len(served), H, D), kn.dtype)
    vs = np.zeros_like(ks)
    ks[:, :, :n], vs[:, :, :n] = (a.transpose(0, 2, 1, 3, 4)
                                  for a in (kn, vn))
    for prev, token in zip(served, served[1:]):
        logits, kn, vn = blk(
            arr(np.array([[prev]], np.int32)), arr(ks), arr(vs),
            arr(np.array([n], np.int32)), arr(np.array([1], np.int32)))
        ks[:, :, n], vs[:, :, n] = kn.asnumpy()[:, 0], vn.asnumpy()[:, 0]
        n += 1
        picks.append(judge(logits, token))
    return tuple(list(col) for col in zip(*picks))


def phase_serve_decode(sizes, rehearse, compiles, ctx):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import serve, telemetry

    S = sizes["decode"]
    dev = ctx.jax_device
    mx.random.seed(0)
    blk = serve.TinyDecoder(vocab_size=S["vocab"], num_layers=S["layers"],
                            num_heads=S["heads"], head_dim=S["head_dim"],
                            hidden=S["hidden"])
    # variance-preserving weights: at d=2048 the default uniform init makes
    # this norm-free decoder's activations grow ~20x a layer (on the chip
    # every request then came back HTTP 400), and bf16 rounding with them
    blk.initialize(mx.initializer.Xavier("gaussian", "in", 1.0), ctx=ctx)
    blk.cast("bfloat16")
    cfg = serve.DecodeConfig(
        page_size=S["page_size"], pool_pages=S["pool_pages"],
        max_live=S["clients"], max_new_tokens=S["new"],
        max_context=S["max_context"], prefill_lengths=S["prefill"],
        batch_sizes=(S["clients"],), dtype="bfloat16")
    t0 = time.perf_counter()
    runner = serve.DecodeRunner(blk, ctx=ctx, config=cfg)
    warm_s = time.perf_counter() - t0
    assert warm_s < 120, "warm-up took %.0fs" % warm_s
    pool_bytes = runner.pool.k.nbytes + runner.pool.v.nbytes
    assert pool_bytes >= S["min_pool_bytes"], pool_bytes
    n_weights = on_device(runner._params, dev)
    on_device([runner.pool.k, runner.pool.v], dev)

    srv = serve.Server(decode=runner)
    assert srv.ready()
    host, port = srv.start_http()
    rs = np.random.RandomState(0)
    lo, hi = S["prompt"]
    lengths = [lo, hi] + [int(n) for n in
                          rs.randint(lo, hi + 1, S["clients"] - 2)]
    prompts = [[int(t) for t in rs.randint(0, S["vocab"], n)]
               for n in lengths]
    compiles0 = compiles.count
    builds0 = telemetry.value("serve_decode_compile_total")
    answers, errors = {}, []
    t_serve = time.perf_counter()

    def client(i):
        try:
            req = urllib.request.Request(
                "http://%s:%d/predict" % (host, port),
                data=json.dumps({"tokens": prompts[i],
                                 "max_new_tokens": S["new"]}).encode(),
                headers={"X-Request-Id": "smoke-%d" % i})
            with urllib.request.urlopen(req, timeout=600) as resp:
                answers[i] = json.load(resp)
        except urllib.error.HTTPError as exc:   # asserted on below
            errors.append((i, exc.code, exc.read().decode()[:500]))
        except Exception as exc:  # noqa: BLE001 - asserted on below
            errors.append((i, repr(exc)))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(S["clients"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join(900)
    serve_s = time.perf_counter() - t_serve
    try:
        assert not errors, errors
        assert sorted(answers) == list(range(S["clients"])), sorted(answers)
        for i, a in answers.items():
            assert len(a["tokens"]) == S["new"], (i, a)
            assert a["finish_reason"] == "length", (i, a)
        # no program was built, and nothing compiled, after warm-up
        assert telemetry.value("serve_decode_compile_total") == builds0
        served_compiles = compiles.count - compiles0
        assert served_compiles == 0, served_compiles
    finally:
        assert srv.shutdown(drain=True, timeout=60)
    assert runner.pool.in_use == 0
    runner.pool.check()
    on_device([runner.pool.k, runner.pool.v], dev)
    # the repo's own bit-identity contract: paged continuous batching
    # yields the tokens of the unpaged greedy decode
    served = answers[0]["tokens"]
    t_ref = time.perf_counter()
    with not_cached():
        want, gaps, ranks = _reference_decode(blk, prompts[0], served, ctx,
                                              "bfloat16")
    # exact: every served token is the reference's argmax after the served
    # tokens before it, so the reference ran free.  On a failure the gaps
    # and ranks say whether bf16 near-ties or a wrong decode broke it
    assert served == want, \
        {"served": served, "reference": want,
         "logit_gap_rel_to_step_max": gaps, "rank_in_reference": ranks}
    return {"config": {k: v for k, v in S.items()},
            "dtype": "bfloat16", "warm_up_seconds": round(warm_s, 1),
            "serve_seconds": round(serve_s, 1),
            "reference_seconds": round(time.perf_counter() - t_ref, 1),
            "buckets": sorted(runner.provenance()),
            "pool_bytes": int(pool_bytes), "weights_on_device": n_weights,
            "device": str(dev), "requests_answered": len(answers),
            "prompt_lengths": lengths, "compiles_after_warm_up": 0,
            "pool_check": "ok",
            "unpaged_reference": {"request": 0, "tokens": len(want),
                                  "tokens_equal": True}}


# ---------------------------------------------------------------------------
# --chips 4
# ---------------------------------------------------------------------------
def _spread(arrays, devices, rehearse):
    """Every device holds a shard of every array, and has used memory."""
    import jax

    want = set(devices)
    leaves = [a for a in jax.tree_util.tree_leaves(arrays)
              if isinstance(a, jax.Array)]
    assert leaves
    for a in leaves:
        have = {s.device for s in a.addressable_shards}
        assert have == want, (a.shape, have)
    peaks = {}
    for d in devices:
        stats = d.memory_stats()
        if stats is None:
            assert rehearse, "no memory_stats() on %s" % d
            continue
        peaks[str(d)] = int(stats["peak_bytes_in_use"])
        assert peaks[str(d)] > 0, d
    return {"arrays": len(leaves), "devices": len(want),
            "peak_bytes_in_use": peaks or "not reported by this backend"}


def _close(a, b):
    return all(abs(x - y) <= LOSS_TOL * max(abs(x), abs(y))
               for x, y in zip(a, b))


def phase_mesh_fused(sizes, rehearse, compiles):
    import jax

    from mxnet_tpu import parallel

    S, batch = sizes["bert"], sizes["mesh_batch"]
    devs = jax.devices()[:4]
    x, y = _bert_batch(S, batch)
    _n, one = _bert_trainer(S, 0.0, parallel.make_mesh({"dp": 1}, devs[:1]))
    ref = _train(one, x, y, 3, compiles)
    del one
    _n, four = _bert_trainer(S, 0.0, parallel.make_mesh({"dp": 4}, devs))
    got = _train(four, x, y, 3, compiles)
    assert _close(got, ref), (got, ref)
    text = four._lower(x, y).compile().as_text()
    kernels = text.count("tpu_custom_call")
    if not rehearse:
        assert kernels == 3 * S["layers"], kernels
    spread = _spread([four.params, four.state_dict()["opt_state"]], devs,
                     rehearse)
    del four
    # with dropout the dp=4 step must lower and run too
    _n, drop = _bert_trainer(S, 0.1, parallel.make_mesh({"dp": 4}, devs))
    dloss = _train(drop, x, y, 1, compiles)
    return {"config": S, "global_batch": batch, "loss_tolerance": LOSS_TOL,
            "losses_dp1": [round(v, 4) for v in ref],
            "losses_dp4": [round(v, 4) for v in got],
            "flash_kernels_in_dp4_step": kernels,
            "all_gathers_in_dp4_step": text.count("all-gather("),
            "dp4_dropout_step_loss": round(dloss[0], 4), "spread": spread}


def phase_mesh_captured(sizes, rehearse, compiles):
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, shard
    from mxnet_tpu.gluon.model_zoo import bert as bert_zoo

    S, batch = sizes["bert"], sizes["mesh_batch"]
    devs = jax.devices()[:4]
    ctx = mx.current_context()
    (tok, typ, pos), (mlab, nsp) = _bert_batch(S, batch)
    weights = np.ones(mlab.shape, np.float32)

    def loss_fn(outs, masked_labels, masked_weights, nsp_labels):
        return bert_zoo.pretraining_loss(
            outs[0].astype("float32"), outs[1].astype("float32"),
            masked_labels, masked_weights, nsp_labels)

    def run(gmesh, zero):
        mx.random.seed(0)
        net = _pretrain_net(S, 0.0)
        net.initialize(ctx=ctx)
        net.cast("bfloat16")
        net.hybridize()
        trainer = gluon.Trainer(
            net.collect_params(), "adam",
            {"learning_rate": 1e-4, "multi_precision": True},
            zero=zero, mesh=gmesh)
        prog = trainer.capture(net, loss_fn)
        data = tuple(nd.array(a, ctx=ctx)
                     for a in (tok, typ, pos))
        label = tuple(nd.array(a, ctx=ctx)
                      for a in (mlab, weights, nsp))
        losses = [float(prog(data, label).asnumpy()) for _ in range(3)]
        rep = prog.report()
        assert rep["paths"] == {"captured": 3, "stitched": 0}, rep
        assert rep["fallbacks"] == [], rep["fallbacks"]
        assert all(np.isfinite(losses)), losses
        return net, trainer, losses

    _net, _tr, ref = run(None, 0)
    del _net, _tr
    gm = shard.GlobalMesh(dp=2, mdl=2, devices=devs)
    net, trainer, got = run(gm, 3)
    assert _close(got, ref), (got, ref)
    spread = _spread(
        [[p.data()._data for p in net.collect_params().values()],
         jax.tree_util.tree_map(lambda s: getattr(s, "_data", s),
                                list(trainer._states.values()))],
        devs, rehearse)
    return {"config": S, "global_batch": batch, "mesh": gm.describe(),
            "zero": 3, "loss_tolerance": LOSS_TOL,
            "losses_unsharded": [round(v, 4) for v in ref],
            "losses_dp2_mdl2_zero3": [round(v, 4) for v in got],
            "fallbacks": 0, "spread": spread}


# ---------------------------------------------------------------------------
def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend JAX has; the last "
                         "line reports the platform it really ran on")
    args = ap.parse_args()

    sys.path.insert(0, REPO)
    from mxnet_tpu.compile import jax_cache_dir

    cache_dir = jax_cache_dir()
    import jax

    dev = jax.devices()
    if dev[0].platform != "tpu" and not args.rehearse:
        sys.exit("chip_smoke: JAX found no TPU (platform %r); --rehearse "
                 "runs the phases at tiny sizes on it" % dev[0].platform)
    import mxnet_tpu as mx

    sizes = TINY if args.rehearse else REAL
    compiles = Compiles()
    ctx = mx.current_context() if args.rehearse else mx.tpu(0)
    t0 = time.perf_counter()
    run_phase("device", phase_device, compiles, args.rehearse, args.chips,
              cache_dir)
    if args.chips == 4:
        run_phase("mesh_fused", phase_mesh_fused, compiles, sizes,
                  args.rehearse, compiles)
        run_phase("mesh_captured", phase_mesh_captured, compiles, sizes,
                  args.rehearse, compiles)
    else:
        run_phase("kernels", phase_kernels, compiles, sizes, args.rehearse)
        run_phase("train_bert", phase_train_bert, compiles, sizes,
                  args.rehearse, compiles)
        run_phase("train_resnet", phase_train_resnet, compiles, sizes,
                  args.rehearse, compiles, ctx)
        run_phase("serve_decode", phase_serve_decode, compiles, sizes,
                  args.rehearse, compiles, ctx)
    print(json.dumps({"phase": "total",
                      "seconds": round(time.perf_counter() - t0, 2),
                      "compile_seconds": round(compiles.seconds, 2)}),
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}),
        flush=True)


if __name__ == "__main__":
    main()
