"""Attention microbenchmark: the Pallas flash kernels, alone on the chip.

    python benchmark/attention_bench.py cells [--impl FILE] [--dtype D]
        the shapes the benchmark's cells send to the kernels, forward and
        backward in one program that starts from the projections' rows,
        ``(B, T, H*D)`` and ``(B, T, Hkv*D)``, and goes through
        ``multi_head_attention(impl="pallas")``; each KERNEL's device time
        is read from a profiler trace by the kernel's name, and the whole
        program's beside it, so the layout work around the kernels is in
        the comparison:
          bert_t512      B 64 x H 12 x T 512 x D 64, no mask
                         (bert_base_t512: 12 such calls a step)
          sdar_bd4k      B 2 x 32 query heads over 4 KV heads x 8,192 x D
                         128 under block_diffusion_mask(4096, 4)
                         (sdar_30b_a3b_bd4k: 6 layers, forward twice)
          laguna_causal  B 1 x 48 over 8 KV heads x 8,192 x 128, causal
          laguna_win     B 1 x 64 over 8 KV heads x 8,192 x 128, window 512
                         (laguna_xs2_t8k: 2 and 3 layers, forward twice)
        One JSON line a kernel: ms a call, the products' TFLOP/s and their
        share of the chip's bf16 peak; then ``all three`` and ``through
        multi_head_attention`` (every device operation of the program, and
        ``around_ms``, what of it is not the kernels).  ``--impl FILE``
        times another version of ``mxnet_tpu/ops/pallas_attention.py`` (say
        the parent commit's, ``git show HEAD~1:mxnet_tpu/ops/
        pallas_attention.py``) in the same process tree: the kernel-level
        before/after of a change to the kernels.  A file from before the
        rows layout (no ``heads_per_step``: its ``flash_attention`` takes
        ``(B, H, T, D)``) is called as its ``multi_head_attention`` called
        it, with the head-major transposes around it.  ``--dtype float32``
        feeds the kernels float32 (their products then run at float32).

    python benchmark/attention_bench.py placed
        what a decoder layer does to q and to k between their projection
        and the kernels (per-head RMSNorm where the model has QK-norm,
        rotary positions, the kernels' head-major view), one pass at a time
        at the shapes of laguna_xs2_t8k's window layers (64 and 8 heads of
        128, whole rotary), its full layers (48 and 8, YaRN tables over 64
        of 128) and sdar_30b_a3b_bd4k's (32 and 4 with the norm, two
        sequences): the Pallas pass (``ops/pallas_rotary.py``, form
        ``kernel``) against XLA's operations (``rms_norm`` /
        ``rotary_embedding`` and a transpose, form ``xla``), forward and
        backward each a program of its own.  One JSON line a (shape, form,
        pass): every device operation's ms a call from the profiler's
        trace, and the GB/s of the bytes the pass has to move (the rows in
        and out once; with a norm the backward reads the rows again).

    python benchmark/attention_bench.py [T ...]     # default 2048 8192
        causal forward + backward, Pallas kernels against the blockwise-JAX
        path, host clock: one JSON line per (T, impl).

Operation count (multiply-adds x 2 over the pairs the mask allows): the
forward runs 2 products a pair (QK^T, PV); the backward runs 7 in two
kernels: ``flash_bwd_dq`` 3 (S again, dP = dO V^T, dQ = dS K) and
``flash_bwd_dkv`` 4 (S again, dP again, dV = P^T dO, dK = dS^T Q).  That is
3.5 x the forward as executed; 2.5 x (5 products) is what ONE backward
kernel would need, and is not what these kernels run.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import re
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

# products a pair, as chipbench/readers.py counts them
KERNEL_PRODUCTS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}


def _peak_bf16_tflops():
    """The one peaks table is bench.py's; an unknown device kind exits."""
    from bench import _peak_bf16_tflops as peak

    return peak()


def _kernels(impl=None):
    """``mxnet_tpu.ops.pallas_attention``, or the version of it in the file
    ``impl`` (loaded beside the tree's own, which stays as it is)."""
    from mxnet_tpu.ops import pallas_attention as pa

    if impl is None:
        return pa
    spec = importlib.util.spec_from_file_location(
        "mxnet_tpu.ops._attention_bench_impl", impl)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_shapes(pa):
    """name -> (B, T, query heads, KV heads, D, the call's rule as
    keywords, allowed pairs a query head)."""
    seq, block, T, w = 4096, 4, 8192, 512
    shapes = {
        "bert_t512": (64, 512, 12, 12, 64, {}, 512 * 512),
        "sdar_bd4k": (2, 2 * seq, 32, 4, 128,
                      {"mask": pa.block_diffusion_mask(seq, block)},
                      seq * seq + seq * block),
        "laguna_causal": (1, T, 48, 8, 128, {"causal": True},
                          T * (T + 1) // 2),
    }
    if hasattr(pa, "window_mask"):
        shapes["laguna_win"] = (1, T, 64, 8, 128,
                                {"mask": pa.window_mask(w)},
                                w * T - w * (w - 1) // 2)
    return shapes


def attend(pa, H, Hkv, kw):
    """``(q, k, v)`` rows -> rows through the kernels of ``pa``, the way
    ``multi_head_attention(impl="pallas")`` reaches them."""
    if hasattr(pa, "heads_per_step"):
        # the tree's own caller, on this file's kernels
        import mxnet_tpu.ops as ops
        from mxnet_tpu.ops.nn import multi_head_attention

        ops.pallas_attention = pa
        return lambda q, k, v: multi_head_attention.fn(
            q, k, v, num_heads=H, num_kv_heads=Hkv, impl="pallas", **kw)

    def heads(x, n):
        B, T, _ = x.shape
        return x.reshape(B, T, n, -1).transpose(0, 2, 1, 3)

    def before_rows(q, k, v):
        out = pa.flash_attention(heads(q, H), heads(k, Hkv), heads(v, Hkv),
                                 **kw)
        return out.transpose(0, 2, 1, 3).reshape(q.shape)

    return before_rows


def device_ms(trace_dir):
    """``({kernel: (calls, summed device ms)}, all operations' summed
    device ms)`` from the profiler's trace: the ``XLA Ops`` events of the
    first TPU plane, the kernels by their names."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    found, every = {}, 0.0
    for plane in ProfileData.from_file(path).planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                every += e.duration_ns / 1e6
                head = e.name.split(" = ", 1)[0]
                for kernel in KERNEL_PRODUCTS:  # no name holds another
                    if kernel in head:
                        n, ms = found.get(kernel, (0, 0.0))
                        found[kernel] = (n + 1, ms + e.duration_ns / 1e6)
                        break
        break
    return found, every


def traced_ms(step, args, iters):
    """``device_ms`` of ``iters`` calls of ``step(*args)`` under the
    profiler."""
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(iters):
            out = step(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        return device_ms(d)


def bench_cell(name, pa, dtype, iters, impl_label):
    B, T, H, Hkv, D, kw, pairs = cell_shapes(pa)[name]
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, w = (jax.random.normal(key, (B, T, n * D), jnp.float32)
                  .astype(dtype) for key, n in zip(keys, (H, Hkv, Hkv, H)))
    call = attend(pa, H, Hkv, kw)

    def loss(q, k, v):
        return (call(q, k, v).astype(jnp.float32)
                * w.astype(jnp.float32)).sum()

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    t0 = time.perf_counter()
    jax.block_until_ready(step(q, k, v))
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(step(q, k, v))
    found, every = traced_ms(step, (q, k, v), iters)
    peak = _peak_bf16_tflops()
    label = {"shape": name, "impl": impl_label,
             "dtype": jnp.dtype(dtype).name}
    rows, total = [], 0.0
    for kernel, products in KERNEL_PRODUCTS.items():
        calls, ms = found.get(kernel, (0, 0.0))
        if not calls:
            raise SystemExit("attention_bench: no %s event in the trace "
                             "(found %s)" % (kernel, sorted(found)))
        ms /= calls
        total += ms
        tflops = 2.0 * products * B * H * pairs * D / (ms * 1e-3) / 1e12
        rows.append(dict(label, kernel=kernel, calls=calls,
                         ms=round(ms, 4), tflops=round(tflops, 2),
                         peak_share_pct=round(100.0 * tflops / peak, 2)))
    rows.append(dict(label, kernel="all three", ms=round(total, 4),
                     compile_s=round(compile_s, 1)))
    rows.append(dict(label, kernel="through multi_head_attention",
                     ms=round(every / iters, 4),
                     around_ms=round(every / iters - total, 4)))
    return rows


def placed_shapes():
    """name -> (B, T, heads, D, QK-norm, rotary keywords)."""
    whole, yarn = {"theta": 1e4}, {
        "rotary_dim": 64, "factor": 1.2,
        "inv_freq": tuple(1e4 ** (-i / 32.0) for i in range(32))}
    return {"laguna_win_q": (1, 8192, 64, 128, False, whole),
            "laguna_win_k": (1, 8192, 8, 128, False, whole),
            "laguna_full_q": (1, 8192, 48, 128, False, yarn),
            "laguna_full_k": (1, 8192, 8, 128, False, yarn),
            "sdar_q": (2, 8192, 32, 128, True, {"theta": 1e6}),
            "sdar_k": (2, 8192, 4, 128, True, {"theta": 1e6})}


def placed_forms(heads, D, norm, rotary):
    """form -> ``f(x, gain, positions)``: rows to the kernels' view."""
    from mxnet_tpu.ops import nn, pallas_rotary as pr

    def kernel(x, gain, positions):
        return pr.placed(x, pr.rotary_tables(positions, D, **rotary), heads,
                         rotary.get("rotary_dim"), gain if norm else None)

    def xla(x, gain, positions):
        B, T, _ = x.shape
        h = x.reshape(B, T, heads, D)
        if norm:
            h = nn.rms_norm.fn(h, gain)
        h = nn.rotary_embedding.fn(h, positions, **rotary)
        return h.transpose(0, 2, 1, 3).reshape(B * heads, T, 1, D)

    return {"kernel": kernel, "xla": xla}


def bench_placed(name, iters):
    B, T, heads, D, norm, rotary = placed_shapes()[name]
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (B, T, heads * D)).astype(jnp.bfloat16)
    dy = jax.random.normal(keys[1], (B * heads, T, 1, D)) \
        .astype(jnp.bfloat16)
    gain = (1 + 0.1 * jax.random.normal(keys[2], (D,))).astype(jnp.bfloat16)
    positions = jnp.arange(T, dtype=jnp.int32)
    rows, outs = [], {}
    for form, f in placed_forms(heads, D, norm, rotary).items():
        passes = {
            "forward": jax.jit(lambda x, gain, dy, f=f: f(x, gain,
                                                          positions)),
            "backward": jax.jit(lambda x, gain, dy, f=f: jax.vjp(
                lambda x, gain: f(x, gain, positions), x, gain)[1](dy)),
        }
        for which, step in passes.items():
            outs[form, which] = jax.block_until_ready(step(x, gain, dy))
            ms = traced_ms(step, (x, gain, dy), iters)[1] / iters
            moved = x.nbytes * (3 if norm and which == "backward" else 2)
            rows.append({"shape": name, "form": form, "pass": which,
                         "ms": round(ms, 4),
                         "gbytes_per_s": round(moved / ms / 1e6, 1)})
    for row in rows:    # the largest difference between the two forms
        leaves = zip(*(jax.tree_util.tree_leaves(outs[form, row["pass"]])
                       for form in ("kernel", "xla")))
        row["forms_differ_by"] = max(float(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)).max())
            for a, b in leaves)
    return rows


def bench_one(T, impl, B=4, H=12, D=64, dtype=jnp.bfloat16, iters=10,
              block=512):
    from mxnet_tpu.ops import pallas_attention as pa

    rs = np.random.RandomState(0)
    q, k, v = (jax.device_put(rs.randn(B, T, H, D).astype(np.float32))
               .astype(dtype) for _ in range(3))

    if impl == "pallas":
        def fwd(q, k, v):
            return pa.flash_attention(q, k, v, causal=True, block_q=block,
                                      block_k=block)
    else:
        def fwd(q, k, v):       # the scan runs head-major
            return pa.blockwise_attention(
                *(x.transpose(0, 2, 1, 3) for x in (q, k, v)), causal=True,
                block_k=block)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    out = step(q, k, v)
    jax.block_until_ready(out)
    float(np.asarray(out[0][0, 0, 0, 0]))  # hard sync
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step(q, k, v)
    jax.block_until_ready(out)
    float(np.asarray(out[0][0, 0, 0, 0]))
    dt = (time.perf_counter() - t0) / iters
    # causal halves the realized flops; 2 products forward, 7 backward
    fwd_flops = 4.0 * B * H * T * T * D / 2.0
    total = fwd_flops * (1.0 + 3.5)
    tflops = total / dt / 1e12
    return {"T": T, "impl": impl, "ms": round(dt * 1e3, 2),
            "model_tflops": round(tflops, 1),
            "mfu": round(tflops / _peak_bf16_tflops(), 3)}


def main():
    from mxnet_tpu.compile import jax_cache_dir

    jax_cache_dir()
    if sys.argv[1:2] == ["placed"]:
        if jax.default_backend() != "tpu":
            raise SystemExit("attention_bench placed: times come from a "
                             "TPU's trace; the backend here is %r"
                             % jax.default_backend())
        for name in placed_shapes():
            for row in bench_placed(name, 10):
                print(json.dumps(row), flush=True)
        return
    if sys.argv[1:2] == ["cells"]:
        ap = argparse.ArgumentParser(prog="attention_bench.py cells")
        ap.add_argument("--impl", default=None,
                        help="another version of pallas_attention.py")
        ap.add_argument("--dtype", default="bfloat16")
        args = ap.parse_args(sys.argv[2:])
        if jax.default_backend() != "tpu":
            raise SystemExit("attention_bench cells: kernel times come from "
                             "a TPU's trace; the backend here is %r"
                             % jax.default_backend())
        pa = _kernels(args.impl)
        for name in cell_shapes(pa):
            for row in bench_cell(name, pa, jnp.dtype(args.dtype), 10,
                                  args.impl or "tree"):
                print(json.dumps(row), flush=True)
        return
    Ts = [int(a) for a in sys.argv[1:]] or [2048, 8192]
    for T in Ts:
        for impl in ("pallas", "blockwise"):
            row = bench_one(T, impl)
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
