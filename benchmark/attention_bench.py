"""Attention microbenchmark: Pallas flash kernel vs blockwise-JAX path.

VERDICT r3 item 7 deliverable: fwd+bwd timings and MFU at long sequence
lengths, demonstrating the flash backward kernel beats the
recompute-through-blockwise path at T=8k.

Usage:
    python benchmark/attention_bench.py [T ...]     # default 2048 8192

Prints one JSON line per (T, impl) with ms/iter and MFU.  FLOP model
(dense-equivalent attention flops, the standard flash-attention
accounting): fwd = 4·B·H·T²·D (QKᵀ and PV, MACs×2); bwd = 2.5× fwd
(dQ, dK, dV matmuls + recomputed P).
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _peak_bf16_tflops():
    """The one peaks table is bench.py's; an unknown device kind exits."""
    from bench import _peak_bf16_tflops as peak

    return peak()


def bench_one(T, impl, B=4, H=12, D=64, dtype=jnp.bfloat16, iters=10,
              block=512):
    from mxnet_tpu.ops import pallas_attention as pa

    rs = np.random.RandomState(0)
    q = jax.device_put(rs.randn(B, H, T, D).astype(np.float32)).astype(dtype)
    k = jax.device_put(rs.randn(B, H, T, D).astype(np.float32)).astype(dtype)
    v = jax.device_put(rs.randn(B, H, T, D).astype(np.float32)).astype(dtype)

    if impl == "pallas":
        def fwd(q, k, v):
            return pa.flash_attention(q, k, v, causal=True, block_q=block,
                                      block_k=block)
    else:
        def fwd(q, k, v):
            return pa.blockwise_attention(q, k, v, causal=True,
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
    # causal halves the realized flops
    fwd_flops = 4.0 * B * H * T * T * D / 2.0
    total = fwd_flops * (1.0 + 2.5)
    tflops = total / dt / 1e12
    return {"T": T, "impl": impl, "ms": round(dt * 1e3, 2),
            "model_tflops": round(tflops, 1),
            "mfu": round(tflops / _peak_bf16_tflops(), 3)}


def main():
    from mxnet_tpu.compile import jax_cache_dir

    jax_cache_dir()
    Ts = [int(a) for a in sys.argv[1:]] or [2048, 8192]
    for T in Ts:
        for impl in ("pallas", "blockwise"):
            row = bench_one(T, impl)
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
