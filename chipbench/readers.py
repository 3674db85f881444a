"""What the per-layer metric readers share: the arithmetic from the reduced
trace (``trace.reduce``) to a metric, and the functions that count a
kernel's operations and bytes from its shapes.

A reader (``metrics/<name>.py``) is ``read(ctx) -> number or None``; ``ctx``
holds the reduced trace, the cell's configuration, traffic and builder, the
chips' peaks (``peaks.json``) and this module under ``"readers"``.  A reader
that finds nothing to read returns None, never 0.
"""
from __future__ import annotations

import statistics

# operations of one flash-attention kernel call, in units of
# (batch x heads) * Tq * Tk * D multiply-adds x 2: forward S=QK^T and PV;
# dq recomputes S and forms dP=dO V^T and dQ=dS K; dkv recomputes S and forms
# dV=P^T dO, dP=dO V^T and dK=dS^T Q (ops/pallas_attention.py's two-kernel
# backward, after benchmark/attention_bench.py's forward count)
FLASH_MATMULS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
# bf16 tensors of (bh, T, D) and float32 rows of (bh, T) a call moves
FLASH_TENSORS = {"flash_fwd": (4, 1), "flash_bwd_dq": (5, 2),
                 "flash_bwd_dkv": (6, 2)}


def flash_ops_bytes(kernel, bh, t, d, itemsize=2):
    """(operations, bytes) one call of ``kernel`` needs, from its shapes."""
    tensors, rows = FLASH_TENSORS[kernel]
    return (2.0 * FLASH_MATMULS[kernel] * bh * t * t * d,
            float(tensors * bh * t * d * itemsize + rows * bh * t * 4))


def least_seconds(ops, nbytes, peaks):
    """(the least time the chip could take, which peak bounds it)."""
    by_ops = ops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), "compute" if by_ops >= by_bytes else "memory"


def fullest(ctx):
    t = ctx["trace"]
    return t["devices"][t["fullest"]]


def step_mfu_pct(ctx):
    t = ctx["trace"]
    return 100.0 * ctx["ops_per_step"] * t["host_steps"] / t["window_s"] \
        / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])


def launch_gap_ms(ctx):
    gaps = fullest(ctx)["launch_gaps_ms"]
    return statistics.median(gaps) if gaps else None


def device_idle_pct(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - fullest(ctx)["busy_s"] / t["window_s"])


def flash_roofline_pct(ctx, kernels):
    """Least time by the peaks for the calls of ``kernels`` found in the
    trace, over their summed device time."""
    dev = fullest(ctx)
    bh, t, d = ctx["builder"].attention_shape(ctx["cfg"], ctx["traffic"],
                                              ctx["chips"])
    least = spent = 0.0
    for kernel in kernels:
        for name, seconds in dev["op_seconds"].items():
            if kernel in name:
                ops, nbytes = flash_ops_bytes(kernel, bh, t, d)
                least += dev["op_counts"][name] * least_seconds(
                    ops, nbytes, ctx["peaks"])[0]
                spent += seconds
    return 100.0 * least / spent if spent else None


def allreduce_exposed_pct(ctx):
    """All-reduce time during which nothing else runs on that chip, as a
    share of the traced window."""
    dev = fullest(ctx)
    if not dev["collective_s"]:
        return None
    return 100.0 * dev["collective_exposed_s"] / ctx["trace"]["window_s"]
