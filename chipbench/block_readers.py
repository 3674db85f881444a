"""What the per-layer readers of the block-diffusion MoE cells share: the
operations and bytes of a flash kernel's call under the block-diffusion mask
with grouped KV heads, and the device time a step spends under one of the
program's named scopes (``mx.moe.route``, ``mx.moe.experts``,
``mx.attn.block_diffusion``).

Beside ``readers.py`` and ``spans.py`` and built on them; a reader
(``metrics/<name>.py``) says ``import block_readers``: run.py's directory is
on ``sys.path`` when it runs as a script.  A reader that finds nothing to
read returns None, never 0, and nothing raises on a trace of a program that
lacks the scopes.
"""
from __future__ import annotations

import functools

import spans  # chipbench/spans.py

# matrix products of a call, each 2 x pairs x D operations a head: forward
# S=QK^T, PV; dq recomputes S, forms dP=dO V^T, dQ=dS K; dkv recomputes S,
# forms dV=P^T dO, dP=dO V^T, dK=dS^T Q (ops/pallas_attention.py)
MATMULS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
# tensors the algorithm has to move: (one a QUERY head, one a KV head,
# float32 rows a query head); K and V are counted once a KV head, and so are
# dK and dV (the kernel writes one a query head and XLA adds the group up:
# that traffic is the implementation's, not the algorithm's)
TENSORS = {"flash_fwd": (2, 2, 1),       # q, o | k, v | lse
           "flash_bwd_dq": (3, 2, 2),    # q, do, dq | k, v | lse, delta
           "flash_bwd_dkv": (2, 4, 2)}   # q, do | k, v, dk, dv | lse, delta


def flash_bd_ops_bytes(kernel, call, itemsize=2):
    """(operations, bytes) one call of ``kernel`` needs under the
    block-diffusion mask; ``call`` as the builder's ``attention_call``:
    only the ``L**2 + L*b`` allowed pairs count, so a kernel that works
    tile by tile reads under 100% of its roofline."""
    b, h, kv = call["batch"], call["heads"], call["kv_heads"]
    seq, d = call["seq"], call["head_dim"]
    pairs = seq * seq + seq * call["block"]
    per_q, per_kv, rows = TENSORS[kernel]
    t = 2 * seq
    return (2.0 * MATMULS[kernel] * b * h * pairs * d,
            float((per_q * h + per_kv * kv) * b * t * d * itemsize
                  + rows * b * h * t * 4))


def flash_bd_roofline_pct(ctx, kernels):
    """Least time by the peaks for the calls of ``kernels`` found in the
    trace, over their summed device time, on the busiest chip."""
    if not hasattr(ctx["builder"], "attention_call"):
        return None
    readers = ctx["readers"]
    dev = readers.fullest(ctx)
    call = ctx["builder"].attention_call(ctx["cfg"], ctx["traffic"],
                                         ctx["chips"])
    least = spent = 0.0
    for kernel in kernels:
        for name, seconds in dev["op_seconds"].items():
            if kernel in name:
                ops, nbytes = flash_bd_ops_bytes(kernel, call)
                least += dev["op_counts"][name] * readers.least_seconds(
                    ops, nbytes, ctx["peaks"])[0]
                spent += seconds
    return 100.0 * least / spent if spent else None


def scope_ms_of(loaded, scope):
    """Device ms a step of the operations whose ``op_name`` holds ``scope``
    (forward, backward and recomputation alike), inside the step program's
    executions on the busiest chip; None where no operation holds it."""
    best = None
    for dev in loaded["devices"].values():
        steps = spans.step_modules(dev["modules"])
        inside, j = [], 0
        for op in dev["ops"]:
            while j < len(steps) and steps[j][1] <= op[0]:
                j += 1
            if j < len(steps) and steps[j][0] <= op[0]:
                inside.append(op)
        owned = spans.exclusive(inside)
        busy = sum(owned)
        under = sum(ns for op, ns in zip(inside, owned) if scope in op[3])
        if steps and (best is None or busy > best[0]):
            best = (busy, under / 1e6 / len(steps))
    return best[1] if best and best[1] > 0 else None


@functools.lru_cache(maxsize=1)
def _loaded(path):
    return spans.load(path)


def scope_ms(scope):
    """For ``metrics/<name>.py``: ``scope_ms_of`` the run's trace, or None."""
    path = spans.newest()
    return scope_ms_of(_loaded(path), scope) if path else None
