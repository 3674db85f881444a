"""What the per-layer readers of a model whose layers call the flash kernels
under DIFFERENT rules share (``laguna_xs2``: causal and causal
sliding-window calls with grouped KV heads): the operations and bytes of a
kernel's call under each rule, and the rooflines of the calls of one rule.

The rules share the kernels' names (``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv``), so the reduced trace's ``op_seconds``, keyed by name,
cannot tell them apart: the calls are told apart by the scope
``mx.attn.<kind>`` in the operation's ``op_name``, which ``spans.py`` reads
from the compiled HLO in the trace's file.

Beside ``block_readers.py`` and built on it (its ``MATMULS`` / ``TENSORS``
and its one parse of the run's trace); a reader (``metrics/<name>.py``) says
``import rule_readers``.  A reader that finds nothing to read returns None,
never 0, and nothing raises on a trace of a program that lacks the scopes or
with a builder that does not describe ``attention_calls``.
"""
from __future__ import annotations

import block_readers  # chipbench/block_readers.py
import spans  # chipbench/spans.py


def flash_ops_bytes(kernel, call, itemsize=2):
    """(operations, bytes) one call of ``kernel`` needs under its rule;
    ``call`` as an entry of the builder's ``attention_calls``: only the
    pairs the rule allows (``call["pairs"]`` a head) count, so a kernel
    that works tile by tile reads under 100% of its roofline; K, V, dK and
    dV once a KV head."""
    b, h, kv = call["batch"], call["heads"], call["kv_heads"]
    t, d = call["seq"], call["head_dim"]
    per_q, per_kv, rows = block_readers.TENSORS[kernel]
    return (2.0 * block_readers.MATMULS[kernel] * b * h * call["pairs"] * d,
            float((per_q * h + per_kv * kv) * b * t * d * itemsize
                  + rows * b * h * t * 4))


def kernel_calls(loaded, kind, kernel):
    """(count, device seconds) of the calls of ``kernel`` under
    ``mx.attn.<kind>`` on the chip that spent most time in them."""
    best = (0, 0.0)
    for dev in loaded["devices"].values():
        mine = [op for op in dev["ops"]
                if op[2].split(".")[0] == kernel
                and "mx.attn.%s" % kind in op[3]]
        seconds = sum(op[1] - op[0] for op in mine) / 1e9
        if seconds > best[1]:
            best = (len(mine), seconds)
    return best


def flash_roofline_pct_of(loaded, calls, peaks, readers, kind, kernels):
    """Least time by the peaks for the calls of ``kernels`` under
    ``mx.attn.<kind>`` found in the trace, over their summed device time."""
    least = spent = 0.0
    for kernel in kernels:
        count, seconds = kernel_calls(loaded, kind, kernel)
        ops, nbytes = flash_ops_bytes(kernel, calls[kind])
        least += count * readers.least_seconds(ops, nbytes, peaks)[0]
        spent += seconds
    return 100.0 * least / spent if spent else None


def flash_roofline_pct(ctx, kind, kernels):
    """For ``metrics/<name>.py``: the roofline share of the run's trace, or
    None."""
    path = spans.newest()
    if not path or not hasattr(ctx.get("builder"), "attention_calls"):
        return None
    calls = ctx["builder"].attention_calls(ctx["cfg"], ctx["traffic"],
                                           ctx["chips"])
    if kind not in calls:
        return None
    return flash_roofline_pct_of(block_readers._loaded(path), calls,
                                 ctx["peaks"], ctx["readers"], kind, kernels)
