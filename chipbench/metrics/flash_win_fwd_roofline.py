"""``flash_fwd`` under ``mx.attn.window``: least time by the chip's peaks for
its calls' ALLOWED pairs (``w T - w (w - 1) / 2`` a head) and bytes (K and V
once a KV head) over their summed device time."""
import rule_readers  # chipbench/rule_readers.py


def read(ctx):
    return rule_readers.flash_roofline_pct(ctx, "window", ["flash_fwd"])
