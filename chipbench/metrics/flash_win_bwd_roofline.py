"""``flash_bwd_dq`` + ``flash_bwd_dkv`` under ``mx.attn.window``: least time
by the chip's peaks for the allowed pairs (3 and 4 products) over their
summed device time."""
import rule_readers  # chipbench/rule_readers.py


def read(ctx):
    return rule_readers.flash_roofline_pct(
        ctx, "window", ["flash_bwd_dq", "flash_bwd_dkv"])
