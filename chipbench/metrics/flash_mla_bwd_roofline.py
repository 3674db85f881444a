"""``flash_bwd_dq`` + ``flash_bwd_dkv`` under ``mx.attn.mla``: least time by
the chip's peaks for the allowed pairs (3 and 4 products at a head's 256
dimensions; K, V, dK and dV once a head, the shared rotary key and its
gradient counted a head) over their summed device time."""
import rule_readers  # chipbench/rule_readers.py


def read(ctx):
    return rule_readers.flash_roofline_pct(
        ctx, "mla", ["flash_bwd_dq", "flash_bwd_dkv"])
