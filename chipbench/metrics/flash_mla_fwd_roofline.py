"""``flash_fwd`` under ``mx.attn.mla`` (latent attention in the expanded
form): least time by the chip's peaks for its calls' ALLOWED pairs (``T (T +
1) / 2`` a head at a head's 256 dimensions) and bytes (Q, K, V and O once a
head: the expanded form has as many KV heads as query heads, and the rotary
key that all heads share is counted a head, as the kernel reads it) over
their summed device time."""
import rule_readers  # chipbench/rule_readers.py


def read(ctx):
    return rule_readers.flash_roofline_pct(ctx, "mla", ["flash_fwd"])
