"""``flash_fwd`` under the block-diffusion mask: least time by the chip's
peaks for its calls' ALLOWED pairs (``L**2 + L*b`` a head) and bytes (K and V
once a KV head) over their summed device time."""
import block_readers  # chipbench/block_readers.py


def read(ctx):
    return block_readers.flash_bd_roofline_pct(ctx, ["flash_fwd"])
