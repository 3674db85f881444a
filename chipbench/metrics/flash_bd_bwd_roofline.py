"""``flash_bwd_dq`` + ``flash_bwd_dkv`` under the block-diffusion mask: least
time by the chip's peaks for the allowed pairs (3 and 4 products) over their
summed device time."""
import block_readers  # chipbench/block_readers.py


def read(ctx):
    return block_readers.flash_bd_roofline_pct(
        ctx, ["flash_bwd_dq", "flash_bwd_dkv"])
