"""``flash_bwd_dq`` + ``flash_bwd_dkv``: least time by the chip's peaks over
their summed device time."""


def read(ctx):
    return ctx["readers"].flash_roofline_pct(
        ctx, ["flash_bwd_dq", "flash_bwd_dkv"])
