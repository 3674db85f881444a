"""``flash_fwd``: least time by the chip's peaks for its calls' operations and
bytes over their summed device time (compute-bound at T=512, D=64)."""


def read(ctx):
    return ctx["readers"].flash_roofline_pct(ctx, ["flash_fwd"])
