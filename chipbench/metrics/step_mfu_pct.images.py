"""The whole step's share of the chips' published bf16 peak: model operations
of the steps in the traced window over the window's length."""


def read(ctx):
    return ctx["readers"].step_mfu_pct(ctx)
