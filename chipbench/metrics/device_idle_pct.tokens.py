"""1 - the union of device-op intervals over the traced window, on the chip
that was busiest."""


def read(ctx):
    return ctx["readers"].device_idle_pct(ctx)
