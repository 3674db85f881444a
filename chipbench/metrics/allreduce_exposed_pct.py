"""Device time of all-reduce events during which no other operation runs on
that chip, as a share of the traced window (the busiest chip's)."""


def read(ctx):
    return ctx["readers"].allreduce_exposed_pct(ctx)
