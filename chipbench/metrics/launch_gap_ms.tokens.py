"""Median device-idle gap between the end of one step program and the start
of the next: the host side of ``FusedTrainer.step`` and of the loss fetch."""


def read(ctx):
    return ctx["readers"].launch_gap_ms(ctx)
