"""Whole step: model FLOPs of the real work, experts' from the routed rows, over the steps' wall at the chip's peak (%)."""
from perfbench import experts


def read(ctx):
    return experts.mfu(ctx)
