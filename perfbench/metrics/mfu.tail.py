"""Whole step: model FLOPs of the real work over the steps' wall at the chip's peak (%)."""
from perfbench import readers


def read(ctx):
    return readers.mfu(ctx)
