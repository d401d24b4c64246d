"""Median of every inter-token gap in the window (ms)."""
from perfbench import readers


def read(ctx):
    return readers.percentile(readers.gaps_ms(ctx), 50)
