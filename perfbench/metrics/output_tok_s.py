"""Output tokens produced in the window over its length (tokens/s)."""
from perfbench import readers


def read(ctx):
    return readers.tokens_per_s(ctx)
