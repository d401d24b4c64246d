"""KV pool / prefix cache: prompt tokens served from the prefix cache over prompt tokens admitted in the window (%)."""
from perfbench import readers


def read(ctx):
    return readers.prefix_hit_share(ctx)
