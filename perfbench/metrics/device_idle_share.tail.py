"""Device: share of the gateway steps' time with no operation on the device (%)."""
from perfbench import readers


def read(ctx):
    return readers.idle_share(ctx)
