"""KV pool and prefix cache: device idle inside the prefix cache's block-save spans, over the gateway steps' time (%)."""
from perfbench import spans


def read(ctx):
    return None if ctx.trace is None else spans.prefix_save_idle_share(ctx.trace)
