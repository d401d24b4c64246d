"""Scheduler: device idle inside the scheduler's step spans, outside its engine and KV spans, over the gateway steps' time (%)."""
from perfbench import spans


def read(ctx):
    return None if ctx.trace is None else spans.scheduler_idle_share(ctx.trace)
