"""Seconds from the start of the run to the window: weights, engine,
compilation or cache loads, shape warm-up, warm-up traffic."""


def read(ctx):
    return ctx.setup_s
