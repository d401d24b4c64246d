"""Scheduler: mean active slots over max_slots across the window's decode steps (%)."""
from perfbench import readers


def read(ctx):
    return readers.occupancy(ctx)
