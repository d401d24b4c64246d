"""Kernels: the paged prefill kernel's share of its roofline (%)."""
from perfbench import readers


def read(ctx):
    return readers.roofline(ctx, "paged_prefill")
