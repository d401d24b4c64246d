"""Kernels: the grouped expert matmul's share of its roofline (%)."""
from perfbench import experts


def read(ctx):
    return experts.gmm_roofline(ctx)
