"""Expert layer: rows the grouped matmul ran beyond those routed to held experts, over those it ran (%)."""
from perfbench import experts


def read(ctx):
    return experts.pad_share(ctx)
