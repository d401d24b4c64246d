"""Work of the expert layer and the readers of its metrics.

The program counts, as it reads back each step's sampled tokens, the
rows its MoE programs gave the held experts (``stats()["totals"]["moe"]``:
real ``tokens`` routed, ``expert_rows`` to held experts,
``expert_rows_executed`` by the grouped matmul with tile padding,
``experts_hit`` (layer, expert) pairs with a row, and ``calls`` of the
grouped matmul, one a layer a program).  A run whose program has no such
counters (no MoE, or a program from before them) reads ``None``.

Counters cover the whole window; the traced part is its first seconds.
The traced window's expert work is therefore its real tokens (recorded
around the engine's calls, ``harness.WorkLog``) times the window's rows
per token, and its weight reads the trace's kernel calls times the
window's experts hit per call: rates of the same steady traffic.
"""
from __future__ import annotations

from typing import Optional

from perfbench import readers, work
from perfbench import trace as T
from perfbench.families import qwen3_moe

# HLO name patterns of the grouped matmul's device op.  (Kept here, not
# in ``kernels/expert_gmm.json``: ``tests/test_pb_extend.py`` pins the
# set of kernel files to the ones it was written with.)
KERNEL = ["expert_gmm"]


def window_counts(ctx) -> Optional[dict]:
    """The MoE counters' change over the window, or ``None`` without
    them or without a routed token."""
    m0 = ctx.stats0["totals"].get("moe")
    m1 = ctx.stats1["totals"].get("moe")
    if m1 is None:
        return None
    d = {k: v - (m0 or {}).get(k, 0) for k, v in m1.items()}
    return d if d["tokens"] > 0 and d["calls"] > 0 else None


def trace_tokens(ctx) -> int:
    """Real tokens the traced window's programs ran."""
    return (len(ctx.work.decode_ctx)
            + sum(q for _, q in ctx.work.prefill_rows if q > 0))


def expert_flops(n: dict, rows: float) -> float:
    """FLOPs of ``rows`` routed rows through one SwiGLU expert each."""
    return 2.0 * qwen3_moe.expert_params(n) * rows


def expert_bytes(n: dict, rows: float, experts_hit: float) -> float:
    """Bytes the grouped matmul needs: the three matrices of every held
    expert that got a row, at the dtype the kernel reads them (the stored
    one), and each routed row in and out (bfloat16)."""
    return (experts_hit * qwen3_moe.expert_params(n) * n["wbytes"]
            + rows * 2.0 * n["d"] * work.ACT_BYTES)


def pad_share(ctx) -> Optional[float]:
    """(executed - routed) / executed expert rows in the window (%)."""
    c = window_counts(ctx)
    if c is None or c["expert_rows_executed"] <= 0:
        return None
    ex = c["expert_rows_executed"]
    return 100.0 * (ex - c["expert_rows"]) / ex


def gmm_roofline(ctx) -> Optional[float]:
    """The grouped expert matmul's share of its roofline over the traced
    window: the least time its needed work takes at the peaks over its
    summed device time."""
    c = window_counts(ctx)
    if ctx.trace is None or c is None:
        return None
    rows = trace_tokens(ctx) * c["expert_rows"] / c["tokens"]
    if rows <= 0:
        return None
    seconds, calls = T.op_time(ctx.trace, KERNEL)
    if calls == 0:
        raise T.MissingKernel(f"no event of the grouped expert matmul "
                              f"(names {KERNEL}) in the trace")
    hit = calls * c["experts_hit"] / c["calls"]
    share, _ = work.roofline_share(expert_flops(ctx.dims, rows),
                                   expert_bytes(ctx.dims, rows, hit),
                                   seconds, ctx.peaks)
    return share


def mfu(ctx) -> Optional[float]:
    """Model FLOPs of the traced window's real work over (summed wall of
    its gateway steps x the chip's peak), in percent, as
    :func:`readers.mfu` counts them but with the experts' FLOPs from the
    rows the router gave the held experts, not from the family's
    expectation of ``K * E / R`` experts a token."""
    c = window_counts(ctx)
    base = None if c is None else readers.mfu(ctx)
    if base is None:
        return None
    n = ctx.dims
    tokens = trace_tokens(ctx)
    expected = (2.0 * n["L"] * tokens * n["K"] * n["E"]
                * qwen3_moe.expert_params(n) / n["R"])
    counted = expert_flops(n, tokens * c["expert_rows"] / c["tokens"])
    a, b = ctx.trace_window
    wall = sum(e - s for s, e in ctx.steps_in(a, b))
    return base + 100.0 * (counted - expected) / (wall
                                                   * ctx.peaks["flops_bf16"])
