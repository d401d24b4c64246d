"""Shared arithmetic of the metric readers in ``metrics/``.

Each reader is ``read(ctx) -> float | None`` over a
:class:`perfbench.harness.Ctx`; ``None`` means the run had nothing to
read, and the metric is left out of the result line.
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence

from perfbench import trace as T
from perfbench import work


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (``q`` in [0, 100]) of all samples."""
    if not xs:
        return None
    s = sorted(xs)
    k = max(1, math.ceil(q / 100.0 * len(s)))
    return s[k - 1]


def gaps_ms(ctx) -> List[float]:
    """Every inter-token gap whose later token came in the window."""
    a, b = ctx.window
    out = []
    for r in ctx.client.all:
        t = r.times
        out.extend(1e3 * (t[i] - t[i - 1]) for i in range(1, len(t))
                   if a <= t[i] < b)
    return out


def first_token_ms(ctx) -> List[tuple]:
    """(due time, wait) of every request due in the window: the wait from
    its due time to its first token, in ms.  A request that never got one
    waits until the run stopped waiting (``ctx.closed``): it is a miss,
    counted as failed."""
    return [(r.due, 1e3 * ((r.times[0] if r.times else ctx.closed) - r.due))
            for r in ctx.due_in_window()]


def tokens_per_s(ctx) -> float:
    a, b = ctx.window
    n = sum(1 for r in ctx.client.all for t in r.times if a <= t < b)
    return n / (b - a)


def counter_delta(ctx, *path) -> float:
    v0, v1 = ctx.stats0["totals"], ctx.stats1["totals"]
    for k in path:
        v0, v1 = v0[k], v1[k]
    return v1 - v0


def prefix_hit_share(ctx) -> Optional[float]:
    prompt = counter_delta(ctx, "prefix_cache", "prompt_tokens")
    if prompt <= 0:
        return None
    return 100.0 * counter_delta(ctx, "prefix_cache",
                                 "cached_tokens_served") / prompt


def occupancy(ctx) -> Optional[float]:
    """Mean active slots over ``max_slots`` across the window's decode
    steps, in percent (from the scheduler's running occupancy mean)."""
    t0, t1 = ctx.stats0["totals"], ctx.stats1["totals"]
    n0, n1 = t0["decode_steps"], t1["decode_steps"]
    if n1 <= n0:
        return None
    return 100.0 * (t1["slot_occupancy"] * n1
                    - t0["slot_occupancy"] * n0) / (n1 - n0)


def decode_step_ms(ctx) -> Optional[float]:
    if ctx.trace is None:
        return None
    t, n = T.module_time(ctx.trace, T.NAMES["programs"]["decode"])
    return 1e3 * t / n if n else None


def roofline(ctx, kernel: str) -> Optional[float]:
    """Share of the roofline of a kernel over the traced window: the least
    time its needed work takes at the peaks over its summed device time."""
    if ctx.trace is None:
        return None
    if kernel == "paged_decode":
        if not ctx.work.decode_ctx:
            return None
        fl, by = work.decode_attention(ctx.dims, ctx.work.decode_ctx)
    else:
        rows = [r for r in ctx.work.prefill_rows if r[1] > 0]
        if not rows:
            return None
        fl, by = work.prefill_attention(ctx.dims, rows)
    share, _ = work.roofline_share(fl, by, T.kernel_seconds(ctx.trace, kernel),
                                   ctx.peaks)
    return share


def mfu(ctx) -> Optional[float]:
    """Model FLOPs of the traced window's real work over (summed wall of
    its gateway steps x the chip's peak), in percent."""
    if ctx.trace is None:
        return None
    a, b = ctx.trace_window
    steps = ctx.steps_in(a, b)
    wall = sum(e - s for s, e in steps)
    if wall <= 0:
        return None
    firsts = sum(1 for r in ctx.client.all if r.times
                 and a <= r.times[0] <= b)
    fl = work.model_flops(ctx.dims, decode_ctx=ctx.work.decode_ctx,
                          prefill_rows=ctx.work.prefill_rows,
                          logits_rows=firsts)
    return 100.0 * fl / (wall * ctx.peaks["flops_bf16"])


def idle_share(ctx) -> Optional[float]:
    if ctx.trace is None:
        return None
    v = T.idle_share(ctx.trace)
    return None if v is None else 100.0 * v
