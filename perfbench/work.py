"""Operations and bytes the algorithm needs, counted from live lengths.

Counted from what each call has to do: the context each decode row
attends over, the start and real query length of each prefill row.
Never from the padded launch shapes, the ``max_seq_len`` stripe of the
block tables, or padding rows, so the count stays the same whatever
implements the kernel.  ``n`` is :func:`perfbench.model.dims` of the
configuration.  K/V, q and the attention output are bfloat16 (2 bytes)
as the configurations serve them.
"""
from __future__ import annotations

from typing import Iterable, Tuple

from perfbench import model

KV_BYTES = 2
ACT_BYTES = 2


def matmul_params(n: dict) -> int:
    """Weights one token multiplies through in one layer, as the
    configuration's family counts them."""
    return model.family(n).matmul_params(n)


def decode_attention(n: dict, ctx: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of the paged decode kernel over every layer for rows
    attending over ``ctx`` positions each (the new token included)."""
    H, KV, D, L = n["H"], n["KV"], n["D"], n["L"]
    flops = byts = 0.0
    for c in ctx:
        flops += 4.0 * c * H * D
        byts += 2.0 * c * KV * D * KV_BYTES + 2.0 * H * D * ACT_BYTES
    return flops * L, byts * L


def prefill_attention(n: dict, rows: Iterable[Tuple[int, int]]
                      ) -> Tuple[float, float]:
    """(FLOPs, bytes) of the paged prefill kernel over every layer for rows
    ``(start, q_len)``: query ``t`` of a row attends over ``start + t + 1``
    positions; the row reads its live K/V once."""
    H, KV, D, L = n["H"], n["KV"], n["D"], n["L"]
    flops = byts = 0.0
    for start, q in rows:
        if q <= 0:
            continue
        flops += 4.0 * H * D * (q * start + q * (q + 1) / 2.0)
        byts += (2.0 * (start + q) * KV * D * KV_BYTES
                 + 2.0 * q * H * D * ACT_BYTES)
    return flops * L, byts * L


def model_flops(n: dict, *, decode_ctx: Iterable[int] = (),
                prefill_rows: Iterable[Tuple[int, int]] = (),
                logits_rows: int = 0) -> float:
    """Model FLOPs of real work: the matmuls of every decoded token and
    real prompt token, attention over live context, and the output head
    for every token whose logits are used (each decoded token, and the
    last prompt token of each completed prefill)."""
    decode_ctx = list(decode_ctx)
    prefill_rows = [r for r in prefill_rows if r[1] > 0]
    per_tok = 2.0 * matmul_params(n) * n["L"]
    toks = len(decode_ctx) + sum(q for _, q in prefill_rows)
    head = 2.0 * n["d"] * n["V"] * (len(decode_ctx) + logits_rows)
    return (per_tok * toks + head + decode_attention(n, decode_ctx)[0]
            + prefill_attention(n, prefill_rows)[0])


def roofline_share(flops: float, byts: float, seconds: float,
                   peaks: dict) -> Tuple[float, str]:
    """Least time the work takes at the chip's peaks over the measured
    time, in percent, and which peak bounds it."""
    t_c = flops / peaks["flops_bf16"]
    t_m = byts / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_c >= t_m else "memory"
    return 100.0 * max(t_c, t_m) / seconds, bound
