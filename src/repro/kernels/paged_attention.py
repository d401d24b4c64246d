"""Pallas TPU paged-attention decode: gather K/V through block tables.

Single-token decode attention where K/V live in *block* (page) storage —
``(num_pages, page_size, KV, D)`` — instead of one dense contiguous
sequence axis per slot.  Each live sequence owns a per-slot row of a
``(B, pages_per_seq)`` block table naming the pages that back its token
positions in order; the pool hands pages out on demand, so the resident
KV footprint tracks the tokens actually generated, not the worst case.

TPU adaptation: the block table and per-sequence lengths ride in as
*scalar-prefetch* operands (``pltpu.PrefetchScalarGridSpec``), so the
page index feeding each K/V tile's DMA — ``table[b, i]`` — is known
before the kernel body runs.  The grid is ``(B, pages_per_seq)`` with
the page axis innermost and sequential, so the online-softmax state
``(m, l, acc)`` of every KV head accumulates in VMEM scratch across
pages exactly like the flash-attention kernel accumulates across KV
tiles.  One K/V block is a whole page, ``(page_size, KV, D)``: Mosaic
requires a block's last two dims to be (8, 128)-divisible or the
array's own, so a one-head ``(page_size, 1, D)`` block is refused, and
the kernel walks the KV heads of the page itself.  Pages past a
sequence's length are skipped (their table entries point at the pool's
trash page and the position mask kills any stray values).

Features match the dense decode path: GQA (all G query heads of a KV
head's group in one tile), sliding window, and
attention-logit softcap.  Validated against
``repro.kernels.ref.paged_attention_ref`` in interpret mode (CPU), which
is itself validated against a dense gather + softmax in the tests.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale: float,
                  window: Optional[int], softcap: Optional[float],
                  page_size: int, kv_heads: int):
    b = pl.program_id(0)
    i = pl.program_id(1)
    ni = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]                     # valid positions: [0, length)
    q_pos = length - 1                      # the one decoding token
    k_start = i * page_size

    # page-level reachability: skip pages holding no attended position
    reachable = k_start < length
    if window is not None:
        reachable &= k_start + page_size - 1 >= q_pos - (window - 1)

    @pl.when(reachable)
    def _compute():
        # one page block carries every KV head; each head's G query
        # heads attend its (page, D) slice
        for h in range(kv_heads):
            q = q_ref[0, h].astype(jnp.float32)             # (G, D)
            k = k_ref[0, :, h, :].astype(jnp.float32)       # (page, D)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = kpos < length                        # causal: q is last
            if window is not None:
                mask &= (q_pos - kpos) < window
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[h]                           # (G, 1)
            l_prev = l_ref[h]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)
            alpha = jnp.where(m_prev == NEG_INF, 0.0,
                              jnp.exp(m_prev - m_new))
            l_ref[h] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(i == ni - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    scale: Optional[float] = None,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    interpret: bool = False):
    """Paged single-token decode attention.

    q: (B, KV, G, D) — one query token per sequence, grouped GQA layout;
    k_pages, v_pages: (num_pages, page_size, KV, D) block storage;
    block_tables: (B, pages_per_seq) int32 — page ids backing positions
      ``[j*page_size, (j+1)*page_size)`` of sequence b (entries past the
      sequence's extent may be any in-range id; they are masked);
    lengths: (B,) int32 — valid positions per sequence, **including** the
      current token (its K/V must already be written to its page).
    Returns (B, KV, G, D) in q.dtype.
    """
    B, KV, G, D = q.shape
    NP, page_size, KVp, Dp = k_pages.shape
    assert (KVp, Dp) == (KV, D), (k_pages.shape, q.shape)
    pages_per_seq = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    # garbage entries must still name a real page for the DMA
    tables = jnp.clip(block_tables.astype(jnp.int32), 0, NP - 1)
    lengths = lengths.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, KV, G, D), lambda b, i, tbl, lens:
                         (b, 0, 0, 0)),
            # all KV heads of one page per block: the block's last two
            # dims (KV, D) are the array's own, as Mosaic requires
            pl.BlockSpec((1, page_size, KV, D), lambda b, i, tbl, lens:
                         (tbl[b, i], 0, 0, 0)),
            pl.BlockSpec((1, page_size, KV, D), lambda b, i, tbl, lens:
                         (tbl[b, i], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, G, D), lambda b, i, tbl, lens:
                               (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),    # running max m
            pltpu.VMEM((KV, G, 1), jnp.float32),    # running denom l
            pltpu.VMEM((KV, G, D), jnp.float32),    # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, window=window,
                          softcap=softcap, page_size=page_size, kv_heads=KV),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        interpret=interpret,
    )(tables, lengths, q, k_pages, v_pages)
