"""Pallas TPU paged chunked-prefill attention: query tiles over block tables.

The prefill counterpart of ``paged_attention.py``: causal attention for a
``(batch, chunk)`` tile of query tokens whose K/V history — including the
chunk itself — lives in *block* (page) storage ``(num_pages, page_size,
KV, D)``.  Each row of the batch is one sequence mid-prefill: its queries
sit at absolute positions ``[start[b], start[b] + q_len[b])`` and attend
every earlier position of the same sequence through the row's block
table.  This is what lets the serving engine write prefill KV straight
into pool blocks and never allocate the transient dense ``max_seq_len``
stripe the chunked-prefill path used to fill before scattering.

TPU adaptation, mirroring the decode kernel: the block table and the
per-row ``(start, q_len)`` scalars ride in as *scalar-prefetch* operands
(``pltpu.PrefetchScalarGridSpec``), so the page id feeding each K/V
tile's DMA — ``table[b, i]`` — is known before the kernel body runs.
The grid is ``(B, pages_per_seq)`` with the page axis innermost and
sequential; the online-softmax state ``(m, l, acc)`` of every KV head
accumulates in VMEM scratch across pages.  One K/V block is a whole
page, ``(page_size, KV, D)`` (a one-head block's last two dims would
not be legal for Mosaic), and the kernel walks the page's KV heads.
The query tile folds ``(chunk, G)`` into one ``CG = chunk * G`` axis
(row ``c * G + g``), so GQA costs one page DMA per page, never per
query head; the per-row chunk index is recovered in-kernel as
``row // G`` for the causal mask.

Pages holding no attended position — entirely past the newest query, or
entirely outside the sliding window of the *oldest* query in the tile —
are skipped at page granularity, so rows that are pure padding
(``q_len == 0``, co-admission waves shorter than the compiled batch)
cost zero compute.  Features match the decode kernel: GQA, sliding
window, attention-logit softcap.  Validated against
``repro.kernels.ref.paged_prefill_ref`` in interpret mode (CPU).
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


def _paged_prefill_kernel(tbl_ref, start_ref, qlen_ref, q_ref, k_ref, v_ref,
                          o_ref, m_ref, l_ref, acc_ref, *, scale: float,
                          window: Optional[int], softcap: Optional[float],
                          page_size: int, group: int, kv_heads: int):
    b = pl.program_id(0)
    i = pl.program_id(1)
    ni = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = start_ref[b]                    # first query's absolute position
    q_len = qlen_ref[b]                     # valid query rows in this chunk
    k_start = i * page_size

    # page-level reachability: the newest query bounds the causal extent,
    # the oldest query's window lower bound cuts pages that scrolled out
    reachable = (q_len > 0) & (k_start <= start + q_len - 1)
    if window is not None:
        reachable &= k_start + page_size - 1 >= start - (window - 1)

    @pl.when(reachable)
    def _compute():
        # one page block carries every KV head; each head's CG query
        # rows attend its (page, D) slice
        for h in range(kv_heads):
            q = q_ref[0, h].astype(jnp.float32)             # (CG, D)
            k = k_ref[0, :, h, :].astype(jnp.float32)       # (page, D)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            # row c*G+g is query token c of the chunk (all G heads of a
            # group share one causal row)
            qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
            qpos = start + qi
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = (kpos <= qpos) & (qi < q_len)
            if window is not None:
                mask &= (qpos - kpos) < window
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[h]                           # (CG, 1)
            l_prev = l_ref[h]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            # a fully-masked row (padding query) has m_new == NEG_INF; its
            # probabilities must be 0, not exp(NEG_INF - NEG_INF) = 1
            p = jnp.where(m_new == NEG_INF, 0.0, jnp.exp(s - m_new))
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


def paged_prefill(q, k_pages, v_pages, block_tables, start_pos, q_lens, *,
                  group: int, scale: Optional[float] = None,
                  window: Optional[int] = None,
                  softcap: Optional[float] = None,
                  interpret: bool = False):
    """Paged chunked-prefill attention (grouped, chunk-folded layout).

    q: (B, KV, CG, D) — CG = chunk * group, row ``c * group + g`` is
      query token c of the chunk for head g of the KV group;
    k_pages, v_pages: (num_pages, page_size, KV, D) block storage, with
      the chunk's own K/V already written at positions
      ``[start_pos[b], start_pos[b] + q_lens[b])``;
    block_tables: (B, pages_per_seq) int32 — page ids backing positions
      ``[j*page_size, (j+1)*page_size)`` of sequence b (entries past the
      sequence's extent may be any id; they are clamped and masked);
    start_pos: (B,) int32 — absolute position of each row's first query;
    q_lens: (B,) int32 — valid query tokens per row (0 = padding row,
      fully skipped).
    Returns (B, KV, CG, D) in q.dtype; padding query rows are zeros.
    """
    B, KV, CG, D = q.shape
    NP, page_size, KVp, Dp = k_pages.shape
    assert (KVp, Dp) == (KV, D), (k_pages.shape, q.shape)
    assert CG % group == 0, (CG, group)
    pages_per_seq = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    # garbage entries must still name a real page for the DMA
    tables = jnp.clip(block_tables.astype(jnp.int32), 0, NP - 1)
    start_pos = start_pos.astype(jnp.int32)
    q_lens = q_lens.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, KV, CG, D), lambda b, i, tbl, st, ql:
                         (b, 0, 0, 0)),
            # all KV heads of one page per block: the block's last two
            # dims (KV, D) are the array's own, as Mosaic requires
            pl.BlockSpec((1, page_size, KV, D), lambda b, i, tbl, st, ql:
                         (tbl[b, i], 0, 0, 0)),
            pl.BlockSpec((1, page_size, KV, D), lambda b, i, tbl, st, ql:
                         (tbl[b, i], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, CG, D), lambda b, i, tbl, st, ql:
                               (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, CG, 1), jnp.float32),   # running max m
            pltpu.VMEM((KV, CG, 1), jnp.float32),   # running denom l
            pltpu.VMEM((KV, CG, D), jnp.float32),   # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_prefill_kernel, scale=scale, window=window,
                          softcap=softcap, page_size=page_size, group=group,
                          kv_heads=KV),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, CG, D), q.dtype),
        interpret=interpret,
    )(tables, start_pos, q_lens, q, k_pages, v_pages)
