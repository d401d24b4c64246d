"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def attention_ref(q, k, v, *, causal: bool = True,
                  window: Optional[int] = None,
                  softcap: Optional[float] = None,
                  scale: Optional[float] = None):
    """q: (BH, Sq, D); k, v: (BH, Skv, D).  Naive softmax attention."""
    Sq, Skv, D = q.shape[1], k.shape[1], q.shape[2]
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows -> zeros (kernel semantics)
    any_valid = mask.any(axis=1)[None, :, None]
    out = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
    return jnp.where(any_valid, out, 0.0).astype(q.dtype)


def _gather_pages(pages, layer, tables, B, T, KV, D):
    """One layer's pages of a stacked (layers, num_pages, page_size,
    KV * D) pool, gathered through the block tables into (B, T, KV, D)."""
    NP = pages.shape[1]
    tables = jnp.clip(tables.astype(jnp.int32), 0, NP - 1)
    return pages[layer][tables.reshape(-1)].reshape(B, T, KV, D)


def paged_attention_ref(q, k_pages, v_pages, layer, block_tables, lengths,
                        *, scale: Optional[float] = None,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None):
    """Oracle for the paged decode kernel: gather every sequence's pages
    back into a dense (B, T, KV, D) layout, then run naive masked softmax
    attention for the single query token.

    q: (B, KV, G, D); k_pages, v_pages: (layers, num_pages, page_size,
    KV * D); layer: the layer read; block_tables: (B, pages_per_seq)
    int32; lengths: (B,) int32 counting valid positions including the
    current token.  Returns (B, KV, G, D).
    """
    B, KV, G, D = q.shape
    page_size = k_pages.shape[2]
    pages_per_seq = block_tables.shape[1]
    T = pages_per_seq * page_size
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    k = _gather_pages(k_pages, layer, block_tables, B, T, KV, D)
    v = _gather_pages(v_pages, layer, block_tables, B, T, KV, D)
    s = jnp.einsum("bkgd,btkd->bkgt", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    kpos = jnp.arange(T)[None, :]                        # (1, T)
    lengths = lengths.astype(jnp.int32)[:, None]
    mask = kpos < lengths                                # causal: q is last
    if window is not None:
        mask &= (lengths - 1) - kpos < window
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p, v.astype(jnp.float32))
    any_valid = mask.any(axis=1)[:, None, None, None]
    return jnp.where(any_valid, out, 0.0).astype(q.dtype)


def paged_prefill_ref(q, k_pages, v_pages, layer, block_tables, start_pos,
                      q_lens, *, scale: Optional[float] = None,
                      window: Optional[int] = None,
                      softcap: Optional[float] = None):
    """Oracle for the paged chunked-prefill kernel: gather every row's
    pages back into a dense (B, T, KV, D) layout, then run naive masked
    softmax attention for the whole query chunk.

    q: (B, C, KV, G, D) — chunk of query tokens per row, GQA-grouped;
    k_pages, v_pages: (layers, num_pages, page_size, KV * D) stacked
    block storage holding the chunk's own K/V at its absolute positions
    of ``layer``; block_tables: (B, pages_per_seq) int32; start_pos:
    (B,) absolute position of each row's first query; q_lens: (B,) valid
    query tokens per row (padding rows/tokens return zeros).  Returns
    (B, C, KV, G, D).
    """
    B, C, KV, G, D = q.shape
    page_size = k_pages.shape[2]
    pages_per_seq = block_tables.shape[1]
    T = pages_per_seq * page_size
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    k = _gather_pages(k_pages, layer, block_tables, B, T, KV, D)
    v = _gather_pages(v_pages, layer, block_tables, B, T, KV, D)
    s = jnp.einsum("bckgd,btkd->bkgct", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qpos = (start_pos.astype(jnp.int32)[:, None]
            + jnp.arange(C)[None, :])                    # (B, C)
    kpos = jnp.arange(T)[None, None, :]                  # (1, 1, T)
    mask = kpos <= qpos[:, :, None]                      # causal
    mask &= (jnp.arange(C)[None, :]
             < q_lens.astype(jnp.int32)[:, None])[:, :, None]
    if window is not None:
        mask &= (qpos[:, :, None] - kpos) < window
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgct,btkd->bckgd", p, v.astype(jnp.float32))
    any_valid = mask.any(axis=2)[:, :, None, None, None]
    return jnp.where(any_valid, out, 0.0).astype(q.dtype)


def ssd_scan_ref(x, dt, A, B, C, chunk: int, initial_state=None):
    """Chunked SSD oracle — delegates to the model-level reference, which is
    itself validated against the naive recurrence in tests/test_ssm.py."""
    from repro.models.ssm import ssd_chunked
    return ssd_chunked(x, dt, A, B, C, chunk, initial_state=initial_state)


def rmsnorm_ref(x, scale, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return y.astype(x.dtype)


def expert_gmm_ref(x, wi, wg, wo, group_sizes, first=0):
    """Rows sorted by group through their group's SwiGLU FFN (group
    ``g``'s weights at ``first + g``), each row with its own expert's
    weights gathered; rows past the groups' sum return zeros."""
    ends = jnp.cumsum(group_sizes)
    rows = jnp.arange(x.shape[0])
    g = first + jnp.minimum(jnp.searchsorted(ends, rows, side="right"),
                            group_sizes.shape[0] - 1)
    xf = x.astype(jnp.float32)
    gate = jnp.einsum("md,mdf->mf", xf, wg[g].astype(jnp.float32))
    up = jnp.einsum("md,mdf->mf", xf, wi[g].astype(jnp.float32))
    out = jnp.einsum("mf,mfd->md", jax.nn.silu(gate) * up,
                     wo[g].astype(jnp.float32))
    return jnp.where((rows < ends[-1])[:, None], out, 0.0).astype(x.dtype)
