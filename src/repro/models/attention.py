"""Attention: GQA + q/k norm + RoPE / M-RoPE + sliding-window + logit softcap +
KV cache.

The prefill/train path is *query-chunked* (a lax.scan over query blocks with
per-chunk remat) so the S x S score matrix is never materialized — this is
what makes the 32k-prefill shapes memory-feasible, and it mirrors the tiling
of the Pallas flash-attention kernel (repro/kernels/flash_attention.py),
which is the TPU hot-path implementation validated against this reference.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import modules as nn

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# RoPE (standard + multimodal M-RoPE)
# ---------------------------------------------------------------------------

def _rope_angles(positions, head_dim: int, theta: float, mrope_sections=None):
    """positions: (B, S) or (3, B, S) for M-RoPE.  Returns (B, S, head_dim/2)."""
    half = head_dim // 2
    freq_exponents = jnp.arange(half, dtype=jnp.float32) / half
    inv_freq = 1.0 / (theta ** freq_exponents)                  # (half,)
    if positions.ndim == 3:                                      # M-RoPE
        sections = mrope_sections
        assert sections is not None and sum(sections) == half, (sections, half)
        # section id per frequency: 0 -> temporal, 1 -> height, 2 -> width
        sec_id = np.repeat(np.arange(len(sections)), sections)   # (half,)
        pos = jnp.take(positions, jnp.asarray(sec_id), axis=0)   # (half, B, S)
        pos = jnp.moveaxis(pos, 0, -1).astype(jnp.float32)       # (B, S, half)
        return pos * inv_freq[None, None, :]
    return positions.astype(jnp.float32)[..., None] * inv_freq[None, None, :]


def apply_rope(x, positions, theta: float = 10_000.0, mrope_sections=None):
    """x: (B, S, H, D); positions: (B, S) or (3, B, S)."""
    half = x.shape[-1] // 2
    ang = _rope_angles(positions, x.shape[-1], theta, mrope_sections)
    cos = jnp.cos(ang)[:, :, None, :]                            # (B, S, 1, half)
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_attention(key, cfg, cross: bool = False):
    d, h, kv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    kq, kk, kvk, ko = jax.random.split(key, 4)
    bias = cfg.qkv_bias and not cross
    p = {
        "wq": nn.init_linear(kq, d, (h, dh), bias=bias),
        "wk": nn.init_linear(kk, d, (kv, dh), bias=bias),
        "wv": nn.init_linear(kvk, d, (kv, dh), bias=bias),
        "wo": nn.init_linear(ko, h * dh, d, stddev=1.0 / np.sqrt(h * dh)),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = nn.init_rmsnorm(dh)
        p["k_norm"] = nn.init_rmsnorm(dh)
    return p


# ---------------------------------------------------------------------------
# Core chunked GQA attention
# ---------------------------------------------------------------------------

def _mask_bias(q_pos, k_pos, *, causal: bool, window: Optional[int]):
    """Additive f32 bias.  q_pos: (Sq,) or (B, Sq); k_pos: (Skv,).

    Returns (Sq, Skv) or (B, Sq, Skv).
    """
    qp = q_pos[..., :, None].astype(jnp.int32)
    kp = k_pos[None, :].astype(jnp.int32)
    m = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        m &= kp <= qp
    if window is not None:
        m &= (qp - kp) < window
    return jnp.where(m, 0.0, NEG_INF).astype(jnp.float32)


def attend(q, k, v, *, scale: float, causal: bool,
           window: Optional[int] = None, softcap_val: Optional[float] = None,
           q_positions=None, k_positions=None, q_chunk: int = 512):
    """Query-chunked attention.

    q: (B, Sq, KV, G, D); k, v: (B, Skv, KV, D).
    q_positions: (Sq,) or (B, Sq) absolute positions; k_positions: (Skv,).
    Returns (B, Sq, KV, G, D).
    """
    B, Sq = q.shape[0], q.shape[1]
    Skv = k.shape[1]
    if q_positions is None:
        q_positions = jnp.arange(Sq)
    if k_positions is None:
        k_positions = jnp.arange(Skv)

    def chunk_body(q_blk, qpos_blk):
        s = jnp.einsum("bqkgd,btkd->bkgqt", q_blk, k,
                       preferred_element_type=jnp.float32) * scale
        s = nn.softcap(s, softcap_val)
        bias = _mask_bias(qpos_blk, k_positions, causal=causal, window=window)
        if bias.ndim == 3:                                       # batched positions
            bias = bias[:, None, None]                           # (B,1,1,Sq,Skv)
        p = jax.nn.softmax(s + bias, axis=-1)
        return jnp.einsum("bkgqt,btkd->bqkgd", p,
                          v.astype(p.dtype)).astype(q.dtype)

    if q_chunk <= 0 or Sq <= q_chunk or Sq % q_chunk != 0:
        return chunk_body(q, q_positions)

    n = Sq // q_chunk
    qs = jnp.moveaxis(q.reshape(B, n, q_chunk, *q.shape[2:]), 1, 0)
    if q_positions.ndim == 1:
        qpos = q_positions.reshape(n, q_chunk)
    else:
        qpos = jnp.moveaxis(q_positions.reshape(B, n, q_chunk), 1, 0)

    def scan_body(_, xs):
        qb, pb = xs
        # remat: the (qc x Skv) score tile is recomputed in the backward pass
        return None, jax.checkpoint(chunk_body)(qb, pb)

    _, out = jax.lax.scan(scan_body, None, (qs, qpos))
    return jnp.moveaxis(out, 0, 1).reshape(B, Sq, *q.shape[2:])


# ---------------------------------------------------------------------------
# Full attention block (projections + rope + attend [+ cache])
# ---------------------------------------------------------------------------

def attention_block(params, cfg, x, *, positions=None, causal: bool = True,
                    window: Optional[int] = None, cache=None,
                    cache_index=None, kv_override=None, use_rope: bool = True,
                    block_tables=None, q_lens=None, layer=None):
    """x: (B, S, d_model).  Returns (out, new_cache).

    positions: (B, S) or (3, B, S) for M-RoPE (defaults to broadcast arange).
    cache: {"k": (B, Smax, KV, D), "v": ...} — decode mode, S must be 1 and
      cache_index (B,) gives each sequence's write position.
    block_tables: (B, blocks_per_slot) int32 — paged mode: cache is the
      whole stacked pool {"k": (layers, num_blocks, block_size, KV * D),
      ...} and ``layer`` this layer's index into it; this step's k/v
      rows are scattered in place to (layer, table[b, pos//bs], pos%bs)
      and attention gathers through the table with the Pallas paged
      kernels, which read the pool's (layer, page) blocks where they lie.
      S == 1 is single-token decode; S > 1 is a *chunked-prefill* tile:
      row b's queries sit at absolute positions ``cache_index[b] + t``,
      their K/V land straight in the row's pool blocks (padding tokens —
      ``t >= q_lens[b]`` or positions past the table's extent — are
      routed to the storage's trailing trash block), and attention runs
      through the Pallas paged-prefill kernel.  No dense per-slot stripe
      is ever materialized.
    q_lens: (B,) int32, paged-prefill only — valid tokens per row of the
      chunk (None means all S).
    kv_override: (B, Skv, d) encoder output => cross-attention (no rope,
      no cache, bidirectional over kv).
    """
    B, S, _ = x.shape
    h, kv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    g = h // kv
    dt = jnp.dtype(cfg.dtype)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    q = nn.linear(params["wq"], x, dtype=dt)                     # (B,S,h,dh)
    kv_src = x if kv_override is None else kv_override.astype(dt)
    k = nn.linear(params["wk"], kv_src, dtype=dt)                # (B,Skv,kv,dh)
    v = nn.linear(params["wv"], kv_src, dtype=dt)
    if cfg.qk_norm and kv_override is None:
        # per-head RMSNorm before RoPE, so the K written to any cache is
        # already normed
        q = nn.rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = nn.rmsnorm(params["k_norm"], k, cfg.norm_eps)

    if use_rope and kv_override is None:
        sections = cfg.mrope_sections if cfg.mrope else None
        q = apply_rope(q, positions, cfg.rope_theta, sections)
        k = apply_rope(k, positions, cfg.rope_theta, sections)

    scale = cfg.attn_scale or (1.0 / np.sqrt(dh))
    q = q.reshape(B, S, kv, g, dh)
    sc = cfg.attn_logit_softcap

    new_cache = cache
    if cache is not None and block_tables is not None and kv_override is None:
        assert "k_scale" not in cache, "paged int8 KV unsupported"
        from repro.kernels import ops as kops
        idx = cache_index                                        # (B,) int32
        _, npages, bs, _ = cache["k"].shape
        if S == 1:
            # paged decode: scatter this step's k/v rows into the pool
            # through the table, then gather-attend with the Pallas kernel
            rows = jnp.arange(B)
            blk = block_tables[rows, idx // bs]
            off = idx % bs
            upd_k = cache["k"].at[layer, blk, off].set(
                k[:, 0].reshape(B, kv * dh).astype(cache["k"].dtype))
            upd_v = cache["v"].at[layer, blk, off].set(
                v[:, 0].reshape(B, kv * dh).astype(cache["v"].dtype))
            new_cache = {"k": upd_k, "v": upd_v}
            # kernel casts tiles to f32 in VMEM; bf16 pages go in as-is
            out = kops.paged_decode_attention(
                q.reshape(B, S, h, dh), upd_k, upd_v, layer, block_tables,
                idx + 1, window=window, softcap=sc, scale=scale)
            out = out.reshape(B, S, kv, g, dh)
        else:
            # paged chunked prefill: the whole (B, S) tile's k/v go
            # straight into each row's pool blocks; padding tokens (past
            # q_lens, or past the table's extent) land in the trailing
            # trash block, never a live page
            bps = block_tables.shape[1]
            qlv = (jnp.full((B,), S, jnp.int32) if q_lens is None
                   else q_lens.astype(jnp.int32))
            pos = idx[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
            valid = ((jnp.arange(S)[None] < qlv[:, None])
                     & (pos < bps * bs))
            rows = jnp.arange(B)[:, None]
            blk = jnp.where(
                valid,
                block_tables[rows, jnp.clip(pos // bs, 0, bps - 1)],
                npages - 1)
            off = pos % bs
            upd_k = cache["k"].at[layer, blk, off].set(
                k.reshape(B, S, kv * dh).astype(cache["k"].dtype))
            upd_v = cache["v"].at[layer, blk, off].set(
                v.reshape(B, S, kv * dh).astype(cache["v"].dtype))
            new_cache = {"k": upd_k, "v": upd_v}
            out = kops.paged_prefill_attention(
                q.reshape(B, S, h, dh), upd_k, upd_v, layer, block_tables,
                idx, qlv, window=window, softcap=sc, scale=scale)
            out = out.reshape(B, S, kv, g, dh)
    elif cache is not None and kv_override is None:
        # decode: write this step's k/v at cache_index, attend over the cache
        assert S == 1, "cache mode is one-token decode"
        idx = cache_index                                        # (B,) int32
        rows = jnp.arange(B)
        if "k_scale" in cache:
            # int8 KV cache: per-(token, kv-head) absmax quantization
            def quantize(x1):                                    # (B, KV, D)
                s = jnp.max(jnp.abs(x1.astype(jnp.float32)),
                            axis=-1) / 127.0 + 1e-8              # (B, KV)
                q8 = jnp.round(x1.astype(jnp.float32)
                               / s[..., None]).astype(jnp.int8)
                return q8, s.astype(jnp.bfloat16)

            k8, ks = quantize(k[:, 0])
            v8, vs = quantize(v[:, 0])
            new_cache = {
                "k": cache["k"].at[rows, idx].set(k8),
                "v": cache["v"].at[rows, idx].set(v8),
                "k_scale": cache["k_scale"].at[rows, idx].set(ks),
                "v_scale": cache["v_scale"].at[rows, idx].set(vs),
            }
            kd = (new_cache["k"].astype(dt)
                  * new_cache["k_scale"].astype(dt)[..., None])
            vd = (new_cache["v"].astype(dt)
                  * new_cache["v_scale"].astype(dt)[..., None])
        else:
            upd_k = cache["k"].at[rows, idx].set(k[:, 0].astype(cache["k"].dtype))
            upd_v = cache["v"].at[rows, idx].set(v[:, 0].astype(cache["v"].dtype))
            new_cache = {"k": upd_k, "v": upd_v}
            kd, vd = upd_k.astype(dt), upd_v.astype(dt)
        out = attend(q, kd, vd, scale=scale,
                     causal=True, window=window, softcap_val=sc,
                     q_positions=idx[:, None],
                     k_positions=jnp.arange(cache["k"].shape[1]),
                     q_chunk=cfg.attn_q_chunk)
    else:
        out = attend(q, k, v, scale=scale, causal=causal and kv_override is None,
                     window=window, softcap_val=sc, q_chunk=cfg.attn_q_chunk)

    out = nn.linear(params["wo"], out.reshape(B, S, h * dh), dtype=dt)
    return out, new_cache
