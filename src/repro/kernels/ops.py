"""jit'd public wrappers around the Pallas kernels.

These are the model-facing entry points: they handle head folding/GQA
layout, choose interpret mode on the CPU (validation) and compiled mode
on the TPU, and are shape-polymorphic over the model stacks' layouts.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _pa
from repro.kernels import paged_prefill as _pp
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd_scan as _ssd


def _default_interpret() -> bool:
    """Compiled on the TPU, interpreted on the CPU (validation only);
    any other backend is an error, never a silent interpreter run."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"Pallas TPU kernels cannot run on backend "
                           f"{backend!r}: use a TPU, or the CPU for "
                           f"interpret-mode validation")
    return backend == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "scale", "interpret"))
def mha_flash_attention(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        scale: Optional[float] = None,
                        interpret: Optional[bool] = None):
    """Model-layout flash attention.

    q: (B, Sq, H, D); k, v: (B, Skv, KV, D) with H = G * KV (GQA).
    Returns (B, Sq, H, D).
    """
    if interpret is None:
        interpret = _default_interpret()
    Bz, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    # fold (B, KV, G) -> BH; repeat kv per group via reshape-broadcast
    qf = q.reshape(Bz, Sq, KV, G, D).transpose(0, 2, 3, 1, 4) \
        .reshape(Bz * KV * G, Sq, D)
    kf = jnp.broadcast_to(k.transpose(0, 2, 1, 3)[:, :, None],
                          (Bz, KV, G, k.shape[1], D)).reshape(
                              Bz * KV * G, k.shape[1], D)
    vf = jnp.broadcast_to(v.transpose(0, 2, 1, 3)[:, :, None],
                          (Bz, KV, G, v.shape[1], D)).reshape(
                              Bz * KV * G, v.shape[1], D)
    out = _fa.flash_attention(qf, kf, vf, causal=causal, window=window,
                              softcap=softcap, scale=scale,
                              interpret=interpret)
    return out.reshape(Bz, KV, G, Sq, D).transpose(0, 3, 1, 2, 4) \
        .reshape(Bz, Sq, H, D)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "scale",
                                             "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None):
    """Model-layout paged decode attention.

    q: (B, 1, H, D) — one decoding token per sequence, H = G * KV (GQA);
    k_pages, v_pages: (num_pages, page_size, KV, D) block storage;
    block_tables: (B, pages_per_seq) int32; lengths: (B,) valid positions
    per sequence including the current token.  Returns (B, 1, H, D).
    """
    if interpret is None:
        interpret = _default_interpret()
    B, S, H, D = q.shape
    assert S == 1, "paged attention is single-token decode"
    KV = k_pages.shape[2]
    G = H // KV
    qf = q[:, 0].reshape(B, KV, G, D)
    out = _pa.paged_attention(qf, k_pages, v_pages, block_tables, lengths,
                              window=window, softcap=softcap, scale=scale,
                              interpret=interpret)
    return out.reshape(B, 1, H, D)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "scale",
                                             "interpret"))
def paged_prefill_attention(q, k_pages, v_pages, block_tables, start_pos,
                            q_lens, *, window: Optional[int] = None,
                            softcap: Optional[float] = None,
                            scale: Optional[float] = None,
                            interpret: Optional[bool] = None):
    """Model-layout paged chunked-prefill attention.

    q: (B, C, H, D) — a chunk of C query tokens per sequence, H = G * KV
    (GQA); k_pages, v_pages: (num_pages, page_size, KV, D) block storage
    with the chunk's own K/V already scattered in; block_tables:
    (B, pages_per_seq) int32; start_pos: (B,) absolute position of each
    row's first query token; q_lens: (B,) valid query tokens per row
    (rows/tokens past q_lens are padding and return zeros).
    Returns (B, C, H, D).
    """
    if interpret is None:
        interpret = _default_interpret()
    B, C, H, D = q.shape
    KV = k_pages.shape[2]
    G = H // KV
    # fold (C, G) -> CG rows grouped per KV head: row c*G+g
    qf = q.reshape(B, C, KV, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KV, C * G, D)
    out = _pp.paged_prefill(qf, k_pages, v_pages, block_tables, start_pos,
                            q_lens, group=G, window=window, softcap=softcap,
                            scale=scale, interpret=interpret)
    return out.reshape(B, KV, C, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, C, H, D)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, A, B, C, *, chunk: int = 256,
        interpret: Optional[bool] = None):
    """Model-layout SSD scan.

    x: (b, S, H, P); dt: (b, S, H); A: (H,); B, C: (b, S, G, N), G | H.
    Returns y: (b, S, H, P).
    """
    if interpret is None:
        interpret = _default_interpret()
    b, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    Bf = jnp.repeat(B, rep, axis=2)                       # (b, S, H, N)
    Cf = jnp.repeat(C, rep, axis=2)
    xf = x.transpose(0, 2, 1, 3).reshape(b * H, S, P)
    dtf = dt.transpose(0, 2, 1).reshape(b * H, S)
    Bff = Bf.transpose(0, 2, 1, 3).reshape(b * H, S, N)
    Cff = Cf.transpose(0, 2, 1, 3).reshape(b * H, S, N)
    Af = jnp.broadcast_to(A[None], (b, H)).reshape(b * H)
    y = _ssd.ssd_scan(xf, dtf, Af, Bff, Cff, chunk, interpret=interpret)
    return y.reshape(b, H, S, P).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-6,
            interpret: Optional[bool] = None):
    if interpret is None:
        interpret = _default_interpret()
    return _rn.rmsnorm(x, scale, eps=eps, interpret=interpret)
