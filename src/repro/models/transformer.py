"""Composable transformer stack covering all assigned architecture families.

Families:
  dense   — llama-style pre-norm GQA decoder (+ gemma2 local/global pattern,
            logit softcaps, post-block norms; qwen2 QKV bias)
  moe     — dense attention + top-k MoE FFN (dbrx, qwen3-moe); serving
            runs the dropless expert-share layer over the experts held
  ssm     — Mamba2 (SSD) stack, attention-free
  hybrid  — Mamba2 backbone + one weight-SHARED attention block applied
            every ``hybrid_attn_every`` layers (zamba2)
  encdec  — whisper: bidirectional encoder over stub frame embeddings +
            causal decoder with cross-attention, LayerNorm/GELU, learned
            position embeddings
  vlm     — qwen2-vl backbone: stub patch embeddings prepended, M-RoPE

Layers are *stacked* (params carry a leading layer dim) and executed with
``lax.scan`` so the compiled HLO contains ONE layer body regardless of
depth — this is what keeps the 46–80-layer dry-run compiles tractable and
the activation footprint flat (one rematted layer live at a time).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as attn
from repro.models import modules as nn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib


# ---------------------------------------------------------------------------
# Norm dispatch
# ---------------------------------------------------------------------------

def _init_norm(cfg):
    return (nn.init_layernorm(cfg.d_model) if cfg.norm_type == "layernorm"
            else nn.init_rmsnorm(cfg.d_model))


def _norm(cfg, p, x):
    return (nn.layernorm(p, x, cfg.norm_eps) if cfg.norm_type == "layernorm"
            else nn.rmsnorm(p, x, cfg.norm_eps))


# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------

def _init_attn_mlp_layer(cfg, key, cross: bool = False, kind: str = "dense"):
    ks = jax.random.split(key, 8)
    p = {"ln1": _init_norm(cfg), "ln2": _init_norm(cfg),
         "attn": attn.init_attention(ks[0], cfg)}
    if cross:
        p["ln_cross"] = _init_norm(cfg)
        p["cross_attn"] = attn.init_attention(ks[1], cfg, cross=True)
    if kind == "moe":
        p["moe"] = moe_lib.init_moe(ks[2], cfg)
    else:
        p["mlp"] = nn.init_mlp(ks[3], cfg.d_model, cfg.d_ff)
    if cfg.post_block_norm:
        p["post_ln1"] = _init_norm(cfg)
        p["post_ln2"] = _init_norm(cfg)
    return p


def _init_mamba_layer(cfg, key):
    return {"ln1": _init_norm(cfg), "mamba": ssm_lib.init_mamba2(key, cfg)}


def _stack_init(fn, keys):
    return jax.vmap(fn)(keys)


# ---------------------------------------------------------------------------
# Per-layer apply
# ---------------------------------------------------------------------------

def _apply_attn_mlp_layer(p, cfg, x, *, window, positions=None, causal=True,
                          cache=None, cache_index=None, encoder_out=None,
                          use_rope=True, block_tables=None, q_lens=None,
                          layer=None, expert_share=None):
    """Pre-norm attention + (cross-attention) + MLP/MoE.  Returns
    (x, new_cache, aux).  MoE layers of the serving programs, which pass
    ``expert_share`` = (valid (B, S), every layer's stacked expert
    weights from :func:`_held_experts`), run the dropless expert-share
    layer over the valid tokens with this ``layer``'s experts, and aux
    is its row counts (:func:`moe_lib.expert_share_block`); training's
    run the capacity dispatch, and aux is the load-balance loss."""
    h = _norm(cfg, p["ln1"], x)
    a, new_cache = attn.attention_block(
        p["attn"], cfg, h, positions=positions, causal=causal, window=window,
        cache=cache, cache_index=cache_index, use_rope=use_rope,
        block_tables=block_tables, q_lens=q_lens, layer=layer)
    if cfg.post_block_norm:
        a = _norm(cfg, p["post_ln1"], a)
    x = x + a
    if encoder_out is not None:
        h = _norm(cfg, p["ln_cross"], x)
        c, _ = attn.attention_block(p["cross_attn"], cfg, h,
                                    kv_override=encoder_out, use_rope=False)
        x = x + c
    h = _norm(cfg, p["ln2"], x)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p and expert_share is not None:
        valid, experts = expert_share
        m, aux = moe_lib.expert_share_block({**p["moe"], **experts}, cfg, h,
                                            valid, layer)
    elif "moe" in p:
        m, aux = moe_lib.moe_block(p["moe"], cfg, h)
    else:
        m = nn.mlp(p["mlp"], h, cfg.act, dtype=jnp.dtype(cfg.dtype))
    if cfg.post_block_norm:
        m = _norm(cfg, p["post_ln2"], m)
    return x + m, new_cache, aux


def _apply_mamba_layer(p, cfg, x, cache=None):
    h = _norm(cfg, p["ln1"], x)
    m, new_cache = ssm_lib.mamba2_block(p["mamba"], cfg, h, cache=cache)
    return x + m, new_cache


# ---------------------------------------------------------------------------
# Layer pattern: windows per position in the scan group
# ---------------------------------------------------------------------------

def layer_pattern(cfg, long_context: bool = False):
    """Returns a tuple of window sizes (None = full attention), one entry per
    layer inside a scan group.  gemma2 alternates (local, global)."""
    if cfg.local_global_pattern:
        g_win = cfg.long_context_window if long_context else None
        return (cfg.sliding_window, g_win)
    win = cfg.sliding_window
    if long_context and cfg.long_context_window is not None:
        win = cfg.long_context_window if win is None else win
    return (win,)


# ---------------------------------------------------------------------------
# Parameter init (full model)
# ---------------------------------------------------------------------------

def init_params(cfg, key) -> Dict[str, Any]:
    keys = jax.random.split(key, 8)
    p: Dict[str, Any] = {
        "embed": nn.init_embedding(keys[0], cfg.vocab_size, cfg.d_model),
        "final_norm": _init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = nn.init_linear(keys[1], cfg.d_model, cfg.vocab_size)
    if cfg.max_pos_embed:
        p["pos_embed"] = nn.truncated_normal_init(
            keys[2], (cfg.max_pos_embed, cfg.d_model), 0.02)

    L = cfg.num_layers
    if cfg.family in ("dense", "vlm", "moe"):
        pat = len(layer_pattern(cfg))
        assert L % pat == 0, (L, pat)
        lk = jax.random.split(keys[3], L).reshape(L // pat, pat, 2)
        kind = "moe" if cfg.family == "moe" else "dense"
        p["layers"] = _stack_init(
            jax.vmap(lambda k: _init_attn_mlp_layer(cfg, k, kind=kind)), lk)
    elif cfg.family == "ssm":
        lk = jax.random.split(keys[3], L)
        p["layers"] = _stack_init(lambda k: _init_mamba_layer(cfg, k), lk)
    elif cfg.family == "hybrid":
        E = cfg.hybrid_attn_every
        G, R = L // E, L % E
        gk = jax.random.split(keys[3], G * E).reshape(G, E, 2)
        p["layers"] = _stack_init(
            jax.vmap(lambda k: _init_mamba_layer(cfg, k)), gk)
        if R:
            rk = jax.random.split(keys[4], R)
            p["tail_layers"] = _stack_init(lambda k: _init_mamba_layer(cfg, k), rk)
        p["shared_attn"] = _init_attn_mlp_layer(cfg, keys[5])
    elif cfg.family == "encdec":
        ek = jax.random.split(keys[3], cfg.encoder_layers)
        p["encoder"] = {
            "layers": _stack_init(
                lambda k: _init_attn_mlp_layer(cfg, k), ek),
            "final_norm": _init_norm(cfg),
            "pos_embed": nn.truncated_normal_init(
                keys[6], (cfg.encoder_seq, cfg.d_model), 0.02),
        }
        dk = jax.random.split(keys[4], L)
        p["layers"] = _stack_init(
            lambda k: _init_attn_mlp_layer(cfg, k, cross=True), dk)
    else:
        raise ValueError(cfg.family)
    return p


# ---------------------------------------------------------------------------
# Serving weights: held in the compute dtype where every use casts to it
# ---------------------------------------------------------------------------

# modules whose ``w`` and ``b`` every program runs through
# ``nn.linear(..., dtype=cfg.dtype)``, which casts them at each use
_COMPUTE_DTYPE_MODULES = ("attn", "cross_attn", "mlp")


def serving_params(cfg, params):
    """The tree a serving engine holds: every leaf that each program casts
    to ``cfg.dtype`` at every use, already in ``cfg.dtype``; every other
    leaf as given.  ``astype`` to a leaf's own dtype is a no-op, so the
    programs compute the same numbers, reading 2-byte weights where a
    float32 master is read (4 bytes), cast (2 written) and read again in
    every call.

    In ``cfg.dtype``: the attention projections and their biases (a bias
    is added in the projection's dtype), the dense MLP, the embedding
    table (gathered in ``cfg.dtype``; a tied head multiplies it by the
    final hidden state, which is in ``cfg.dtype``) and ``lm_head``.  As
    given: norm gains, q/k norms included (``rmsnorm`` applies them in
    float32); the MoE router (routes in float32); the expert stacks (the
    grouped matmul casts them in VMEM); the SSM leaves; position tables.
    A tree of ``jax.ShapeDtypeStruct`` (shapes to lower programs from)
    maps to one."""
    dt = jnp.dtype(cfg.dtype)

    def hold(path, a):
        keys = [getattr(k, "key", None) for k in path]
        if not (keys[0] in ("embed", "lm_head")
                or (len(keys) >= 3 and keys[-1] in ("w", "b")
                    and keys[-3] in _COMPUTE_DTYPE_MODULES)):
            return a
        if isinstance(a, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(a.shape, dt, sharding=a.sharding)
        return a.astype(dt)

    return jax.tree_util.tree_map_with_path(hold, params)


# ---------------------------------------------------------------------------
# KV / SSM cache
# ---------------------------------------------------------------------------

def _cache_struct(cfg, batch: int, seq_len: int):
    """Nested dict of (shape, dtype) tuples describing the decode cache."""
    kv = cfg.num_kv_heads
    dh = cfg.resolved_head_dim if cfg.num_heads else 0
    L = cfg.num_layers

    def attn_cache(lead):
        if cfg.kv_cache_dtype == "int8":
            return {"k": (lead + (batch, seq_len, kv, dh), jnp.int8),
                    "v": (lead + (batch, seq_len, kv, dh), jnp.int8),
                    "k_scale": (lead + (batch, seq_len, kv), jnp.bfloat16),
                    "v_scale": (lead + (batch, seq_len, kv), jnp.bfloat16)}
        return {"k": (lead + (batch, seq_len, kv, dh), jnp.bfloat16),
                "v": (lead + (batch, seq_len, kv, dh), jnp.bfloat16)}

    def mamba_cache(lead):
        H, N, P = cfg.ssm_num_heads, cfg.ssm_state, cfg.ssm_head_dim
        conv_dim = cfg.ssm_d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
        return {"ssm": (lead + (batch, H, N, P), jnp.float32),
                "conv": (lead + (batch, cfg.ssm_conv_width - 1, conv_dim),
                         jnp.float32)}

    if cfg.family in ("dense", "vlm", "moe"):
        pat = len(layer_pattern(cfg))
        return {"layers": attn_cache((L // pat, pat))}
    if cfg.family == "ssm":
        return {"layers": mamba_cache((L,))}
    if cfg.family == "hybrid":
        E = cfg.hybrid_attn_every
        G, R = L // E, L % E
        c = {"layers": mamba_cache((G, E)), "shared_attn": attn_cache((G,))}
        if R:
            c["tail_layers"] = mamba_cache((R,))
        return c
    if cfg.family == "encdec":
        return {"layers": attn_cache((L,))}
    raise ValueError(cfg.family)


def init_cache_specs(cfg, batch: int, seq_len: int):
    return jax.tree.map(lambda sd: jax.ShapeDtypeStruct(*sd),
                        _cache_struct(cfg, batch, seq_len),
                        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
                        and isinstance(x[0], tuple))


def init_cache(cfg, batch: int, seq_len: int):
    return jax.tree.map(lambda sd: jnp.zeros(*sd),
                        _cache_struct(cfg, batch, seq_len),
                        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
                        and isinstance(x[0], tuple))


def init_paged_cache(cfg, num_blocks: int, block_size: int):
    """The paged KV pool: ``{"k", "v"}``, each ``(num_layers, num_blocks,
    block_size, KV * D)`` bf16 — every attention layer's blocks in one
    stacked array, position p's KV head h in lanes ``[h * D, (h + 1) *
    D)`` of the block's row ``p % block_size``.

    This is the layout the paged kernels' page blocks read, and the one a
    token's K/V row is scattered into in place, so the paged programs
    carry the pool through their layer loop with no per-layer slice,
    relayout or restack (a ``(…, KV, D)`` minor pair would be padded and
    relaid out on the TPU)."""
    shape = (cfg.num_layers, num_blocks, block_size,
             cfg.num_kv_heads * cfg.resolved_head_dim)
    return {"k": jnp.zeros(shape, jnp.bfloat16),
            "v": jnp.zeros(shape, jnp.bfloat16)}


# ---------------------------------------------------------------------------
# Scan helpers
# ---------------------------------------------------------------------------

def _scan_layers(body, x0, stacked, length_axis_trees, remat: bool,
                 scan: bool = True, policy: str = "nothing"):
    """Scan ``body(carry, layer_slice)`` over the leading dim of ``stacked``.

    length_axis_trees: extra trees scanned alongside (e.g. caches); pass ()
    if none.  Returns (final_carry, stacked_outputs).

    scan=False unrolls into a python loop over layer slices (identical
    math and param layout) — used by the roofline pass because XLA's
    cost_analysis counts a while-loop body once, not x trip-count.
    """
    if remat:
        pol = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
               if policy == "dots"
               else jax.checkpoint_policies.nothing_saveable)
        fn = jax.checkpoint(body, policy=pol)
    else:
        fn = body
    if scan:
        return jax.lax.scan(fn, x0, (stacked, *length_axis_trees))
    length = jax.tree.leaves(stacked)[0].shape[0]
    carry, ys = x0, []
    for i in range(length):
        xs = jax.tree.map(lambda a: a[i], (stacked, *length_axis_trees))
        carry, y = fn(carry, xs)
        ys.append(y)
    stacked_ys = (None if all(y is None for y in ys)
                  else jax.tree.map(lambda *a: jnp.stack(a), *ys))
    return carry, stacked_ys


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def forward(params, cfg, batch: Dict[str, Any], *,
            long_context: bool = False,
            last_only: bool = False,
            return_hidden: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward.  Returns (logits (B, S, V) f32, aux_loss).

    last_only: unembed only the final position (prefill serving) — avoids
    materializing the (B, S, V) logits tensor.
    return_hidden: return the final-norm hidden states instead of logits
    (used by the chunked-CE loss).
    """
    dt = jnp.dtype(cfg.dtype)
    tokens = batch["tokens"]
    B = tokens.shape[0]

    x = nn.embed(params["embed"], tokens, dtype=dt)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), dt)

    positions = None
    encoder_out = None
    if cfg.family == "vlm":
        x = jnp.concatenate([batch["patch_embeddings"].astype(dt), x], axis=1)
        positions = batch["mrope_positions"]
    S = x.shape[1]
    if cfg.max_pos_embed:
        x = x + params["pos_embed"][:S][None].astype(dt)
    if cfg.family == "encdec":
        encoder_out = _encode(params["encoder"], cfg, batch["encoder_input"])

    windows = layer_pattern(cfg, long_context)
    aux_total = jnp.zeros((), jnp.float32)

    if cfg.family in ("dense", "vlm", "moe", "encdec"):
        use_rope = cfg.max_pos_embed == 0

        def body(carry, xs):
            x, aux = carry
            (group_params,) = xs
            for i, win in enumerate(windows if cfg.family != "encdec" else (None,)):
                lp = jax.tree.map(lambda a: a[i], group_params) \
                    if cfg.family != "encdec" else group_params
                x, _, a = _apply_attn_mlp_layer(
                    lp, cfg, x, window=win, positions=positions,
                    encoder_out=encoder_out, use_rope=use_rope)
                aux = aux + a
            return (x, aux), None

        if cfg.family == "encdec":
            stacked = params["layers"]
        else:
            stacked = params["layers"]
        (x, aux_total), _ = _scan_layers(body, (x, aux_total), stacked, (),
                                         cfg.remat, cfg.scan_layers,
                                         cfg.remat_policy)
    elif cfg.family == "ssm":
        def body(carry, xs):
            (lp,) = xs
            x, _ = _apply_mamba_layer(lp, cfg, carry)
            return x, None
        x, _ = _scan_layers(body, x, params["layers"], (), cfg.remat,
                            cfg.scan_layers, cfg.remat_policy)
    elif cfg.family == "hybrid":
        shared = params["shared_attn"]
        win = cfg.long_context_window if long_context else None

        def body(carry, xs):
            (gp,) = xs
            x = carry
            for i in range(cfg.hybrid_attn_every):
                lp = jax.tree.map(lambda a: a[i], gp)
                x, _ = _apply_mamba_layer(lp, cfg, x)
            x, _, _ = _apply_attn_mlp_layer(shared, cfg, x, window=win)
            return x, None

        x, _ = _scan_layers(body, x, params["layers"], (), cfg.remat,
                            cfg.scan_layers, cfg.remat_policy)
        if "tail_layers" in params:
            def tail_body(carry, xs):
                (lp,) = xs
                x, _ = _apply_mamba_layer(lp, cfg, carry)
                return x, None
            x, _ = _scan_layers(tail_body, x, params["tail_layers"], (),
                                cfg.remat, cfg.scan_layers, cfg.remat_policy)
    else:
        raise ValueError(cfg.family)

    if last_only:
        x = x[:, -1:]
    x = _norm(cfg, params["final_norm"], x)
    if return_hidden:
        return x, aux_total
    logits = (nn.unembed(params["embed"], x) if cfg.tie_embeddings
              else nn.linear(params["lm_head"], x, dtype=dt).astype(jnp.float32))
    logits = nn.softcap(logits, cfg.final_logit_softcap)
    return logits, aux_total


def _encode(enc_params, cfg, encoder_input):
    dt = jnp.dtype(cfg.dtype)
    x = encoder_input.astype(dt)
    x = x + enc_params["pos_embed"][:x.shape[1]][None].astype(dt)

    def body(carry, xs):
        (lp,) = xs
        x, _, _ = _apply_attn_mlp_layer(lp, cfg, carry, window=None,
                                        causal=False, use_rope=False)
        return x, None

    x, _ = _scan_layers(body, x, enc_params["layers"], (), cfg.remat,
                        cfg.scan_layers, cfg.remat_policy)
    return _norm(cfg, enc_params["final_norm"], x)


# ---------------------------------------------------------------------------
# Decode step (one token against a seq_len cache)
# ---------------------------------------------------------------------------

def _held_experts(cfg, layers):
    """The serving programs' split of a MoE stack: the stacked layer tree
    without the experts' weights, for the layer scan, and those weights
    of every layer as ``(layers * E, ...)`` stacks, which the grouped
    matmul reads in place (a scanned slice of them would be copied out,
    every call, for the kernel).  Other families pass through."""
    if cfg.family != "moe":
        return layers, None
    m = layers["moe"]
    experts = {k: m[k].reshape((-1,) + m[k].shape[-2:])
               for k in ("wi", "wg", "wo")}
    return dict(layers, moe={"router": m["router"]}), experts


def decode_step(params, cfg, batch: Dict[str, Any], *,
                long_context: bool = False,
                capacity_moe: bool = False) -> Tuple[jnp.ndarray, Any]:
    """One-token decode against the dense per-slot cache.  batch: tokens
    (B,1), positions (B,), cache, plus encoder_output / mrope_positions
    when applicable, and for an MoE model ``valid`` (B,) bool, the rows
    that serve a sequence (every row when absent).  MoE layers run the
    dropless expert-share layer over the valid rows, as served; with
    ``capacity_moe`` they run training's capacity dispatch instead (the
    sharded dry-run decode).  (The paged pool decodes through
    :func:`prefill_step` with one-token chunks.)  Returns (logits
    (B, 1, V) f32, new_cache)."""
    dt = jnp.dtype(cfg.dtype)
    tokens, idx, cache = batch["tokens"], batch["positions"], batch["cache"]
    B = tokens.shape[0]

    x = nn.embed(params["embed"], tokens, dtype=dt)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), dt)
    if cfg.max_pos_embed:
        x = x + params["pos_embed"].astype(dt)[idx][:, None]

    positions = batch.get("mrope_positions")
    if positions is None:
        positions = idx[:, None]                                   # (B,1)
    encoder_out = batch.get("encoder_output")
    windows = layer_pattern(cfg, long_context)
    use_rope = cfg.max_pos_embed == 0
    new_cache = dict(cache)

    if cfg.family == "moe" and not capacity_moe:
        layers, experts = _held_experts(cfg, params["layers"])
        valid = batch.get("valid", jnp.ones((B,), bool))[:, None]  # (B,1)
        groups = jax.tree.leaves(layers)[0].shape[0]

        def body(x, xs):
            gp, gc, g = xs
            ncs = []
            for i, win in enumerate(windows):
                lp = jax.tree.map(lambda a: a[i], gp)
                lc = jax.tree.map(lambda a: a[i], gc)
                x, nc_i, _ = _apply_attn_mlp_layer(
                    lp, cfg, x, window=win, positions=positions, cache=lc,
                    cache_index=idx, use_rope=use_rope,
                    layer=g * len(windows) + i, expert_share=(valid, experts))
                ncs.append(nc_i)
            return x, jax.tree.map(lambda *a: jnp.stack(a), *ncs)

        x, nc = _scan_layers(
            body, x, layers,
            (cache["layers"], jnp.arange(groups, dtype=jnp.int32)), False,
            cfg.scan_layers)
        new_cache["layers"] = nc
    elif cfg.family in ("dense", "vlm", "moe", "encdec"):
        def body(x, xs):
            if cfg.family == "encdec":
                lp, lc = xs
                x, nc, _ = _apply_attn_mlp_layer(
                    lp, cfg, x, window=None, positions=positions, cache=lc,
                    cache_index=idx, encoder_out=encoder_out, use_rope=use_rope)
            else:
                gp, gc = xs
                ncs = []
                for i, win in enumerate(windows):
                    lp = jax.tree.map(lambda a: a[i], gp)
                    lc = jax.tree.map(lambda a: a[i], gc)
                    x, nc_i, _ = _apply_attn_mlp_layer(
                        lp, cfg, x, window=win, positions=positions, cache=lc,
                        cache_index=idx, use_rope=use_rope)
                    ncs.append(nc_i)
                nc = jax.tree.map(lambda *a: jnp.stack(a), *ncs)
            return x, nc

        x, nc = _scan_layers(body, x, params["layers"], (cache["layers"],),
                             False, cfg.scan_layers)
        new_cache["layers"] = nc
    elif cfg.family == "ssm":
        def body(x, xs):
            lp, lc = xs
            x, nc = _apply_mamba_layer(lp, cfg, x, cache=lc)
            return x, nc
        x, nc = _scan_layers(body, x, params["layers"], (cache["layers"],),
                             False, cfg.scan_layers)
        new_cache["layers"] = nc
    elif cfg.family == "hybrid":
        shared = params["shared_attn"]
        win = cfg.long_context_window if long_context else None

        def body(x, xs):
            gp, gc, ac = xs
            ncs = []
            for i in range(cfg.hybrid_attn_every):
                lp = jax.tree.map(lambda a: a[i], gp)
                lc = jax.tree.map(lambda a: a[i], gc)
                x, nc_i = _apply_mamba_layer(lp, cfg, x, cache=lc)
                ncs.append(nc_i)
            x, nac, _ = _apply_attn_mlp_layer(
                shared, cfg, x, window=win, positions=positions, cache=ac,
                cache_index=idx)
            return x, (jax.tree.map(lambda *a: jnp.stack(a), *ncs), nac)

        x, (nc, nac) = _scan_layers(
            body, x, params["layers"],
            (cache["layers"], cache["shared_attn"]), False, cfg.scan_layers)
        new_cache["layers"], new_cache["shared_attn"] = nc, nac
        if "tail_layers" in params:
            def tail(x, xs):
                lp, lc = xs
                x, nc = _apply_mamba_layer(lp, cfg, x, cache=lc)
                return x, nc
            x, ntc = _scan_layers(tail, x, params["tail_layers"],
                                  (cache["tail_layers"],), False,
                                  cfg.scan_layers)
            new_cache["tail_layers"] = ntc
    else:
        raise ValueError(cfg.family)

    x = _norm(cfg, params["final_norm"], x)
    logits = (nn.unembed(params["embed"], x) if cfg.tie_embeddings
              else nn.linear(params["lm_head"], x, dtype=dt).astype(jnp.float32))
    logits = nn.softcap(logits, cfg.final_logit_softcap)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Paged steps: the stacked pool carried through the layer loop
# ---------------------------------------------------------------------------

def prefill_step(params, cfg, batch: Dict[str, Any], *,
                 long_context: bool = False) -> Tuple[jnp.ndarray, Any]:
    """One chunk of batched paged prefill: ``C`` tokens for every row.

    batch: tokens (B, C); positions (B,) — each row's *start* position
    (row b's token t sits at absolute position ``positions[b] + t``);
    q_lens (B,) — valid tokens per row (padding tokens and whole padding
    rows are masked and their K/V routed to the trash block; absent:
    all C); cache — the paged pool (:func:`init_paged_cache`);
    block_tables (B, blocks_per_slot).

    The chunk's K/V are written straight into each row's pool blocks and
    attention gathers the full history (earlier chunks + this one)
    through the tables with the Pallas paged-prefill kernel, so paged
    prefill never materializes a dense ``max_seq_len`` stripe.  With
    C == 1 this is one paged decode step: the attention layer writes
    each row's K/V at its position and runs the paged decode kernel;
    batch ``valid`` (B,) then marks the rows that serve a sequence (a
    chunk's real tokens are those within ``q_lens``).  MoE layers run the
    dropless expert-share layer over the real tokens only.
    The whole stacked pool rides in the layer loop's carry: layer ``l``
    (scan group ``g``, member ``i``: ``l = g * len(pattern) + i``)
    scatters its rows into ``pool[:, l]`` in place and its kernel reads
    ``(l, page)`` blocks of the same buffer, so no layer's pages are
    sliced out, relaid out or restacked.  Returns (logits (B, C, V) f32,
    new pool, expert counts); logits at padding positions are garbage —
    callers index only real tokens.  The expert counts are ``{}`` unless
    the family is moe: then ``"expert_rows"`` (L, E) int32, each held
    expert's rows of real tokens in each layer, and
    ``"expert_rows_executed"`` (L,), the rows each layer's grouped
    matmul ran.
    """
    if cfg.family not in ("dense", "vlm", "moe"):
        raise ValueError(
            f"paged prefill needs a positional attention cache; family "
            f"{cfg.family!r} unsupported")
    dt = jnp.dtype(cfg.dtype)
    tokens, starts = batch["tokens"], batch["positions"]
    B, C = tokens.shape
    positions = (starts.astype(jnp.int32)[:, None]
                 + jnp.arange(C, dtype=jnp.int32)[None])           # (B, C)

    x = nn.embed(params["embed"], tokens, dtype=dt)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), dt)
    if cfg.max_pos_embed:
        safe = jnp.clip(positions, 0, cfg.max_pos_embed - 1)
        x = x + params["pos_embed"].astype(dt)[safe]

    windows = layer_pattern(cfg, long_context)
    use_rope = cfg.max_pos_embed == 0
    layers, experts = _held_experts(cfg, params["layers"])
    share = None
    if experts is not None:
        if "valid" in batch:                # one-token decode: live rows
            valid = jnp.broadcast_to(batch["valid"][:, None], (B, C))
        else:                               # a chunk's real tokens
            q_lens = batch.get("q_lens", jnp.full((B,), C, jnp.int32))
            valid = jnp.arange(C)[None] < q_lens[:, None]
        share = (valid, experts)

    def body(carry, xs):
        x, pool = carry
        gp, g = xs
        counts = []
        for i, win in enumerate(windows):
            lp = jax.tree.map(lambda a: a[i], gp)
            x, pool, aux = _apply_attn_mlp_layer(
                lp, cfg, x, window=win, positions=positions, cache=pool,
                cache_index=starts, use_rope=use_rope,
                block_tables=batch["block_tables"],
                q_lens=batch.get("q_lens"), layer=g * len(windows) + i,
                expert_share=share)
            counts.append(aux)
        if share is None:
            return (x, pool), None
        return (x, pool), jax.tree.map(lambda *a: jnp.stack(a), *counts)

    groups = jax.tree.leaves(layers)[0].shape[0]
    (x, pool), counts = _scan_layers(
        body, (x, batch["cache"]), layers,
        (jnp.arange(groups, dtype=jnp.int32),), False, cfg.scan_layers)
    expert_counts = {}
    if counts is not None:
        L = cfg.num_layers
        expert_counts = {
            "expert_rows": counts["rows"].reshape(L, -1),
            "expert_rows_executed": counts["executed"].reshape(L)}

    x = _norm(cfg, params["final_norm"], x)
    logits = (nn.unembed(params["embed"], x) if cfg.tie_embeddings
              else nn.linear(params["lm_head"], x, dtype=dt).astype(jnp.float32))
    logits = nn.softcap(logits, cfg.final_logit_softcap)
    return logits, pool, expert_counts


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def lm_loss(params, cfg, batch: Dict[str, Any], *,
            long_context: bool = False) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Next-token cross-entropy (+ MoE aux).  Returns (loss, metrics)."""
    logits, aux = forward(params, cfg, batch, long_context=long_context)
    labels = batch["labels"]
    if cfg.family == "vlm":
        npatch = cfg.num_patches
        logits = logits[:, npatch:]
        labels = labels[:, npatch:]
    # shift: logits[t] predicts labels[t+1]
    lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    tgt = labels[:, 1:]
    nll = -jnp.take_along_axis(lp, tgt[..., None], axis=-1)[..., 0]
    loss = jnp.mean(nll)
    total = loss + aux
    return total, {"ce_loss": loss, "aux_loss": aux,
                   "perplexity": jnp.exp(loss)}


# ---------------------------------------------------------------------------
# Chunked (fused) cross-entropy — beyond-paper memory optimization
# ---------------------------------------------------------------------------

def lm_loss_chunked(params, cfg, batch: Dict[str, Any], *,
                    long_context: bool = False,
                    seq_chunk: int = 512) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """CE loss without materializing the (B, S, V) logits tensor.

    Runs the trunk once, then scans over SEQUENCE chunks: each chunk
    unembeds (B, c, V), computes logsumexp + the target logit, and is
    rematerialized in the backward pass.  Peak activation memory for the
    loss drops from O(B*S*V) to O(B*seq_chunk*V) — the §Perf lever for the
    256k-vocab gemma2 train shapes.
    """
    dt = jnp.dtype(cfg.dtype)
    # --- trunk (same as forward, but stop before unembedding) -------------
    trunk_batch = dict(batch)
    labels = batch["labels"]

    x, aux = _trunk(params, cfg, trunk_batch, long_context=long_context)
    if cfg.family == "vlm":
        npatch = cfg.num_patches
        x = x[:, npatch:]
        labels = labels[:, npatch:]
    B, S, _ = x.shape
    x = x[:, :-1]
    tgt = labels[:, 1:]
    Sm = S - 1
    pad = (-Sm) % seq_chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        tgt = jnp.pad(tgt, ((0, 0), (0, pad)))
    n = (Sm + pad) // seq_chunk
    xs = jnp.moveaxis(x.reshape(B, n, seq_chunk, -1), 1, 0)
    ts = jnp.moveaxis(tgt.reshape(B, n, seq_chunk), 1, 0)
    valid = jnp.moveaxis(
        (jnp.arange(Sm + pad) < Sm).reshape(n, seq_chunk)[None].repeat(B, 0),
        1, 0)

    def chunk_nll(xc, tc, vc):
        logits = (nn.unembed(params["embed"], xc) if cfg.tie_embeddings
                  else nn.linear(params["lm_head"], xc, dtype=dt)
                  .astype(jnp.float32))
        logits = nn.softcap(logits, cfg.final_logit_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        hit = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - hit) * vc)

    def body(acc, xs_):
        xc, tc, vc = xs_
        return acc + jax.checkpoint(chunk_nll)(xc, tc, vc), None

    total_nll, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                (xs, ts, valid))
    loss = total_nll / (B * Sm)
    return loss + aux, {"ce_loss": loss, "aux_loss": aux,
                        "perplexity": jnp.exp(loss)}


def _trunk(params, cfg, batch, *, long_context=False):
    """forward() up to (but excluding) the unembedding; returns (x, aux)."""
    # reuse forward with a sentinel: final norm applied, no unembed
    return forward(params, cfg, batch, long_context=long_context,
                   return_hidden=True)
