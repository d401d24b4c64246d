"""Mixture-of-Experts: top-k router; capacity-bounded dispatch for
training, the dropless expert-share layer for serving.

Serving (:func:`expert_share_block`): the layer is told which experts
this chip holds (``cfg.moe_first_expert`` and ``cfg.num_experts`` of the
router's ``cfg.router_width`` outputs).  Each real token is routed over
every router output in float32 (softmax, top-k, the k gates renormalised
over themselves); its assignments to held experts are sorted by expert
and computed in one grouped matmul (``kernels/expert_gmm.py``), with no
capacity, so no assignment is dropped and a token's output never depends
on what else is in the step.  Padding tokens and inactive decode rows
(``valid`` false) get no expert rows.  What the experts held elsewhere
would add is left out: on one chip the layer runs without the exchange
that expert parallelism puts around it.

Training (:func:`moe_block`) keeps the grouped capacity dispatch below.

TPU adaptation: instead of the GShard one-hot-einsum dispatch (whose
(tokens, experts, capacity) dispatch tensor is quadratically large for the
assigned 128-expert / 1M-token shapes), we use a *grouped sort-based*
dispatch:

  * tokens are processed in G groups (one group per sequence), so under the
    (data, model) mesh the per-group argsort/rank is local to the data
    shard — routing never forces a global gather of tokens;
  * within a group: top-k assignment -> argsort by expert id -> rank within
    expert via a max-scan -> scatter into a dense (E, C, d) buffer;
  * batched expert FFN: one einsum over the expert dim (MXU friendly, and
    the natural target for expert-parallel sharding of E over 'model' —
    XLA SPMD turns the buffer re-sharding into the paper-family all-to-all);
  * gather back + combine with renormalized router weights.

Memory is O(G * E * C_g * d) with C_g ~ tokens_per_group * k / E, matching
the activation footprint of the dense archs.  Tokens beyond an expert's
capacity are dropped (zero combine weight) — the standard capacity-factor
trade-off; the Switch-style aux loss pushes the router away from overflow.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.models import modules as nn

ROW_TILE = 128          # rows of the grouped matmul's tiles


def init_moe(key, cfg):
    d, dff, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    kr, ki, kg, ko = jax.random.split(key, 4)
    s = 1.0 / np.sqrt(d)
    return {
        "router": nn.init_linear(kr, d, cfg.router_width),
        # expert-stacked gated MLP weights: leading dim = experts
        "wi": nn.truncated_normal_init(ki, (e, d, dff), s),
        "wg": nn.truncated_normal_init(kg, (e, d, dff), s),
        "wo": nn.truncated_normal_init(ko, (e, dff, d), 1.0 / np.sqrt(dff)),
    }


def expert_capacity(tokens_per_group: int, cfg) -> int:
    c = int(np.ceil(tokens_per_group * cfg.num_experts_per_tok
                    * cfg.moe_capacity_factor / cfg.num_experts))
    return max(8, int(np.ceil(c / 8)) * 8)          # pad to a lane-friendly size


def _dispatch_indices(eidx, capacity: int):
    """Per-group dispatch bookkeeping.

    eidx: (T, K) expert ids.  Returns (expert, slot_rank, keep) each (T*K,).
    """
    T, K = eidx.shape
    flat_e = eidx.reshape(T * K)
    order = jnp.argsort(flat_e)                                   # stable
    sorted_e = flat_e[order]
    # rank of each sorted slot within its expert segment
    is_start = jnp.concatenate(
        [jnp.ones((1,), jnp.int32),
         (sorted_e[1:] != sorted_e[:-1]).astype(jnp.int32)])
    seg_start = jnp.where(is_start == 1, jnp.arange(T * K), 0)
    seg_start = jax.lax.associative_scan(jnp.maximum, seg_start)
    rank_sorted = jnp.arange(T * K) - seg_start
    rank = rank_sorted[jnp.argsort(order)]                        # undo the sort
    keep = rank < capacity
    safe_e = jnp.where(keep, flat_e, 0)
    safe_r = jnp.where(keep, rank, capacity - 1)
    return safe_e, safe_r, keep


def moe_block(params, cfg, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    Tokens are dispatched in contiguous groups of ~moe_group_size tokens
    (batch-major, so groups never straddle the batch/data sharding).  The
    group count adapts to the calling shape: train/prefill get ~4096-token
    groups; a decode batch collapses to ONE group so capacity padding does
    not explode (the §Perf fix for the MoE decode shapes).
    """
    B, S, d = x.shape
    T = B * S
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    dt = jnp.dtype(cfg.dtype)
    # choose a group size that divides T and is close to moe_group_size
    tokens_per_group = min(cfg.moe_group_size, T)
    while T % tokens_per_group != 0:
        tokens_per_group -= 1
    G = T // tokens_per_group
    xg = x.reshape(G, tokens_per_group, d)
    C = expert_capacity(tokens_per_group, cfg)

    Tg = tokens_per_group

    # ---- router (f32) --------------------------------------------------------
    logits = nn.linear(params["router"], xg.astype(jnp.float32),
                       dtype=jnp.float32)                         # (G, Tg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, K)                          # (G, Tg, K)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    # ---- aux load-balance loss (Switch-style, over all tokens) ----------------
    me = jnp.mean(probs, axis=(0, 1))                             # (E,)
    ce = jnp.mean(jax.nn.one_hot(eidx[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    aux = cfg.router_aux_loss_coef * E * jnp.sum(me * ce)

    # ---- per-group dispatch -----------------------------------------------------
    safe_e, safe_r, keep = jax.vmap(
        lambda ei: _dispatch_indices(ei, C))(eidx)                # (G, Tg*K)

    tok_of_slot = jnp.arange(Tg * K) // K

    def scatter_group(xgr, eg, rg, kg):
        contrib = jnp.where(kg[:, None], xgr[tok_of_slot].astype(dt), 0)
        return jnp.zeros((E, C, d), dt).at[eg, rg].add(contrib)

    buf = jax.vmap(scatter_group)(xg, safe_e, safe_r, keep)       # (G, E, C, d)
    if cfg.moe_buffer_shard:
        from jax.sharding import PartitionSpec as P
        buf = jax.lax.with_sharding_constraint(
            buf, P(None, cfg.moe_buffer_shard, None, None))

    # ---- batched expert FFN (E is the expert-parallel axis) ----------------------
    a = nn.activation(cfg.act)
    hg = jnp.einsum("gecd,edf->gecf", buf, params["wg"].astype(dt))
    hi = jnp.einsum("gecd,edf->gecf", buf, params["wi"].astype(dt))
    ho = jnp.einsum("gecf,efd->gecd", a(hg) * hi, params["wo"].astype(dt))

    # ---- gather back + combine ------------------------------------------------------
    def gather_group(hog, eg, rg):
        return hog[eg, rg]                                        # (Tg*K, d)

    slot_out = jax.vmap(gather_group)(ho, safe_e, safe_r)         # (G, Tg*K, d)
    w = jnp.where(keep, gate.reshape(G, Tg * K), 0.0)
    out = jnp.sum((slot_out.astype(jnp.float32)
                   * w[..., None]).reshape(G, Tg, K, d), axis=2)
    return out.reshape(B, S, d).astype(x.dtype), aux


def route(params, cfg, x):
    """Router of every expert, in float32: ``(gates (T, K), experts
    (T, K))`` for tokens ``x`` (T, d), the K gates renormalised to sum
    to 1."""
    logits = jnp.dot(x.astype(jnp.float32),
                     params["router"]["w"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    gate, eidx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                               cfg.num_experts_per_tok)
    return gate / jnp.sum(gate, axis=-1, keepdims=True), eidx


def expert_share_block(params, cfg, x, valid, layer=0):
    """Dropless serving expert layer over the experts this chip holds.

    x: (B, S, d); valid: (B, S) bool, false for padding tokens and
    inactive decode rows.  ``params["router"]`` is this layer's router;
    ``params["wi"]``, ``["wg"]``, ``["wo"]`` hold ``E`` experts a layer,
    this layer's from index ``layer * E``: one layer's (``layer`` 0), or
    every layer's stacked, which the grouped matmul reads in place.
    Returns ``(out (B, S, d), counts)``: ``counts["rows"]`` (E,) int32,
    the assignments of valid tokens to each held expert, and
    ``counts["executed"]``, the rows the grouped matmul ran (tile padding
    included).
    """
    B, S, d = x.shape
    T, E, K = B * S, cfg.num_experts, cfg.num_experts_per_tok
    xf = x.reshape(T, d)
    gate, eidx = route(params, cfg, xf)
    local = eidx - cfg.moe_first_expert
    held = ((local >= 0) & (local < E)
            & valid.reshape(T, 1)).reshape(T * K)
    # each assignment's row among the rows sorted by held expert; the
    # assignments of no held expert (group E) follow them, uncomputed
    group = jnp.where(held, local.reshape(T * K), E)
    onehot = (group[:, None] == jnp.arange(E + 1)[None]).astype(jnp.int32)
    sizes = jnp.sum(onehot, axis=0)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    row = jnp.cumsum(sizes)[group] - sizes[group] + rank
    m = -(-(T * K) // ROW_TILE) * ROW_TILE
    token = jnp.zeros((m,), jnp.int32).at[row].set(
        jnp.arange(T * K, dtype=jnp.int32) // K)
    y, executed = ops.expert_gmm(
        xf.astype(cfg.dtype)[token], params["wi"], params["wg"],
        params["wo"], sizes[:E], layer * E, tm=ROW_TILE)
    part = jnp.where(held[:, None], y[row].astype(jnp.float32)
                     * gate.reshape(T * K, 1), 0.0)
    out = jnp.sum(part.reshape(T, K, d), axis=1)
    return (out.reshape(B, S, d).astype(x.dtype),
            {"rows": sizes[:E], "executed": executed})
