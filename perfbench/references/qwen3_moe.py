"""Plain float32 reference of the Qwen3 sparse-expert decoder
(``Qwen3MoeForCausalLM``), for the chip's share of its experts.

Written from the published layer equations, independent of the serving
program: token embedding; per layer, RMSNorm, q/k/v projections (no
biases), RMSNorm of every head of q and of k over the head dimension
(its own gain), rotary embedding of q and k (rotate-half form,
frequencies ``theta ** (-2i / D)``), grouped-query causal softmax
attention scaled by ``1/sqrt(D)``, the output projection and a residual;
RMSNorm, then the router: softmax over all ``R`` experts of the layer,
the top ``K`` kept and their gates renormalised to sum to 1; the output
is the gate-weighted sum of ``down(silu(gate(x)) * up(x))`` over the
selected experts among the ``E`` held here (the others would be computed
on other chips and are left out, as in the program), and a residual; a
final RMSNorm and the separate output head.  Every held expert is
computed for every token and weighted by its gate, zero where the router
did not select it: a dense mixture, with no dispatch.

Every matmul runs at ``Precision.HIGHEST`` (and under
``jax.default_matmul_precision("highest")``).  ``mode="fp8"`` is the
control: every matmul's operands, the router's included, are rounded to
float8 (e4m3, scaled per tensor) before a float32 product.  One
sequence at a time, one layer at a time, attention in blocks of query
rows.  Departures from the published model: none, beyond the share.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import model
from perfbench.references.dense import (BUCKET, Q_BLOCK, _Frozen, _mm, _rms,
                                        _rope)


@functools.partial(jax.jit, static_argnames=("n", "mode"))
def _layer(x, w, *, n, mode):
    S = x.shape[0]
    H, KV, D, E, K = n["H"], n["KV"], n["D"], n["E"], n["K"]
    G = H // KV
    pos = jnp.arange(S)
    h = _rms(x, w["ln1"], n["eps"])
    q = _rms(_mm(h, w["wq"], "sd,dhk->shk", mode), w["q_norm"], n["eps"])
    k = _rms(_mm(h, w["wk"], "sd,dhk->shk", mode), w["k_norm"], n["eps"])
    v = _mm(h, w["wv"], "sd,dhk->shk", mode)
    q, k = _rope(q, pos, n["theta"]), _rope(k, pos, n["theta"])
    q = q.reshape(S, KV, G, D)
    outs = []
    for s0 in range(0, S, Q_BLOCK):
        qb = q[s0:s0 + Q_BLOCK]
        sc = _mm(qb, k, "qkgd,tkd->kgqt", mode) / np.sqrt(D)
        qpos = pos[s0:s0 + Q_BLOCK]
        sc = jnp.where(pos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(_mm(p, v, "kgqt,tkd->qkgd", mode))
    a = jnp.concatenate(outs, axis=0).reshape(S, H * D)
    x = x + _mm(a, w["wo"], "se,ed->sd", mode)
    h = _rms(x, w["ln2"], n["eps"])
    probs = jax.nn.softmax(_mm(h, w["router"], "sd,dr->sr", mode), axis=-1)
    top, idx = jax.lax.top_k(probs, K)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    held = idx - n["e0"]
    # (S, E): each held expert's renormalised gate, 0 where not selected
    mix = jnp.sum(jnp.where(held[:, :, None] == jnp.arange(E), top[:, :, None],
                            0.0), axis=1)
    g = _mm(h, w["wg"], "sd,edf->esf", mode)
    u = _mm(h, w["wi"], "sd,edf->esf", mode)
    y = _mm(jax.nn.silu(g) * u, w["wd"], "esf,efd->esd", mode)
    return x + jnp.einsum("se,esd->sd", mix, y)


@functools.partial(jax.jit, static_argnames=("n", "mode"))
def _head(x, final_gain, head_w, *, n, mode):
    return _mm(_rms(x, final_gain, n["eps"]), head_w, "sd,dv->sv", mode)


def logits(weights, config: dict, tokens: np.ndarray, rows: np.ndarray,
           mode: str = "f32") -> np.ndarray:
    """Reference logits of ``tokens`` (one sequence) at positions ``rows``:
    (len(rows), vocab) float32 on the host."""
    n = model.dims(config)
    key = _Frozen(tuple(sorted(n.items())))
    S = len(tokens)
    toks = np.zeros(-(-S // BUCKET) * BUCKET, np.int32)
    toks[:S] = tokens
    # the head runs on the rows padded to a multiple of BUCKET (the last
    # row repeated), so that one program serves every request's length
    at = np.full(-(-len(rows) // BUCKET) * BUCKET, rows[-1], np.int32)
    at[:len(rows)] = rows
    with jax.default_matmul_precision("highest"):
        x = jnp.take(weights["embed"]["table"], jnp.asarray(toks), axis=0)
        for i in range(n["L"]):
            x = _layer(x, model.layer(n, weights, i), n=key, mode=mode)
        out = _head(x[jnp.asarray(at)], weights["final_norm"]["scale"],
                    weights["lm_head"]["w"], n=key, mode=mode)
    return np.asarray(out[:len(rows)])
