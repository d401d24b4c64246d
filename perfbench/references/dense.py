"""Plain float32 reference of a dense llama-family decoder (Llama, Qwen2).

Written from the published layer equations, independent of the serving
program: token embedding; per layer, RMSNorm, q/k/v projections (with
biases where the configuration has them), rotary embedding of q and k
(rotate-half form, frequencies ``theta ** (-2i / D)``), grouped-query
causal softmax attention scaled by ``1/sqrt(D)``, the output projection
and a residual; RMSNorm, the SwiGLU MLP ``down(silu(gate(x)) * up(x))``
and a residual; a final RMSNorm and the tied or separate output head.

Every matmul runs at ``Precision.HIGHEST``: on a TPU a float32 matmul is
otherwise computed from bfloat16 passes.  ``mode="fp8"`` is the control:
every matmul's operands are rounded to float8 (e4m3, scaled per tensor
by its largest magnitude) before a float32 product, the precision one
step below the bfloat16 the configurations state.

One sequence at a time, one layer at a time, attention in blocks of
query rows, so that the reference fits beside the weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import model

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
BUCKET = 512         # sequences are padded to a multiple of this (causal:
                     # padding after a position never changes it)


def _q(x, mode):
    if mode != "fp8":
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, spec, mode):
    return jnp.einsum(spec, _q(a, mode), _q(b, mode), precision=HI,
                      preferred_element_type=jnp.float32)


def _rms(x, gain, eps):
    v = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(v + eps) * (1.0 + gain)


def _rope(x, pos, theta):
    D = x.shape[-1]
    half = D // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) * 2 / D))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@functools.partial(jax.jit, static_argnames=("n", "mode"))
def _layer(x, w, *, n, mode):
    S = x.shape[0]
    H, KV, D = n["H"], n["KV"], n["D"]
    G = H // KV
    pos = jnp.arange(S)
    h = _rms(x, w["ln1"], n["eps"])
    q = _mm(h, w["wq"], "sd,dhk->shk", mode)
    k = _mm(h, w["wk"], "sd,dhk->shk", mode)
    v = _mm(h, w["wv"], "sd,dhk->shk", mode)
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
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
    g = _mm(h, w["wg"], "sd,df->sf", mode)
    u = _mm(h, w["wi"], "sd,df->sf", mode)
    return x + _mm(jax.nn.silu(g) * u, w["wd"], "sf,fd->sd", mode)


@functools.partial(jax.jit, static_argnames=("n", "mode"))
def _head(x, final_gain, head_w, tied_table, *, n, mode):
    h = _rms(x, final_gain, n["eps"])
    if n["tied"]:
        return _mm(h, tied_table, "sd,vd->sv", mode)
    return _mm(h, head_w, "sd,dv->sv", mode)


def logits(weights, config: dict, tokens: np.ndarray, rows: np.ndarray,
           mode: str = "f32") -> np.ndarray:
    """Reference logits of ``tokens`` (one sequence) at positions ``rows``:
    (len(rows), vocab) float32 on the host."""
    n = model.dims(config)
    n_key = tuple(sorted(n.items()))
    S = len(tokens)
    Sp = -(-S // BUCKET) * BUCKET
    toks = np.zeros(Sp, np.int32)
    toks[:S] = tokens
    x = jnp.take(weights["embed"]["table"], jnp.asarray(toks), axis=0)
    for i in range(n["L"]):
        x = _layer(x, model.layer(n, weights, i), n=_Frozen(n_key), mode=mode)
    x = x[jnp.asarray(rows)]
    head_w = weights.get("lm_head", {}).get("w")
    out = _head(x, weights["final_norm"]["scale"], head_w,
                weights["embed"]["table"], n=_Frozen(n_key), mode=mode)
    return np.asarray(out)


class _Frozen(dict):
    """The sizes as a hashable static argument."""

    def __init__(self, items):
        super().__init__(items)
        self._key = tuple(items)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Frozen) and self._key == other._key
