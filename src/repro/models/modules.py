"""Low-level parameterized modules (pure-functional, pytree params).

Params are nested dicts of jnp arrays.  Every ``init_*`` takes a PRNG key
and returns a params pytree; every ``apply``-style function is pure.
Compute happens in ``cfg.dtype`` (bf16 by default); params are made in
``cfg.param_dtype`` (f32 master copies).  A serving engine holds
``cfg.dtype`` copies of the weights that every use casts to it
(``transformer.serving_params``), so those casts are no-ops there.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def truncated_normal_init(key, shape, stddev, dtype=jnp.float32):
    return stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# Linear
# ---------------------------------------------------------------------------

def init_linear(key, d_in: int, d_out, bias: bool = False, dtype=jnp.float32,
                stddev: Optional[float] = None):
    """d_out may be an int or a tuple (e.g. (heads, head_dim))."""
    out_shape = (d_out,) if isinstance(d_out, int) else tuple(d_out)
    if stddev is None:
        stddev = 1.0 / np.sqrt(d_in)
    p = {"w": truncated_normal_init(key, (d_in, *out_shape), stddev, dtype)}
    if bias:
        p["b"] = jnp.zeros(out_shape, dtype)
    return p


def linear(p, x, dtype=None):
    w = p["w"]
    if dtype is not None:
        w = w.astype(dtype)
        x = x.astype(dtype)
    n_out = w.ndim - 1
    y = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=x.dtype)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def linear_nd_in(p, x, n_in: int, dtype=None):
    """Linear contracting the last ``n_in`` dims of x (e.g. (heads, head_dim))."""
    w = p["w"]
    if dtype is not None:
        w = w.astype(dtype)
        x = x.astype(dtype)
    axes_x = tuple(range(x.ndim - n_in, x.ndim))
    axes_w = tuple(range(n_in))
    y = jax.lax.dot_general(x, w, ((axes_x, axes_w), ((), ())),
                            preferred_element_type=x.dtype)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype=jnp.float32):
    return {"scale": jnp.zeros((d,), dtype)}        # (1 + scale) parameterization


def rmsnorm(p, x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + p["scale"].astype(jnp.float32))).astype(dt)


def init_layernorm(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p, x, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d: int, dtype=jnp.float32):
    # GPT-style small init: keeps tied-unembedding logits O(1) at init
    return {"table": truncated_normal_init(key, (vocab, d), 0.02, dtype)}


def embed(p, tokens, dtype=None):
    t = p["table"]
    if dtype is not None:
        t = t.astype(dtype)
    return jnp.take(t, tokens, axis=0)


def unembed(p, x):
    return jax.lax.dot_general(
        x, p["table"].astype(x.dtype), (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Activations / misc
# ---------------------------------------------------------------------------

def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": lambda x: jax.nn.gelu(x, approximate=True),
            "relu": jax.nn.relu}[name]


def softcap(x, cap: Optional[float]):
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# Gated MLP (llama-style; used by all dense archs and as the expert FFN)
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": init_linear(k1, d_model, d_ff, dtype=dtype),
        "wg": init_linear(k2, d_model, d_ff, dtype=dtype),
        "wo": init_linear(k3, d_ff, d_model, dtype=dtype),
    }


def mlp(p, x, act: str = "silu", dtype=None):
    a = activation(act)
    h = a(linear(p["wg"], x, dtype)) * linear(p["wi"], x, dtype)
    return linear(p["wo"], h, dtype)
