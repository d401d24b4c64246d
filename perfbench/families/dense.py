"""The dense llama-family decoder (Llama, Qwen2): GQA attention with
optional q/k/v biases and a SwiGLU MLP in every layer.

A family module states five functions, which :mod:`perfbench.model`
calls for every configuration whose ``family`` names it: ``dims``,
``program_config``, ``_tree`` (the weight tree), ``layer`` and
``matmul_params``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dims(c: dict) -> dict:
    """Sizes every other module of the benchmark works from."""
    h = c["num_attention_heads"]
    return {"L": c["num_hidden_layers"], "d": c["hidden_size"], "H": h,
            "KV": c["num_key_value_heads"],
            "D": c.get("head_dim") or c["hidden_size"] // h,
            "F": c["intermediate_size"], "V": c["vocab_size"],
            "tied": bool(c["tie_word_embeddings"]),
            "bias": bool(c.get("qkv_bias", False)),
            "theta": float(c["rope_theta"]), "eps": float(c["rms_norm_eps"])}


def program_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    if c.get("rope_scaling") and "rope_scaling" not in c.get("departures", {}):
        raise ValueError("the serving program has no RoPE scaling; a "
                         "configuration that runs without it says so under "
                         "'departures'")
    n = dims(c)
    return ModelConfig(
        name=c["name"], family="dense", num_layers=n["L"], d_model=n["d"],
        num_heads=n["H"], num_kv_heads=n["KV"], head_dim=n["D"],
        d_ff=n["F"], vocab_size=n["V"], qkv_bias=n["bias"],
        rope_theta=n["theta"], norm_eps=n["eps"], tie_embeddings=n["tied"],
        dtype=c["served"]["compute_dtype"],
        param_dtype=c["served"]["param_dtype"],
        kv_cache_dtype=c["served"]["kv_cache_dtype"])


def _tree(n: dict, key):
    """The program's parameter tree (stacked layers, float32 masters, norm
    gains stored as ``1 + scale``), drawn from ``key``."""
    L, d, H, KV, D, F, V = (n[k] for k in ("L", "d", "H", "KV", "D", "F", "V"))
    ks = iter(jax.random.split(key, 16))

    def w(shape, fan_in):
        x = jax.random.truncated_normal(next(ks), -2.0, 2.0, shape)
        return x * (1.0 / np.sqrt(fan_in))

    def gain(shape):
        return jax.random.uniform(next(ks), shape, minval=-0.1, maxval=0.1)

    def small(shape):
        return 0.02 * jax.random.normal(next(ks), shape)

    lead = (L, 1)            # (layer groups, layers per group) as stacked
    attn = {"wq": {"w": w(lead + (d, H, D), d)},
            "wk": {"w": w(lead + (d, KV, D), d)},
            "wv": {"w": w(lead + (d, KV, D), d)},
            "wo": {"w": w(lead + (H * D, d), H * D)}}
    if n["bias"]:
        attn["wq"]["b"] = small(lead + (H, D))
        attn["wk"]["b"] = small(lead + (KV, D))
        attn["wv"]["b"] = small(lead + (KV, D))
    p = {"embed": {"table": small((V, d))},
         "final_norm": {"scale": gain((d,))},
         "layers": {"ln1": {"scale": gain(lead + (d,))},
                    "ln2": {"scale": gain(lead + (d,))},
                    "attn": attn,
                    "mlp": {"wi": {"w": w(lead + (d, F), d)},
                            "wg": {"w": w(lead + (d, F), d)},
                            "wo": {"w": w(lead + (F, d), F)}}}}
    if not n["tied"]:
        p["lm_head"] = {"w": small((d, V))}
    return jax.tree.map(lambda a: a.astype(jnp.float32), p)


def layer(weights, i: int) -> dict:
    """Layer ``i``'s weights as a flat dict (reference side)."""
    ly = weights["layers"]
    a, m = ly["attn"], ly["mlp"]
    out = {"ln1": ly["ln1"]["scale"][i, 0], "ln2": ly["ln2"]["scale"][i, 0],
           "wq": a["wq"]["w"][i, 0], "wk": a["wk"]["w"][i, 0],
           "wv": a["wv"]["w"][i, 0], "wo": a["wo"]["w"][i, 0],
           "wi": m["wi"]["w"][i, 0], "wg": m["wg"]["w"][i, 0],
           "wd": m["wo"]["w"][i, 0]}
    for k in ("wq", "wk", "wv"):
        if "b" in a[k]:
            out["b" + k[1]] = a[k]["b"][i, 0]
    return out


def matmul_params(n: dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, H, KV, D, F = n["d"], n["H"], n["KV"], n["D"], n["F"]
    return d * H * D + 2 * d * KV * D + H * D * d + 3 * d * F
