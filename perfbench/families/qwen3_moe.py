"""The Qwen3 sparse-expert decoder (``Qwen3MoeForCausalLM``): GQA
attention with per-head q/k RMSNorm before RoPE and no biases, and in
every layer a softmax router over all the layer's experts, top-k
renormalised over the k, into SwiGLU experts, no shared expert.

The configuration states the chip's share of a deployment that divides
each layer's experts over several chips: ``num_experts`` is the experts
held here (``first_expert`` onward), ``published["num_experts"]`` the
router's outputs.  The program's ``ModelConfig`` says the same
(``num_experts``, ``moe_router_width``, ``moe_first_expert``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BYTES = {"float32": 4, "bfloat16": 2}


def dims(c: dict) -> dict:
    """Sizes every other module of the benchmark works from: ``E`` held
    experts from ``e0`` of the router's ``R``, ``K`` a token, width
    ``F``; ``wbytes`` the bytes of a stored weight."""
    return {"L": c["num_hidden_layers"], "d": c["hidden_size"],
            "H": c["num_attention_heads"], "KV": c["num_key_value_heads"],
            "D": c["head_dim"], "F": c["moe_intermediate_size"],
            "E": c["num_experts"], "R": c["published"]["num_experts"],
            "e0": c["first_expert"], "K": c["num_experts_per_tok"],
            "V": c["vocab_size"], "tied": bool(c["tie_word_embeddings"]),
            "theta": float(c["rope_theta"]), "eps": float(c["rms_norm_eps"]),
            "wbytes": BYTES[c["served"]["param_dtype"]]}


def program_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    if c.get("rope_scaling") or c.get("attention_bias"):
        raise ValueError("the serving program has no RoPE scaling or "
                         "attention bias for this family")
    if not c["norm_topk_prob"] or c["mlp_only_layers"] or \
            c["decoder_sparse_step"] != 1:
        raise ValueError("the family serves renormalised top-k experts in "
                         "every layer")
    n = dims(c)
    return ModelConfig(
        name=c["name"], family="moe", num_layers=n["L"], d_model=n["d"],
        num_heads=n["H"], num_kv_heads=n["KV"], head_dim=n["D"],
        d_ff=n["F"], num_experts=n["E"], num_experts_per_tok=n["K"],
        moe_router_width=n["R"], moe_first_expert=n["e0"], qk_norm=True,
        vocab_size=n["V"], rope_theta=n["theta"], norm_eps=n["eps"],
        tie_embeddings=n["tied"], dtype=c["served"]["compute_dtype"],
        param_dtype=c["served"]["param_dtype"],
        kv_cache_dtype=c["served"]["kv_cache_dtype"])


# The router's draw, in units of 1/sqrt(fan_in).  At 1 its 128 logits
# spread about 0.9 and the softmax is near uniform: each of a token's 8
# experts gets about an eighth of the layer's output, so a near tie at
# the 8th place, which bfloat16 rounding of the residual stream flips,
# swaps an eighth of it, and the served tokens' gaps reach those of the
# float8 control.  At 3 (logits spread about 2.6) the kept gates fall
# off from the first pick, as a trained router's are assumed to, and a
# flip at the 8th place moves the output by its small gate only.
ROUTER_SCALE = 3.0


def _tree(n: dict, key):
    """The program's parameter tree (stacked layers, float32 masters, norm
    gains stored as ``1 + scale``), drawn from ``key``."""
    L, d, H, KV, D, F, E, R, V = (n[k] for k in
                                  ("L", "d", "H", "KV", "D", "F", "E", "R",
                                   "V"))
    ks = iter(jax.random.split(key, 16))

    def w(shape, fan_in):
        x = jax.random.truncated_normal(next(ks), -2.0, 2.0, shape)
        return x * (1.0 / np.sqrt(fan_in))

    def gain(shape):
        return jax.random.uniform(next(ks), shape, minval=-0.1, maxval=0.1)

    def small(shape):
        return 0.02 * jax.random.normal(next(ks), shape)

    lead = (L, 1)            # (layer groups, layers per group) as stacked
    p = {"embed": {"table": small((V, d))},
         "final_norm": {"scale": gain((d,))},
         "layers": {"ln1": {"scale": gain(lead + (d,))},
                    "ln2": {"scale": gain(lead + (d,))},
                    "attn": {"wq": {"w": w(lead + (d, H, D), d)},
                             "wk": {"w": w(lead + (d, KV, D), d)},
                             "wv": {"w": w(lead + (d, KV, D), d)},
                             "wo": {"w": w(lead + (H * D, d), H * D)},
                             "q_norm": {"scale": gain(lead + (D,))},
                             "k_norm": {"scale": gain(lead + (D,))}},
                    "moe": {"router": {"w": ROUTER_SCALE
                                       * w(lead + (d, R), d)},
                            "wi": w(lead + (E, d, F), d),
                            "wg": w(lead + (E, d, F), d),
                            "wo": w(lead + (E, F, d), F)}}}
    if not n["tied"]:
        p["lm_head"] = {"w": small((d, V))}
    return jax.tree.map(lambda a: a.astype(jnp.float32), p)


def layer(weights, i: int) -> dict:
    """Layer ``i``'s weights as a flat dict (reference side)."""
    ly = weights["layers"]
    a, m = ly["attn"], ly["moe"]
    return {"ln1": ly["ln1"]["scale"][i, 0], "ln2": ly["ln2"]["scale"][i, 0],
            "wq": a["wq"]["w"][i, 0], "wk": a["wk"]["w"][i, 0],
            "wv": a["wv"]["w"][i, 0], "wo": a["wo"]["w"][i, 0],
            "q_norm": a["q_norm"]["scale"][i, 0],
            "k_norm": a["k_norm"]["scale"][i, 0],
            "router": m["router"]["w"][i, 0], "wi": m["wi"][i, 0],
            "wg": m["wg"][i, 0], "wd": m["wo"][i, 0]}


def expert_params(n: dict) -> int:
    """Weights of one SwiGLU expert: gate, up and down."""
    return 3 * n["d"] * n["F"]


def matmul_params(n: dict) -> int:
    """Weights one token multiplies through in one layer: attention, the
    router, and the ``K * E / R`` held experts a token visits on average
    (the exact routed rows are counted by the program)."""
    d, H, KV, D = n["d"], n["H"], n["KV"], n["D"]
    attn = d * H * D + 2 * d * KV * D + H * D * d
    return attn + d * n["R"] + n["K"] * n["E"] * expert_params(n) // n["R"]
