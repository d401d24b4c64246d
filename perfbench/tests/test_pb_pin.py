"""The dense configurations as they were when the harness knew one model
family: the weights, the program's ``ModelConfig``, the sizes and the
work counts.  Each constant was computed from the harness before
configurations named their family; a change that moves one of them
moves what the benchmark's existing cells measure."""
import hashlib
import json

import jax
import numpy as np
import pytest

from perfbench import model, work
from perfbench.tests import tiny

DECODE_CTX = [1, 2, 17, 300, 1025, 4095]
PREFILL_ROWS = [(0, 16), (16, 16), (100, 7), (5, 0), (2048, 64)]

# sha256 over each leaf's path, shape, dtype and bytes, in tree order
TINY_WEIGHTS = {
    0: "482c0c7388d9ec441dbb01038130490a46a10d86b3f1a80d8b45cb573601d7a0",
    3_000_000_019:
        "3d6df1a9a7211cb3e9a507ae640090b5271c4752ce47b73d1992fe6fddedee83",
}

# per configuration: the ModelConfig's arguments (every other field at
# its default), dims, model_flops / decode_attention / prefill_attention
# over DECODE_CTX and PREFILL_ROWS, and sha256 of the weight tree's
# (path, shape, dtype) list
PINNED = {
    "tiny-dense": {
        "program": dict(num_layers=2, d_model=64, num_heads=4,
                        num_kv_heads=2, head_dim=16, d_ff=128,
                        vocab_size=307, qkv_bias=True, rope_theta=10000.0,
                        norm_eps=1e-06, tie_embeddings=True),
        "dims": {"L": 2, "d": 64, "H": 4, "KV": 2, "D": 16, "F": 128,
                 "V": 307, "tied": True, "bias": True, "theta": 10000.0,
                 "eps": 1e-06},
        "flops": 88028544.0,
        "decode": (2785280.0, 1395712.0),
        "prefill": (68816896.0, 633088.0),
        "shapes": "2bf8e93022d2a88c2c572eff52d41f5b"
                  "8eae80c82223cde89410993612e040dc",
    },
    "qwen2-0.5b": {
        "program": dict(num_layers=24, d_model=896, num_heads=14,
                        num_kv_heads=2, head_dim=64, d_ff=4864,
                        vocab_size=151936, qkv_bias=True,
                        rope_theta=1000000.0, norm_eps=1e-06,
                        tie_embeddings=True),
        "dims": {"L": 24, "d": 896, "H": 14, "KV": 2, "D": 64, "F": 4864,
                 "V": 151936, "tied": True, "bias": True,
                 "theta": 1000000.0, "eps": 1e-06},
        "flops": 92485779456.0,
        "decode": (467927040.0, 67362816.0),
        "prefill": (11561238528.0, 36716544.0),
        "shapes": "d64dd9fdb6be9466e734610eef3c5b2d"
                  "9ea27de1bec78521781689843c7aa89d",
    },
    "deepseek-coder-33b-stage": {
        "program": dict(num_layers=3, d_model=7168, num_heads=56,
                        num_kv_heads=8, head_dim=128, d_ff=19200,
                        vocab_size=32256, qkv_bias=False,
                        rope_theta=100000.0, norm_eps=1e-06,
                        tie_embeddings=False),
        "dims": {"L": 3, "d": 7168, "H": 56, "KV": 8, "D": 128, "F": 19200,
                 "V": 32256, "tied": False, "bias": False,
                 "theta": 100000.0, "eps": 1e-06},
        "flops": 363018485760.0,
        "decode": (467927040.0, 67362816.0),
        "prefill": (11561238528.0, 36716544.0),
        "shapes": "31fa88d5be7604c9f376b1accd73e0b4"
                  "6069a6d197b3e2b40a3072a9ce7dd6e6",
    },
}


def _config(name):
    if name == tiny.CONFIG["name"]:
        return tiny.CONFIG
    return json.loads((tiny.BENCH / "configs" / f"{name}.json").read_text())


def _digest(tree):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str((a.shape, a.dtype.str)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", sorted(TINY_WEIGHTS))
def test_tiny_weights_bit_for_bit(seed):
    assert _digest(model.make_weights(tiny.CONFIG, seed)) == \
        TINY_WEIGHTS[seed]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_program_config_and_dims(name):
    from repro.configs.base import ModelConfig
    c, want = _config(name), PINNED[name]
    assert model.program_config(c) == ModelConfig(
        name=name, family="dense", dtype="bfloat16", param_dtype="float32",
        kv_cache_dtype="bfloat16", **want["program"])
    assert model.dims(c) == dict(want["dims"], family="dense")


@pytest.mark.parametrize("name", sorted(PINNED))
def test_work_counts(name):
    n, want = model.dims(_config(name)), PINNED[name]
    assert work.model_flops(n, decode_ctx=DECODE_CTX,
                            prefill_rows=PREFILL_ROWS,
                            logits_rows=3) == want["flops"]
    assert work.decode_attention(n, DECODE_CTX) == want["decode"]
    assert work.prefill_attention(n, PREFILL_ROWS) == want["prefill"]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_weight_tree_shapes(name):
    c = _config(name)
    s = jax.eval_shape(lambda: model.make_weights(c, 0))
    txt = json.dumps([[jax.tree_util.keystr(p), list(x.shape), x.dtype.name]
                      for p, x in jax.tree_util.tree_flatten_with_path(s)[0]])
    assert hashlib.sha256(txt.encode()).hexdigest() == PINNED[name]["shapes"]
