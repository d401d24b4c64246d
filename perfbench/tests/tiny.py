"""A tiny cell for CPU tests: the harness's real path at smoke widths."""
import copy
import json
from pathlib import Path

from perfbench import harness

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent

CONFIG = {
    "name": "tiny-dense", "family": "dense", "reference": "dense",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 307, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": True, "qkv_bias": True,
    "served": {"compute_dtype": "bfloat16", "param_dtype": "float32",
               "kv_cache_dtype": "bfloat16"},
}

CHAT = {
    "name": "tiny-chat",
    "engine": {"max_seq_len": 256, "max_slots": 4, "paged": True,
               "num_blocks": None, "prefill_batch": 2,
               "prefix_cache_blocks": 32},
    "scheduler": {"prefill_token_budget": 64},
    "traffic": {"kind": "sessions", "shape_seed": 0, "session_rate": 2.0,
                "system_prompts": {"count": 2, "tokens": 40, "zipf_s": 1.0},
                "turns": {"mean": 2, "cap": 3},
                "user_tokens": {"median": 8, "sigma": 0.5, "lo": 2, "hi": 20},
                "max_new_tokens": {"median": 5, "sigma": 0.5, "lo": 2,
                                   "hi": 10},
                "think_s": 0.2},
    "warmup_s": 1.0, "first_token_wait_s": 20, "trace_s": 1.0,
    "check": {"requests": 3, "tokens": 8},
}

BATCH = {
    "name": "tiny-batch",
    "engine": {"max_seq_len": 256, "max_slots": 4, "paged": True,
               "num_blocks": None, "prefill_batch": 2,
               "prefix_cache_blocks": 32},
    "scheduler": {"prefill_token_budget": None},
    "traffic": {"kind": "outstanding", "shape_seed": 0, "outstanding": 6,
                "pool": 64,
                "contexts": {"count": 3, "lo": 20, "hi": 50, "zipf_s": 1.1},
                "suffix_tokens": {"median": 10, "sigma": 0.5, "lo": 3,
                                  "hi": 30},
                "max_new_tokens": {"median": 5, "sigma": 0.4, "lo": 2,
                                   "hi": 8}},
    "warmup_s": 1.0, "first_token_wait_s": 0, "trace_s": 1.0,
    "check": {"requests": 3, "tokens": 8},
}


def cell(mix=CHAT, config=CONFIG, workload="tiny-dense.tiny-chat"):
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec = copy.deepcopy(spec)
    wl = {"name": workload, "config": config["name"],
          "traffic": mix["name"], "chips": 1, "why": "test"}
    spec["workloads"].append(wl)
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    return harness.Cell(spec, wl, copy.deepcopy(config), copy.deepcopy(mix))


V5E = harness.load_json(BENCH / "peaks.json")["TPU v5 lite"]

# the tiny cells' logit-gap limit: sound runs read 0.0 at these widths
# (bfloat16 rounding never reorders the top logits of a 2-layer model),
# the float8 control about 0.07
LIMIT = 0.02
