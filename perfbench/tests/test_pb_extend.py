"""A later change adds a configuration, a traffic mix, a per-layer
metric, a model family and a kernel as new files only: the harness finds
them by the names in BENCHMARK.json and in the configuration, and no
file the benchmark already has is edited."""
import hashlib
import json
import shutil
import subprocess
import sys

import pytest

from perfbench.tests import tiny

PROBE = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
from perfbench import harness, traffic
cell = harness.load_cell("tiny-dense.tiny-chat")
names = [m["name"] for m in cell.metrics("per_layer")]
read = harness.reader("prompt_tokens_per_request")
class C:  # the reader sees a run's context
    pass
ctx = C(); ctx.stats0 = {"totals": {"prefix_cache": {"prompt_tokens": 10}, "requests_completed": 1}}
ctx.stats1 = {"totals": {"prefix_cache": {"prompt_tokens": 70}, "requests_completed": 4}}
gen = traffic.make(cell.mix, cell.config["vocab_size"], 1, 30)
print(json.dumps({"config": cell.config["name"], "mix": cell.mix["name"],
                  "metrics": names, "value": read(ctx),
                  "due": len(gen.due_until(10.0))}))
"""

METRIC = '''"""Prompt tokens admitted per completed request in the window."""
from perfbench import readers


def read(ctx):
    n = readers.counter_delta(ctx, "requests_completed")
    return readers.counter_delta(ctx, "prefix_cache", "prompt_tokens") / n
'''


def _digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()
                                                    ).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def _copy(tmp_path):
    bench = tmp_path / "perfbench"
    shutil.copytree(tiny.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    return bench, _digest(bench)


def _add_cell(tmp_path, config, mix, metric=None):
    """Write ``config`` and ``mix`` as new files, and a BENCHMARK.json
    that adds their cell and, in it, the per-layer ``metric``."""
    bench, name = tmp_path / "perfbench", config["name"]
    wl = f"{name}.{mix['name']}"
    (bench / "configs" / f"{name}.json").write_text(json.dumps(config))
    (bench / "mixes" / f"{mix['name']}.json").write_text(json.dumps(mix))
    spec = json.loads((tiny.BENCH.parent / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": name, "source": "test",
                            "file": f"perfbench/configs/{name}.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": wl, "config": name,
                              "traffic": mix["name"], "chips": 1,
                              "why": "test"})
    if metric:
        spec["per_layer"].append(dict(metric, workloads=[wl]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))


def _run(tmp_path, code):
    return subprocess.run([sys.executable, "-c", code, str(tmp_path),
                           str(tiny.BENCH.parent / "src")],
                          capture_output=True, text=True, timeout=120,
                          env={"JAX_PLATFORMS": "cpu",
                               "PATH": "/usr/bin:/bin"})


def _probe(tmp_path, code):
    p = _run(tmp_path, code)
    assert p.returncode == 0, p.stderr
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_add_config_mix_and_metric_as_new_files(tmp_path):
    bench, before = _copy(tmp_path)
    (bench / "metrics" / "prompt_tokens_per_request.py").write_text(METRIC)
    _add_cell(tmp_path, tiny.CONFIG, tiny.CHAT, {
        "name": "prompt_tokens_per_request", "unit": "tokens",
        "better": "lower", "source": "program_counter",
        "layer": "scheduler", "moves": "itl_p95_ms"})
    after = _digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before
    out = _probe(tmp_path, PROBE)
    assert out["config"] == "tiny-dense" and out["mix"] == "tiny-chat"
    assert out["metrics"] == ["prompt_tokens_per_request"]
    assert out["value"] == 20.0
    assert out["due"] > 0


# a second model family, sparse experts at smoke widths, as the program
# builds it (``family="moe"``): every layer routes each token to 2 of 4
# SwiGLU experts
FAMILY = '''"""Sparse-expert decoder: GQA attention and a routed SwiGLU
expert layer in every layer."""
import jax
import jax.numpy as jnp
import numpy as np


def dims(c):
    h = c["num_attention_heads"]
    return {"L": c["num_hidden_layers"], "d": c["hidden_size"], "H": h,
            "KV": c["num_key_value_heads"], "D": c["head_dim"],
            "F": c["moe_intermediate_size"], "E": c["num_experts"],
            "K": c["num_experts_per_tok"], "V": c["vocab_size"],
            "tied": bool(c["tie_word_embeddings"]),
            "theta": float(c["rope_theta"]), "eps": float(c["rms_norm_eps"])}


def program_config(c):
    from repro.configs.base import ModelConfig
    n = dims(c)
    return ModelConfig(
        name=c["name"], family="moe", num_layers=n["L"], d_model=n["d"],
        num_heads=n["H"], num_kv_heads=n["KV"], head_dim=n["D"],
        d_ff=n["F"], num_experts=n["E"], num_experts_per_tok=n["K"],
        vocab_size=n["V"], rope_theta=n["theta"], norm_eps=n["eps"],
        tie_embeddings=n["tied"], dtype=c["served"]["compute_dtype"],
        param_dtype=c["served"]["param_dtype"],
        kv_cache_dtype=c["served"]["kv_cache_dtype"])


def _tree(n, key):
    L, d, H, KV, D, F, E, V = (n[k] for k in "L d H KV D F E V".split())
    ks = iter(jax.random.split(key, 16))

    def w(shape, fan_in):
        return (jax.random.truncated_normal(next(ks), -2.0, 2.0, shape)
                / np.sqrt(fan_in))

    lead = (L, 1)
    p = {"embed": {"table": 0.02 * jax.random.normal(next(ks), (V, d))},
         "final_norm": {"scale": jnp.zeros((d,))},
         "layers": {"ln1": {"scale": jnp.zeros(lead + (d,))},
                    "ln2": {"scale": jnp.zeros(lead + (d,))},
                    "attn": {"wq": {"w": w(lead + (d, H, D), d)},
                             "wk": {"w": w(lead + (d, KV, D), d)},
                             "wv": {"w": w(lead + (d, KV, D), d)},
                             "wo": {"w": w(lead + (H * D, d), H * D)}},
                    "moe": {"router": {"w": w(lead + (d, E), d)},
                            "wi": w(lead + (E, d, F), d),
                            "wg": w(lead + (E, d, F), d),
                            "wo": w(lead + (E, F, d), F)}}}
    if not n["tied"]:
        p["lm_head"] = {"w": 0.02 * jax.random.normal(next(ks), (d, V))}
    return jax.tree.map(lambda a: a.astype(jnp.float32), p)


def layer(weights, i):
    ly = weights["layers"]
    a, m = ly["attn"], ly["moe"]
    return {"ln1": ly["ln1"]["scale"][i, 0], "ln2": ly["ln2"]["scale"][i, 0],
            "wq": a["wq"]["w"][i, 0], "wk": a["wk"]["w"][i, 0],
            "wv": a["wv"]["w"][i, 0], "wo": a["wo"]["w"][i, 0],
            "router": m["router"]["w"][i, 0], "wi": m["wi"][i, 0],
            "wg": m["wg"][i, 0], "wd": m["wo"][i, 0]}


def matmul_params(n):
    d, H, KV, D, F, E, K = (n[k] for k in "d H KV D F E K".split())
    return d * H * D + 2 * d * KV * D + H * D * d + d * E + K * 3 * d * F
'''

MOE_CONFIG = {
    "name": "tiny-moe", "family": "tiny-moe",
    "hidden_size": 64, "moe_intermediate_size": 32, "num_experts": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 307, "rms_norm_eps": 1e-6, "rope_theta": 1000000.0,
    "tie_word_embeddings": False,
    "served": {"compute_dtype": "bfloat16", "param_dtype": "float32",
               "kv_cache_dtype": "bfloat16"},
}

KERNEL_METRIC = '''"""Device ms per call of the tiny kernel over the traced window."""
from perfbench import trace as T


def read(ctx):
    if ctx.trace is None:
        return None
    t, n = T.op_time(ctx.trace, T.NAMES["kernels"]["tiny_kernel"])
    return 1e3 * t / n if n else None
'''

MOE_PROBE = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import jax
from perfbench import harness, model, trace, work
from repro.models import transformer as T
cell = harness.load_cell("tiny-moe.tiny-batch")
c = cell.config
n = model.dims(c)
want = jax.eval_shape(lambda: T.init_params(model.program_config(c),
                                            jax.random.PRNGKey(0)))
leaves = lambda t: [(jax.tree_util.keystr(p), x.shape, str(x.dtype))
                    for p, x in jax.tree_util.tree_flatten_with_path(t)[0]]
w = model.make_weights(c, 2**33 + 5)
again = model.make_weights(c, 2**33 + 5)
tr = trace.Trace(ops={"/device:TPU:0": [
    ("%tiny_kernel.3 = bf16[8]{0} custom-call()", 1.0, 1.004),
    ("%fusion.1 = bf16[8]{0} fusion()", 1.004, 1.5),
    ("%tiny_kernel.7 = bf16[8]{0} custom-call()", 2.0, 2.002)]})
class C:
    pass
ctx = C(); ctx.trace = tr
print(json.dumps({
    "config": c["name"], "mix": cell.mix["name"], "family": n["family"],
    "metrics": [m["name"] for m in cell.metrics("per_layer")],
    "program_family": model.program_config(c).family,
    "experts": model.program_config(c).num_experts,
    "same_tree": (jax.tree.structure(w) == jax.tree.structure(want)
                  and leaves(w) == leaves(want)),
    "same_seed_same_bits": all(
        bool((a == b).all()) for a, b in zip(jax.tree.leaves(w),
                                              jax.tree.leaves(again))),
    "matmul_params": work.matmul_params(n),
    "flops": work.model_flops(n, decode_ctx=[8], prefill_rows=[(0, 4)]),
    "decode_attention": work.decode_attention(n, [8])[0],
    "prefill_attention": work.prefill_attention(n, [(0, 4)])[0],
    "kernels": trace.NAMES["kernels"],
    "kernel_s": trace.kernel_seconds(tr, "tiny_kernel"),
    "kernel_ms": harness.reader("tiny_kernel_ms")(ctx)}))
"""


def test_add_model_family_and_kernel_as_new_files(tmp_path):
    """A configuration of another family, with its mix, metric and kernel,
    arrives as new files: the harness loads it, builds the program's
    config and weights as the program lays them out, counts the family's
    own work (2 experts a token, not 4) and reads the new kernel."""
    bench, before = _copy(tmp_path)
    (bench / "families" / "tiny-moe.py").write_text(FAMILY)
    (bench / "metrics" / "tiny_kernel_ms.py").write_text(KERNEL_METRIC)
    (bench / "kernels" / "tiny_kernel.json").write_text('["tiny_kernel"]')
    _add_cell(tmp_path, MOE_CONFIG, tiny.BATCH, {
        "name": "tiny_kernel_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "output_tok_s"})
    after = _digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before
    out = _probe(tmp_path, MOE_PROBE)
    assert out["config"] == "tiny-moe" and out["mix"] == "tiny-batch"
    assert out["family"] == "tiny-moe" and out["program_family"] == "moe"
    assert out["experts"] == 4
    assert out["metrics"] == ["tiny_kernel_ms"]
    assert out["same_tree"] and out["same_seed_same_bits"]
    d, H, KV, D, F, E = 64, 4, 2, 16, 32, 4
    attn = d * H * D + 2 * d * KV * D + H * D * d
    assert out["matmul_params"] == attn + d * E + 2 * 3 * d * F
    L, V = 2, 307
    assert out["flops"] == pytest.approx(
        2 * L * out["matmul_params"] * (1 + 4) + 2 * d * V * 1
        + out["decode_attention"] + out["prefill_attention"])
    assert out["kernels"] == {"paged_decode": ["paged_decode_attention"],
                              "paged_prefill": ["paged_prefill_attention"],
                              "tiny_kernel": ["tiny_kernel"]}
    assert out["kernel_s"] == pytest.approx(0.006)
    assert out["kernel_ms"] == pytest.approx(3.0)


def test_configuration_without_family_is_refused(tmp_path):
    """A configuration file with no ``family`` is an error that names the
    file, not a silent default."""
    _copy(tmp_path)
    _add_cell(tmp_path, {k: v for k, v in tiny.CONFIG.items()
                         if k != "family"}, tiny.CHAT)
    p = _run(tmp_path, "import sys; sys.path[:0] = sys.argv[1:3]; "
             "from perfbench import harness; "
             "harness.load_cell('tiny-dense.tiny-chat')")
    assert p.returncode != 0
    assert "perfbench/configs/tiny-dense.json" in p.stderr
    assert "family" in p.stderr
