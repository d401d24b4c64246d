"""Reproduce the §Perf hillclimb measurements (EXPERIMENTS.md).

Re-lowers every (baseline, iteration) configuration of the three
hillclimbed pairs and prints the roofline terms, so the §Perf tables are
regenerable from source:

  PYTHONPATH=src python -m benchmarks.hillclimb [--pair A|B|C|A3]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_PROG = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp
from repro import optim
from repro.configs import get_config
from repro.configs.base import SHAPES
from repro.launch.mesh import device_peaks, make_production_mesh
from repro.launch.dryrun import (_compile_costs, _group_counts,
                                 collective_bytes, collective_bytes_by_scope)
from repro.distributed import stepfn

PEAKS = device_peaks("TPU v5 lite")     # the production mesh's chips

def terms(cfg, shape_name, strategy, **step_kw):
    shape = SHAPES[shape_name]
    mesh = make_production_mesh()
    G, cfg1, cfg2 = _group_counts(cfg)
    out = []
    for c in (cfg1, cfg2):
        c = c.with_(scan_layers=False, attn_q_chunk=0)
        if step_kw:
            jitted, structs, _ = stepfn.make_train_step(
                c, optim.adamw(1e-4), mesh, strategy, shape, **step_kw)
        else:
            jitted, structs = stepfn.make_step_for_shape(c, mesh, strategy, shape)
        with mesh, jax.transfer_guard("disallow"):
            comp = jitted.lower(*structs).compile()
        cost = comp.cost_analysis()
        out.append((float(cost.get("flops", 0)),
                    float(cost.get("bytes accessed", 0)),
                    float(sum(collective_bytes(comp.as_text()).values()))))
    ex = lambda i: out[0][i] + (G - 1) * (out[1][i] - out[0][i])
    return {"compute_ms": ex(0)/PEAKS["flops_bf16"]*1e3,
            "memory_ms": ex(1)/PEAKS["hbm_bytes_per_s"]*1e3,
            "collective_ms": ex(2)/PEAKS["ici_bytes_per_s_per_link"]*1e3}

def emit(pair, name, t):
    print("ROW " + json.dumps({"pair": pair, "iter": name, **t}), flush=True)

pair = os.environ.get("HILLCLIMB_PAIR", "all")

if pair in ("A", "all"):
    q = get_config("qwen2-0.5b")
    emit("A", "A0 pure DP", terms(q, "train_4k", "dp"))
    emit("A", "A1 dp_tp (refuted)", terms(q, "train_4k", "dp_tp"))
    emit("A", "A2 DP + chunked CE",
         terms(q, "train_4k", "dp", loss_variant="chunked_ce"))

if pair in ("B", "all"):
    d = get_config("dbrx-132b")
    emit("B", "B0 per-seq groups",
         terms(d.with_(moe_group_size=1), "decode_32k", "fsdp_tp"))
    emit("B", "B1 adaptive groups", terms(d, "decode_32k", "fsdp_tp"))
    emit("B", "B2 groups of 8 (refuted)",
         terms(d.with_(moe_group_size=8), "decode_32k", "fsdp_tp"))
    emit("B", "B3 + int8 KV cache",
         terms(d.with_(kv_cache_dtype="int8"), "decode_32k", "fsdp_tp"))

if pair in ("C", "all"):
    m = get_config("qwen3-moe-30b-a3b")
    emit("C", "C0 baseline", terms(m, "train_4k", "fsdp_tp"))
    emit("C", "C1 cf=1.05",
         terms(m.with_(moe_capacity_factor=1.05), "train_4k", "fsdp_tp"))
    emit("C", "C2 remat=dots",
         terms(m.with_(remat_policy="dots"), "train_4k", "fsdp_tp"))
    emit("C", "C3 buffer shard (refuted)",
         terms(m.with_(remat_policy="dots", moe_buffer_shard="model"),
               "train_4k", "fsdp_tp"))

if pair in ("A3", "all"):
    # multi-pod hierarchical allreduce: inter-pod bytes, flat vs hier
    from repro.models import transformer as T
    from repro.core import hvd
    cfg = get_config("qwen2-0.5b")
    mesh = make_production_mesh(multi_pod=True)
    opt = optim.rmsprop(1e-3)
    loss_fn = lambda p, b: T.lm_loss(p, cfg, b)
    key = jax.random.PRNGKey(0)
    p_s = jax.eval_shape(lambda k: T.init_params(cfg, k), key)
    s_s = jax.eval_shape(opt.init, p_s)
    b_s = {"tokens": jax.ShapeDtypeStruct((512, 2048), jnp.int32),
           "labels": jax.ShapeDtypeStruct((512, 2048), jnp.int32)}
    for name, hier in [("A3 flat allreduce", False),
                       ("A3 hierarchical", True)]:
        step = hvd.make_train_step(loss_fn, opt, mesh,
                                   axes=("pod", "data", "model"),
                                   hierarchical=hier, donate=False)
        with mesh:
            comp = step.lower(p_s, s_s, b_s).compile()
        scope = collective_bytes_by_scope(comp.as_text(), pod_size=256)
        print("ROW " + json.dumps(
            {"pair": "A3", "iter": name,
             "intra_pod_GB": scope["intra_pod"]/1e9,
             "inter_pod_GB": scope["inter_pod"]/1e9}), flush=True)
"""


def run(pair: str = "all"):
    env = dict(os.environ, PYTHONPATH="src", HILLCLIMB_PAIR=pair)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _PROG], capture_output=True,
                       text=True, env=env)
    rows = [json.loads(l[4:]) for l in r.stdout.splitlines()
            if l.startswith("ROW ")]
    if r.returncode != 0 and not rows:
        raise RuntimeError(r.stderr[-2000:])
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", default="all", choices=["A", "B", "C", "A3",
                                                      "all"])
    args = ap.parse_args()
    for row in run(args.pair):
        print(row)


if __name__ == "__main__":
    main()
