"""Roofline analysis (deliverable g): three terms per (arch x shape), from
the compiled dry-run artifacts on the single-pod 16x16 mesh.

  compute term    = HLO_FLOPs_per_device / 197 TFLOP/s   (bf16 MXU peak)
  memory term     = HLO_bytes_per_device / 819 GB/s      (HBM)
  collective term = collective_bytes_per_device / 50 GB/s (ICI link)

FLOPs/bytes come from ``cost_analysis()`` of the UNROLLED G=1/G=2 programs
extrapolated linearly in depth (exact for homogeneous layers — XLA counts a
while-loop body once; see launch/dryrun.py); collective bytes are parsed
from the compiled HLO text.  MODEL_FLOPS = 6·N·D (train) / 2·N_active·D
(inference) catches remat/dispatch overhead in the ratio column.

Writes results/roofline.jsonl and prints the EXPERIMENTS.md table.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import Dict, List, Optional

from repro.launch.mesh import device_peaks

# the dry-run meshes describe TPU v5e chips
_PEAKS = device_peaks("TPU v5 lite")


def model_flops_per_device(arch: str, shape_name: str, chips: int = 256) -> float:
    """Analytic useful-FLOPs per device for the MODEL_FLOPS/HLO_FLOPs ratio."""
    from repro.configs import get_config
    from repro.configs.base import SHAPES
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        total = 6 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        total = 2 * n_active * tokens
    else:  # decode: one token per sequence
        total = 2 * n_active * shape.global_batch
    return total / chips


def terms(rec: Dict) -> Dict:
    f, b, cb = rec["flops"], rec["bytes_accessed"], rec["collective_bytes_total"]
    t_c = f / _PEAKS["flops_bf16"]
    t_m = b / _PEAKS["hbm_bytes_per_s"]
    t_x = cb / _PEAKS["ici_bytes_per_s_per_link"]
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x),
              key=lambda kv: kv[1])[0]
    mf = model_flops_per_device(rec["arch"], rec["shape"], rec["chips"])
    advice = {
        "compute": "compute-bound: good — push MXU utilization via kernel "
                   "block tuning / fewer rematerialized FLOPs",
        "memory": "HBM-bound: fuse elementwise chains (Pallas rmsnorm), "
                  "reuse KV/cache tiles, bf16-ify residuals",
        "collective": "ICI-bound: reshard (bigger per-shard blocks), "
                      "hierarchical pod-aware allreduce, overlap "
                      "collectives with compute",
    }[dom]
    return {
        "arch": rec["arch"], "shape": rec["shape"],
        "strategy": rec.get("strategy"),
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
        "dominant": dom,
        "model_flops_per_dev": mf,
        "useful_flops_ratio": mf / f if f else 0.0,
        "advice": advice,
        "collective_breakdown": rec.get("collective_bytes", {}),
    }


def fmt_row(t: Dict) -> str:
    return (f"| {t['arch']} | {t['shape']} | {t['strategy']} "
            f"| {t['compute_s']*1e3:9.3f} | {t['memory_s']*1e3:9.3f} "
            f"| {t['collective_s']*1e3:9.3f} | {t['dominant']:10s} "
            f"| {t['useful_flops_ratio']:5.2f} |")


def run_sweep(out_path: str, pairs: Optional[List] = None) -> List[Dict]:
    """Run roofline_pair for every (arch, shape) in a 512-device subprocess
    (one process for the whole sweep)."""
    prog = """
import os, json, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
from repro.launch.dryrun import roofline_pair
from repro.configs import ARCHS
from repro.configs.base import SHAPES
pairs = json.loads(sys.argv[1]) if len(sys.argv) > 1 else \
    [(a, s) for a in ARCHS for s in SHAPES]
for a, s in pairs:
    try:
        rec = roofline_pair(a, s)
    except Exception as e:
        import traceback; traceback.print_exc()
        rec = {"arch": a, "shape": s, "status": "fail",
               "error": f"{type(e).__name__}: {e}"}
    print("REC " + json.dumps(rec), flush=True)
"""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    args = [sys.executable, "-c", prog]
    if pairs:
        args.append(json.dumps(pairs))
    r = subprocess.run(args, capture_output=True, text=True, env=env)
    recs = [json.loads(l[4:]) for l in r.stdout.splitlines()
            if l.startswith("REC ")]
    if out_path:
        with open(out_path, "w") as f:
            for rec in recs:
                f.write(json.dumps(rec) + "\n")
    if r.returncode != 0 and not recs:
        raise RuntimeError(r.stderr[-2000:])
    return recs


def table(recs: List[Dict]) -> str:
    lines = ["| arch | shape | strategy | compute ms | memory ms | "
             "collective ms | dominant | useful-FLOPs ratio |",
             "|---|---|---|---|---|---|---|---|"]
    for rec in recs:
        if rec.get("status") == "ok":
            lines.append(fmt_row(terms(rec)))
        elif rec.get("status") == "skip":
            lines.append(f"| {rec['arch']} | {rec['shape']} | — | — | — | — "
                         f"| skip | — |")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="results/roofline.jsonl")
    ap.add_argument("--pairs", help="JSON list of [arch, shape] pairs")
    ap.add_argument("--from-file", help="render table from existing jsonl")
    args = ap.parse_args()
    if args.from_file:
        recs = [json.loads(l) for l in open(args.from_file)]
    else:
        pairs = json.loads(args.pairs) if args.pairs else None
        recs = run_sweep(args.out, pairs)
    print(table(recs))


if __name__ == "__main__":
    main()
