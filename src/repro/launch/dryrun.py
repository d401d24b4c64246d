import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"       # a CPU analysis: never open the chip

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input shape) pair this lowers AND compiles the
appropriate step program (train_step / prefill / serve_step) against the
production mesh — 16x16 single-pod and 2x16x16 multi-pod — using
ShapeDtypeStruct inputs only (no allocation), then records:

  * memory_analysis(): per-device bytes (proves it fits 16 GB HBM),
  * cost_analysis(): HLO FLOPs / bytes (roofline compute & memory terms),
  * collective bytes parsed from the compiled HLO text (roofline
    collective term).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2_2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--json out]
"""
import argparse
import json
import re
import sys
import time
import traceback
from typing import Dict, Optional

import jax
import numpy as np


# ---------------------------------------------------------------------------
# Collective-byte accounting from HLO text
# ---------------------------------------------------------------------------

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.-]+\s*=\s*(\(?[^=]*?\)?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", re.M)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output-shape bytes of every collective op (per device)."""
    # strip /*index=N*/ comments: the '=' inside breaks the shape matcher
    hlo_text = re.sub(r"/\*.*?\*/", "", hlo_text)
    out: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        shape_str, op = m.group(1), m.group(2)
        # avoid double counting start/done pairs: skip -done (its operand is
        # the -start tuple)
        full = m.group(0)
        if "-done(" in full:
            continue
        out[op] += _shape_bytes(shape_str)
    return out


_GROUPS_RE = re.compile(r"replica_groups=\{?\{([\d,{}\s]*)\}\}?")
_LINE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.-]+\s*=\s*(\(?[^=]*?\)?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\((?P<rest>.*)$", re.M)


def collective_bytes_by_scope(hlo_text: str, pod_size: int = 256) -> Dict[str, int]:
    """Split collective bytes into intra-pod vs inter-pod traffic by whether
    any replica group spans the pod boundary (device id // pod_size)."""
    hlo_text = re.sub(r"/\*.*?\*/", "", hlo_text)
    out = {"intra_pod": 0, "inter_pod": 0}
    for m in _LINE_RE.finditer(hlo_text):
        shape_str = m.group(1)
        rest = m.group("rest")
        nbytes = _shape_bytes(shape_str)
        gm = _GROUPS_RE.search(rest)
        scope = "intra_pod"
        if gm:
            for grp in gm.group(1).split("},{"):
                ids = [int(t) for t in re.findall(r"\d+", grp)]
                if ids and len({i // pod_size for i in ids}) > 1:
                    scope = "inter_pod"
                    break
        elif "collective-permute" in m.group(2):
            scope = "intra_pod"
        out[scope] += nbytes
    return out


# ---------------------------------------------------------------------------
# The dry-run itself
# ---------------------------------------------------------------------------

def dryrun_pair(arch: str, shape_name: str, *, multi_pod: bool = False,
                strategy: Optional[str] = None, unrolled: bool = False,
                verbose: bool = True) -> Dict:
    """unrolled=True lowers with the layer loop unrolled and attention
    unchunked, so cost_analysis() FLOPs/bytes and the HLO-text collective
    bytes are exact (XLA counts a while-loop body once, not x trip-count).
    The scanned version stays the canonical compile-feasibility artifact."""
    from repro.configs import get_config, default_strategy
    from repro.configs.base import SHAPES, input_specs, shape_skips
    from repro.distributed import stepfn
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    skip = shape_skips(cfg, shape)
    if skip:
        return {"arch": arch, "shape": shape_name, "status": "skip",
                "reason": skip}
    if unrolled:
        cfg = cfg.with_(scan_layers=False, attn_q_chunk=0)
    strategy = strategy or default_strategy(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    jitted, structs = stepfn.make_step_for_shape(cfg, mesh, strategy, shape)
    with mesh, jax.transfer_guard("disallow"):
        lowered = jitted.lower(*structs)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    n_chips = int(np.prod(list(mesh.shape.values())))
    rec = {
        "arch": arch, "shape": shape_name, "status": "ok",
        "strategy": strategy, "multi_pod": multi_pod, "chips": n_chips,
        "unrolled": unrolled,
        "compile_s": round(time.time() - t0, 1),
        "flops": float(cost.get("flops", -1)),
        "bytes_accessed": float(cost.get("bytes accessed", -1)),
        "collective_bytes": coll,
        "collective_bytes_total": int(sum(coll.values())),
        "peak_memory_per_device": int(getattr(mem, "peak_memory_in_bytes", -1)),
        "argument_size": int(getattr(mem, "argument_size_in_bytes", -1)),
        "output_size": int(getattr(mem, "output_size_in_bytes", -1)),
        "temp_size": int(getattr(mem, "temp_size_in_bytes", -1)),
    }
    if verbose:
        print(f"[{arch} x {shape_name} | {'2x16x16' if multi_pod else '16x16'}"
              f" | {strategy}] compile {rec['compile_s']}s  "
              f"flops/dev {rec['flops']:.3e}  bytes/dev {rec['bytes_accessed']:.3e}  "
              f"coll/dev {rec['collective_bytes_total']:.3e}  "
              f"peak-mem/dev {rec['peak_memory_per_device']/2**30:.2f} GiB")
        print("  memory_analysis:", mem)
    return rec


# ---------------------------------------------------------------------------
# Roofline costs via layer-linearity extrapolation
# ---------------------------------------------------------------------------
#
# XLA's cost_analysis counts a while-loop body ONCE (not x trip count), and
# fully unrolling 62-80 layer configs takes tens of minutes on one CPU core.
# Layers are homogeneous, so every cost term is affine in the number of scan
# groups G:  cost(G) = fixed + G * per_group.  We compile the UNROLLED
# program at G=1 and G=2 (seconds each) and extrapolate exactly:
#     cost(G_target) = cost1 + (G_target - 1) * (cost2 - cost1)
# Validated against a full 26-layer unroll in tests/test_dryrun.py.

def _group_counts(cfg):
    """(G_target, cfg_at_1_group, cfg_at_2_groups)."""
    from repro.models.transformer import layer_pattern
    if cfg.family == "hybrid":
        E, L = cfg.hybrid_attn_every, cfg.num_layers
        G, R = L // E, L % E
        return G, cfg.with_(num_layers=E + R), cfg.with_(num_layers=2 * E + R)
    if cfg.family == "encdec":
        G = cfg.num_layers
        assert cfg.encoder_layers == cfg.num_layers
        return G, cfg.with_(num_layers=1, encoder_layers=1), \
            cfg.with_(num_layers=2, encoder_layers=2)
    pat = len(layer_pattern(cfg))
    G = cfg.num_layers // pat
    return G, cfg.with_(num_layers=pat), cfg.with_(num_layers=2 * pat)


def _compile_costs(cfg, shape, mesh, strategy):
    from repro.distributed import stepfn
    jitted, structs = stepfn.make_step_for_shape(cfg, mesh, strategy, shape)
    with mesh, jax.transfer_guard("disallow"):
        compiled = jitted.lower(*structs).compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": {k: float(v) for k, v in coll.items()},
            "coll_total": float(sum(coll.values()))}


def roofline_pair(arch: str, shape_name: str, *,
                  strategy: Optional[str] = None,
                  multi_pod: bool = False, verbose: bool = True) -> Dict:
    """Exact per-device roofline cost terms for (arch x shape) via the
    G=1/G=2 extrapolation above.  Single-pod by default (per the brief)."""
    from repro.configs import get_config, default_strategy
    from repro.configs.base import SHAPES, shape_skips
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    skip = shape_skips(cfg, shape)
    if skip:
        return {"arch": arch, "shape": shape_name, "status": "skip",
                "reason": skip}
    strategy = strategy or default_strategy(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    G, cfg1, cfg2 = _group_counts(cfg)
    cfg1 = cfg1.with_(scan_layers=False, attn_q_chunk=0)
    cfg2 = cfg2.with_(scan_layers=False, attn_q_chunk=0)
    t0 = time.time()
    c1 = _compile_costs(cfg1, shape, mesh, strategy)
    c2 = _compile_costs(cfg2, shape, mesh, strategy)

    def extrap(a, b):
        return a + (G - 1) * (b - a)

    coll = {k: extrap(c1["coll"][k], c2["coll"][k]) for k in c1["coll"]}
    rec = {
        "arch": arch, "shape": shape_name, "status": "ok",
        "strategy": strategy, "multi_pod": multi_pod,
        "chips": int(np.prod(list(mesh.shape.values()))),
        "groups": G, "compile_s": round(time.time() - t0, 1),
        "flops": extrap(c1["flops"], c2["flops"]),
        "bytes_accessed": extrap(c1["bytes"], c2["bytes"]),
        "collective_bytes": {k: int(v) for k, v in coll.items()},
        "collective_bytes_total": int(sum(coll.values())),
    }
    if verbose:
        print(f"[roofline {arch} x {shape_name} | {strategy}] "
              f"G={G} compile {rec['compile_s']}s  "
              f"flops/dev {rec['flops']:.3e}  bytes/dev "
              f"{rec['bytes_accessed']:.3e}  coll/dev "
              f"{rec['collective_bytes_total']:.3e}")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", help="architecture id (see repro/configs)")
    ap.add_argument("--shape", help="input shape name",
                    choices=["train_4k", "prefill_32k", "decode_32k",
                             "long_500k"])
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) pair")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the 2x16x16 = 512-chip mesh")
    ap.add_argument("--both-meshes", action="store_true",
                    help="run each pair on single-pod AND multi-pod meshes")
    ap.add_argument("--strategy", choices=["dp", "dp_tp", "fsdp_tp"])
    ap.add_argument("--unrolled", action="store_true",
                    help="unroll layer loops for exact cost accounting "
                         "(roofline mode)")
    ap.add_argument("--json", help="append JSONL records to this path")
    args = ap.parse_args(argv)

    from repro.configs import ARCHS
    from repro.configs.base import SHAPES

    pairs = []
    archs = ARCHS if args.all else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                pairs.append((a, s, mp))

    records, failures = [], []
    for arch, shape, mp in pairs:
        try:
            rec = dryrun_pair(arch, shape, multi_pod=mp,
                              strategy=args.strategy, unrolled=args.unrolled)
        except Exception as e:
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                   "status": "fail", "error": f"{type(e).__name__}: {e}"}
            failures.append(rec)
        records.append(rec)
        if args.json:
            with open(args.json, "a") as f:
                f.write(json.dumps(rec) + "\n")

    ok = sum(r["status"] == "ok" for r in records)
    skip = sum(r["status"] == "skip" for r in records)
    print(f"\ndry-run: {ok} ok, {skip} skip, {len(failures)} FAIL "
          f"of {len(records)}")
    for f_ in failures:
        print("  FAIL:", f_["arch"], f_["shape"],
              "multi_pod" if f_["multi_pod"] else "", f_["error"][:200])
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
