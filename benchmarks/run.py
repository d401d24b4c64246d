"""Benchmark harness — one entry per paper table/figure + the roofline.

Prints ``name,us_per_call,derived`` CSV rows (quick mode by default; pass
--full for the long versions).

  Table 1 / Fig 2  -> scaling            (cost model vs paper + HLO bytes)
  Table 2 / 3      -> container_overhead (capsule vs bare throughput/memory)
  SII-H            -> allreduce_vs_ps    (collective-traffic contrast)
  deliverable (g)  -> roofline           (summary of results/roofline.jsonl)
  physics          -> physics_validation
  fault_tolerance, fabric -> replica-kill salvage and bit-identity gates
                      (BENCH_faults.json / BENCH_fabric.json, --check)

Serving speed is not measured here: ``perfbench/`` measures it on the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback


def _roofline_summary(rows):
    path = "results/roofline.jsonl"
    if not os.path.exists(path):
        rows.append(("roofline/missing", 0.0,
                     "run: python -m benchmarks.roofline"))
        return
    from benchmarks.roofline import terms
    recs = [json.loads(l) for l in open(path)]
    ok = [r for r in recs if r.get("status") == "ok"]
    doms = {}
    for rec in ok:
        t = terms(rec)
        doms[t["dominant"]] = doms.get(t["dominant"], 0) + 1
        rows.append((f"roofline/{rec['arch']}/{rec['shape']}",
                     (t["compute_s"] + t["memory_s"] + t["collective_s"]) * 1e6,
                     f"dom={t['dominant']} c={t['compute_s']*1e3:.2f}ms "
                     f"m={t['memory_s']*1e3:.2f}ms "
                     f"x={t['collective_s']*1e3:.2f}ms "
                     f"useful={t['useful_flops_ratio']:.2f}"))
    rows.append(("roofline/dominant_terms", 0.0,
                 " ".join(f"{k}:{v}" for k, v in sorted(doms.items()))))


def _check_bench_json() -> list:
    """CI guard: every emitted BENCH_*.json must carry a nonzero
    completed-request count, and ``bit_identical_outputs`` — where the
    benchmark records one — must be true.  A benchmark that silently
    stopped completing work or lost bit-identity fails the build instead
    of shipping a green-looking artifact."""
    import glob

    def dicts(o):
        if isinstance(o, dict):
            yield o
            for v in o.values():
                yield from dicts(v)
        elif isinstance(o, list):
            for v in o:
                yield from dicts(v)

    errors = []
    paths = sorted(glob.glob("BENCH_*.json"))
    if not paths:
        return ["--check: no BENCH_*.json artifacts found"]
    for p in paths:
        try:
            with open(p) as f:
                data = json.load(f)
        except Exception as e:                       # noqa: BLE001
            errors.append(f"{p}: unreadable ({e})")
            continue
        bits = [d["bit_identical_outputs"] for d in dicts(data)
                if "bit_identical_outputs" in d]
        if any(v is not True for v in bits):
            errors.append(f"{p}: bit_identical_outputs is not true")
        # true completion counters only — n_requests is configuration
        # (always nonzero by construction) and would make this vacuous
        counts = [d[k] for d in dicts(data)
                  for k in ("requests_completed", "completed")
                  if isinstance(d.get(k), (int, float))]
        if not counts:
            errors.append(f"{p}: no completed-request count found")
        elif max(counts) <= 0:
            errors.append(f"{p}: zero completed requests")
        if p in ("BENCH_faults.json", "BENCH_fabric.json"):
            errors.extend(_check_faults(p, data))
    return errors


def _check_faults(p: str, data) -> list:
    """The fault-tolerance and fabric artifacts must prove the failover
    claim: the kill salvaged work (not a no-op crash), every salvaged
    request completed on a survivor, and nothing resolved to a typed
    failure."""
    errors = []
    for k in ("salvage_success_rate", "salvaged_requests",
              "failed_requests", "failovers"):
        if not isinstance(data.get(k), (int, float)):
            errors.append(f"{p}: missing or non-numeric '{k}'")
    if errors:
        return errors
    if data["salvaged_requests"] <= 0 or data["failovers"] <= 0:
        errors.append(f"{p}: the injected kill salvaged nothing — the "
                      f"crash landed after the burst finished")
    if data["salvage_success_rate"] != 1.0:
        errors.append(f"{p}: salvage_success_rate "
                      f"{data['salvage_success_rate']} != 1.0 — salvaged "
                      f"requests were lost")
    if data["failed_requests"] != 0:
        errors.append(f"{p}: {data['failed_requests']} request(s) "
                      f"resolved to typed failures with survivors "
                      f"available")
    return errors


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", help="comma list: scaling,overhead,ps,physics,"
                                   "roofline,fault_tolerance,fabric")
    ap.add_argument("--check", action="store_true",
                    help="after running, validate every BENCH_*.json in "
                         "the cwd (bit_identical_outputs true where "
                         "present, nonzero completed requests, and the "
                         "fault/fabric salvage gates) and exit nonzero "
                         "on any failure")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    rows = []

    def want(name):
        return only is None or name in only

    if want("scaling"):
        from benchmarks import scaling
        try:
            rows += scaling.run(quick=not args.full)
        except Exception:
            traceback.print_exc()
            rows.append(("scaling/FAILED", 0.0, "see stderr"))
    if want("ps"):
        from benchmarks import allreduce_vs_ps
        try:
            rows += allreduce_vs_ps.run()
        except Exception:
            traceback.print_exc()
            rows.append(("allreduce_vs_ps/FAILED", 0.0, "see stderr"))
    if want("overhead"):
        from benchmarks import container_overhead
        try:
            rows += container_overhead.run()
        except Exception:
            traceback.print_exc()
            rows.append(("container_overhead/FAILED", 0.0, "see stderr"))
    if want("fault_tolerance"):
        from benchmarks import fault_tolerance
        try:
            rows += fault_tolerance.run(quick=not args.full)
        except Exception:
            traceback.print_exc()
            rows.append(("fault_tolerance/FAILED", 0.0, "see stderr"))
    if want("fabric"):
        from benchmarks import fabric
        try:
            rows += fabric.run(quick=not args.full)
        except Exception:
            traceback.print_exc()
            rows.append(("fabric/FAILED", 0.0, "see stderr"))
    if want("physics"):
        from benchmarks import physics_validation
        try:
            rows += physics_validation.run(
                train_steps=60 if args.full else 25)
        except Exception:
            traceback.print_exc()
            rows.append(("physics/FAILED", 0.0, "see stderr"))
    if want("roofline"):
        _roofline_summary(rows)

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    # a failed phase fails the run, with or without --check
    errors = [f"{name}: benchmark failed" for name, _, _ in rows
              if name.endswith("/FAILED")]
    if args.check:
        errors += _check_bench_json()
    if errors:
        for e in errors:
            print(f"CHECK FAILED: {e}", file=sys.stderr)
        sys.exit(1)
    if args.check:
        print("check: all BENCH_*.json artifacts healthy")


if __name__ == "__main__":
    main()
