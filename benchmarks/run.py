"""Benchmark harness — one entry per paper table/figure + the roofline.

Prints ``name,us_per_call,derived`` CSV rows (quick mode by default; pass
--full for the long versions).

  Table 1 / Fig 2  -> scaling            (cost model vs paper + HLO bytes)
  Table 2 / 3      -> container_overhead (capsule vs bare throughput/memory)
  SII-H            -> allreduce_vs_ps    (collective-traffic contrast)
  deliverable (g)  -> roofline           (summary of results/roofline.jsonl)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
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


def _kernel_micro(rows):
    """Microbenchmark the jnp hot paths the Pallas kernels replace (CPU
    timings; the kernels themselves are TPU-target, validated in
    interpret mode by tests/test_kernels.py)."""
    import jax
    import jax.numpy as jnp
    from repro.models.attention import attend
    from repro.models.ssm import ssd_chunked
    key = jax.random.PRNGKey(0)

    q = jax.random.normal(key, (1, 512, 2, 4, 64), jnp.bfloat16)
    k = jax.random.normal(key, (1, 512, 2, 64), jnp.bfloat16)
    v = jax.random.normal(key, (1, 512, 2, 64), jnp.bfloat16)
    f = jax.jit(lambda q, k, v: attend(q, k, v, scale=0.125, causal=True))
    f(q, k, v).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        out = f(q, k, v)
    out.block_until_ready()
    rows.append(("attend_ref/512tok_bf16", (time.perf_counter() - t0) / 10 * 1e6,
                 "jnp reference path (Pallas flash kernel = TPU hot path)"))

    x = jax.random.normal(key, (1, 512, 4, 64))
    dt = jax.nn.softplus(jax.random.normal(key, (1, 512, 4)))
    A = -jnp.exp(jax.random.normal(key, (4,)))
    B = jax.random.normal(key, (1, 512, 1, 64))
    C = jax.random.normal(key, (1, 512, 1, 64))
    g = jax.jit(lambda *a: ssd_chunked(*a, chunk=128)[0])
    g(x, dt, A, B, C).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        out = g(x, dt, A, B, C)
    out.block_until_ready()
    rows.append(("ssd_ref/512tok", (time.perf_counter() - t0) / 10 * 1e6,
                 "jnp reference path (Pallas ssd_scan = TPU hot path)"))


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
        if p in ("BENCH_tracing.json", "BENCH_slo.json"):
            errors.extend(_check_overhead_bound(p, data, dicts))
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


def _check_overhead_bound(p: str, data, dicts) -> list:
    """The tracing/observatory artifacts must *prove* their overhead
    claim: enabled-vs-disabled walls, their ratio, and a bound no looser
    than the documented 5% must all be present, with ratio <= bound.  A
    benchmark that quietly stopped measuring the disabled baseline (or
    relaxed its own budget) fails the build here, not in a review."""
    fields = ("disabled_wall_s", "enabled_wall_s", "overhead_ratio",
              "overhead_bound")
    holders = [d for d in dicts(data)
               if all(isinstance(d.get(k), (int, float)) for k in fields)]
    if not holders:
        missing = sorted({k for k in fields
                          if not any(isinstance(d.get(k), (int, float))
                                     for d in dicts(data))})
        return [f"{p}: overhead-bound fields missing or non-numeric "
                f"({', '.join(missing) or 'scattered across dicts'})"]
    errors = []
    for d in holders:
        if d["overhead_bound"] > 1.05:
            errors.append(f"{p}: overhead_bound {d['overhead_bound']} is "
                          f"looser than the documented 5% budget (1.05)")
        if d["overhead_ratio"] > d["overhead_bound"]:
            errors.append(f"{p}: overhead_ratio {d['overhead_ratio']:.4f} "
                          f"exceeds its bound {d['overhead_bound']}")
        if min(d["disabled_wall_s"], d["enabled_wall_s"]) <= 0:
            errors.append(f"{p}: non-positive wall-clock measurement")
    return errors


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", help="comma list: scaling,overhead,ps,physics,"
                                   "roofline,kernels,serving,prefix_cache,"
                                   "paged_attention,batched_prefill,"
                                   "interleaved,tracing,slo,"
                                   "fault_tolerance,fabric")
    ap.add_argument("--check", action="store_true",
                    help="after running, validate every BENCH_*.json in "
                         "the cwd (bit_identical_outputs true where "
                         "present, nonzero completed requests, and the "
                         "tracing/slo overhead ratio present and within "
                         "its documented 5%% bound) and exit nonzero on "
                         "any failure")
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
    if want("kernels"):
        try:
            _kernel_micro(rows)
        except Exception:
            traceback.print_exc()
            rows.append(("kernels/FAILED", 0.0, "see stderr"))
    if want("serving"):
        from benchmarks import serving_throughput
        try:
            rows += serving_throughput.run(quick=not args.full)
        except Exception:
            traceback.print_exc()
            rows.append(("serving/FAILED", 0.0, "see stderr"))
    if want("prefix_cache"):
        from benchmarks import prefix_cache
        try:
            rows += prefix_cache.run(quick=not args.full)
        except Exception:
            traceback.print_exc()
            rows.append(("prefix_cache/FAILED", 0.0, "see stderr"))
    if want("paged_attention"):
        from benchmarks import paged_attention
        try:
            rows += paged_attention.run(quick=not args.full)
        except Exception:
            traceback.print_exc()
            rows.append(("paged_attention/FAILED", 0.0, "see stderr"))
    if want("batched_prefill"):
        from benchmarks import batched_prefill
        try:
            rows += batched_prefill.run(quick=not args.full)
        except Exception:
            traceback.print_exc()
            rows.append(("batched_prefill/FAILED", 0.0, "see stderr"))
    if want("interleaved"):
        from benchmarks import interleaved_prefill
        try:
            rows += interleaved_prefill.run(quick=not args.full)
        except Exception:
            traceback.print_exc()
            rows.append(("interleaved_prefill/FAILED", 0.0, "see stderr"))
    if want("tracing"):
        from benchmarks import tracing_overhead
        try:
            rows += tracing_overhead.run(quick=not args.full)
        except Exception:
            traceback.print_exc()
            rows.append(("tracing_overhead/FAILED", 0.0, "see stderr"))
    if want("slo"):
        from benchmarks import slo_observatory
        try:
            rows += slo_observatory.run(quick=not args.full)
        except Exception:
            traceback.print_exc()
            rows.append(("slo_observatory/FAILED", 0.0, "see stderr"))
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
