"""Serving-observatory overhead benchmark: the full telemetry stack
(per-tenant SLO monitoring + span-timed step phases + recompile
tracking) enabled vs disabled on the interleaved workload.

The observatory is only admissible in the serving hot loop if it is
effectively free and inert: the claim is that an armed observatory —
enabled tracer, per-tenant sliding-window percentiles, an SLO monitor
evaluated every step, step phases timed at their program spans and
shape-signature recompile tracking — keeps wall clock within 5% of the
bare metrics path on the interleaved prefill/decode workload, with
greedy outputs bit-identical.

Four measurements, written to ``BENCH_slo.json``:

* **overhead** — the interleaved-benchmark request stream (2 long
  decodes + 3x8-deep prompt bursts, paged engine, budgeted prefill),
  requests labelled round-robin across two tenants, run with the
  observatory off and on in alternating order (A/B then B/A), medians
  over reps; asserts ``on_wall <= 1.05 x off_wall`` and bit-identical
  outputs;
* **recompiles** — after warmup the tracker is marked warm; asserts
  steady-state interleaved serving causes *zero* post-warm
  recompilations across every measured rep (both modes share one
  engine, so a drifting shape in either would trip it);
* **fleet rollup** — a 2-replica gateway run with tenant labels;
  asserts the merged multi-replica summary carries per-tenant TTFT
  p95 > 0 and inter-token-gap percentiles for both tenants;
* **breach demo** — one run under a deliberately impossible policy
  (TTFT p95 <= 0.001 ms); asserts the monitor records breaches and at
  least one ``slo_breach`` event lands in the trace buffer.

A paged-kernel cost/roofline profile (``profile_paged_kernels``) is
recorded alongside for the report, not asserted on: CPU wall numbers
for TPU-target kernels are context, not claims.

  PYTHONPATH=src python -m benchmarks.slo_observatory          # smoke
  PYTHONPATH=src python -m benchmarks.slo_observatory --full
"""
from __future__ import annotations

import argparse
import time

from benchmarks.interleaved_prefill import (BURST_DEPTH, BURST_STEPS,
                                            MAX_NEW_BURST, MAX_NEW_LONG,
                                            N_LONG, _warmup, _workload)

TENANTS = ("tenant-a", "tenant-b")
OVERHEAD_BOUND = 1.05
FLEET_REQUESTS = 10


def _serve(engine, cfg, budget, tracer):
    """One interleaved-workload run, requests labelled round-robin over
    ``TENANTS``, through a scheduler wearing ``tracer`` (armed or not) —
    same code path either way."""
    from repro.serving import Request, SamplingParams, Scheduler
    longs, bursts = _workload(cfg)
    sched = Scheduler(engine, prefill_token_budget=budget, tracer=tracer)
    n_sub = 0

    def sub(prompt, max_new):
        nonlocal n_sub
        rid = sched.submit(Request(
            prompt, SamplingParams(max_new_tokens=max_new, greedy=True),
            tenant=TENANTS[n_sub % len(TENANTS)]))
        n_sub += 1
        return rid

    rids = [sub(p, MAX_NEW_LONG) for p in longs]
    pending = list(zip(BURST_STEPS, bursts))
    steps = 0
    t0 = time.perf_counter()
    while sched.has_work or pending:
        if pending and steps >= pending[0][0]:
            burst = pending.pop(0)[1]
            rids += [sub(p, MAX_NEW_BURST) for p in burst]
        sched.step()
        steps += 1
    wall = time.perf_counter() - t0
    return [sched.output(r) for r in rids], sched.metrics.summary(), wall


def _fleet_rollup(engine_fn, cfg, budget, slo_config):
    """2-replica gateway with tenant labels: the merged summary must
    carry per-tenant percentiles, not just per-replica ones."""
    import numpy as np
    from repro.serving import ReplicaGateway, Request, SamplingParams

    gw = ReplicaGateway.from_engines(
        [engine_fn(), engine_fn()], prefill_token_budget=budget,
        tracing=True, slo_config=slo_config)
    rng = np.random.default_rng(5)
    for i in range(FLEET_REQUESTS):
        gw.submit(Request(
            rng.integers(0, cfg.vocab_size, int(rng.integers(8, 24)),
                         dtype=np.int32),
            SamplingParams(max_new_tokens=6, greedy=True),
            tenant=TENANTS[i % len(TENANTS)]))
    gw.drain()
    totals = gw.stats()["totals"]
    assert totals["requests_completed"] == FLEET_REQUESTS
    for t in TENANTS:
        ts = totals["tenants"][t]
        assert ts["requests_completed"] > 0, f"{t}: no completions merged"
        assert ts["ttft_ms"]["p95"] > 0, f"{t}: TTFT p95 missing"
        assert {"p50", "p95", "max"} <= set(ts["decode_gap_ms"]), (
            f"{t}: gap percentiles missing from merged rollup")
    return {
        "replicas": 2, "requests": FLEET_REQUESTS,
        "tenants": {t: {"requests_completed":
                        totals["tenants"][t]["requests_completed"],
                        "ttft_p95_ms": totals["tenants"][t]["ttft_ms"]["p95"],
                        "gap_p95_ms":
                        totals["tenants"][t]["decode_gap_ms"]["p95"]}
                    for t in TENANTS},
        "slo_breaches": totals.get("slo_breaches", 0),
    }


def _breach_demo(engine, cfg, budget):
    """An impossible policy must breach, and the breach must land in
    the trace buffer as an ``slo_breach`` event."""
    from repro.serving import SLOConfig, SLOMonitor, Tracer
    tight = SLOConfig.from_dict({
        "default": {"ttft_p95_ms": 0.001, "min_samples": 1}})
    tracer = Tracer(enabled=True, slo=SLOMonitor(tight))
    _serve(engine, cfg, budget, tracer)
    breaches = tracer.slo.breaches
    events = [e for e in tracer.snapshot() if e["kind"] == "slo_breach"]
    assert breaches >= 1, "impossible TTFT policy did not breach"
    assert events, "breach not emitted as an slo_breach trace event"
    return {"breaches": breaches, "breach_events": len(events)}


def run(quick: bool = True, out_path: str = "BENCH_slo.json"):
    import jax
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    from repro.serving import (ServingEngine, SLOConfig, SLOMonitor, Tracer,
                               profile_paged_kernels)

    arch = "qwen2-0.5b"
    block, max_seq_len, slots, prefill_batch, chunk = 16, 64, 12, 4, 8
    budget = prefill_batch * chunk
    reps = 3 if quick else 5

    cfg = get_smoke_config(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    num_blocks = slots * (max_seq_len // block)

    def engine():
        return ServingEngine(cfg, params, max_seq_len=max_seq_len,
                             max_slots=slots, kv_block_size=block,
                             prefill_chunk=chunk,
                             prefill_batch=prefill_batch,
                             paged=True, num_blocks=num_blocks)

    # generous policy: the cost of *evaluating* SLOs every step is what
    # is being measured, not the cost of breaching them
    slo_config = SLOConfig.from_dict({
        "default": {"ttft_p95_ms": 60_000.0, "gap_p95_ms": 60_000.0,
                    "queue_wait_p95_ms": 60_000.0}})

    def armed_tracer():
        return Tracer(enabled=True, slo=SLOMonitor(slo_config))

    # one engine serves both modes: identical compile caches — the only
    # variable is the observatory; the warm rep covers every shape the
    # workload compiles, so post-warm novelty below is a regression
    eng = engine()
    _warmup(eng, cfg)
    _serve(eng, cfg, budget, armed_tracer())   # warm discarded rep
    eng.recompiles.mark_warm()

    off_walls, on_walls = [], []
    off_out = on_out = None
    on_sum = {}
    events_recorded = 0
    for rep in range(reps):
        order = ("off", "on") if rep % 2 == 0 else ("on", "off")
        for mode in order:
            if mode == "off":
                off_out, _off_sum, wall = _serve(eng, cfg, budget,
                                                 Tracer())
                off_walls.append(wall)
            else:
                tr = armed_tracer()
                on_out, on_sum, wall = _serve(eng, cfg, budget, tr)
                on_walls.append(wall)
                events_recorded = tr.emitted_events

    for a, b in zip(off_out, on_out):
        np.testing.assert_array_equal(a, b)          # observatory is inert

    n_req = N_LONG + BURST_DEPTH * len(BURST_STEPS)
    assert on_sum["requests_completed"] == n_req
    for t in TENANTS:
        assert on_sum["tenants"][t]["ttft_ms"]["count"] > 0

    recomp = eng.recompiles.summary()
    assert recomp["post_warm_recompiles"] == 0, (
        f"steady-state serving recompiled post-warm: {recomp}")

    off_wall = sorted(off_walls)[reps // 2]
    on_wall = sorted(on_walls)[reps // 2]
    ratio = on_wall / off_wall
    assert ratio <= OVERHEAD_BOUND, (
        f"armed observatory cost {(ratio - 1) * 100:.1f}% wall clock "
        f"({on_wall:.3f}s vs {off_wall:.3f}s bare, medians of {reps}) — "
        f"over the {(OVERHEAD_BOUND - 1) * 100:.0f}% budget")

    kernels = {name: {k: prof[k] for k in
                      ("wall_ms_median", "flops", "bytes_accessed",
                       "achieved_tflops", "fraction_of_peak_flops",
                       "achieved_gbps", "fraction_of_peak_bw",
                       "arithmetic_intensity")}
               for name, prof in profile_paged_kernels(eng).items()}

    fleet = _fleet_rollup(engine, cfg, budget, slo_config)
    breach = _breach_demo(engine(), cfg, budget)

    record = {
        "arch": arch, "quick": quick, "n_requests": n_req, "reps": reps,
        "block_size": block, "max_seq_len": max_seq_len,
        "max_slots": slots, "num_blocks": num_blocks,
        "prefill_token_budget": budget,
        "tenants": list(TENANTS),
        "disabled_wall_s": off_wall,
        "enabled_wall_s": on_wall,
        "overhead_ratio": ratio,
        "overhead_bound": OVERHEAD_BOUND,
        "events_per_run": events_recorded,
        "requests_completed": on_sum["requests_completed"],
        "bit_identical_outputs": True,
        "per_tenant": on_sum["tenants"],
        "recompiles": recomp,
        "kernel_profiles": kernels,
        "fleet_rollup": fleet,
        "breach_demo": breach,
    }
    # atomic (tmp + os.replace): a benchmark killed mid-write can never
    # leave a truncated BENCH_*.json for run.py --check to choke on
    from repro.serving.metrics import atomic_write_json
    atomic_write_json(out_path, record)

    ta = on_sum["tenants"][TENANTS[0]]
    rows = [
        ("slo_observatory/disabled", off_wall * 1e6,
         f"interleaved workload, bare metrics path, median of {reps}"),
        ("slo_observatory/enabled", on_wall * 1e6,
         f"SLO monitor + span-timed phases + recompile tracker on: "
         f"{(ratio - 1) * 100:+.1f}% wall vs bare "
         f"(bound {(OVERHEAD_BOUND - 1) * 100:.0f}%), bit-identical, "
         f"{recomp['post_warm_recompiles']} post-warm recompiles, "
         f"results -> {out_path}"),
        ("slo_observatory/per_tenant", 0.0,
         f"{TENANTS[0]}: ttft p95 {ta['ttft_ms']['p95']:.1f} ms, "
         f"gap p95 {ta['decode_gap_ms']['p95']:.2f} ms over "
         f"{ta['requests_completed']} requests; fleet rollup over "
         f"{fleet['replicas']} replicas carries both tenants"),
        ("slo_observatory/breach_demo", 0.0,
         f"impossible policy: {breach['breaches']} breach(es), "
         f"{breach['breach_events']} slo_breach event(s) in trace"),
    ]
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out", default="BENCH_slo.json")
    args = ap.parse_args()
    rows = run(quick=not args.full, out_path=args.out)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
