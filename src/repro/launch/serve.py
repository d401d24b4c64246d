"""Production serving launcher (in-capsule entrypoint).

Routes requests through the continuous-batching scheduler: admission
queue -> per-slot prefill -> batched decode with per-request sampling ->
early exit on each request's own ``max_new_tokens`` / EOS.  Prints
per-request outputs plus TTFT / throughput telemetry, and can fan out
over multiple engine replicas (``--replicas``, each conceptually one
``ch-run`` capsule) behind the prefix-affine, load-balanced gateway.
``--prefix-cache-blocks N`` (default on) gives each replica an N-block
prefix store + radix index; ``--shared-prefix K`` makes every request
open with the same K synthetic tokens to exercise it.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \\
      --requests 8 --max-new 16 --shared-prefix 64

Add ``--metrics-json PATH`` to export the scheduler telemetry for the
benchmark harness, ``--metrics-out PATH`` for just the gateway-merged
totals summary, and ``--trace-out BASE`` to enable request-lifecycle
tracing and write ``BASE.jsonl`` (merged event log) plus
``BASE.chrome.json`` (Perfetto / chrome://tracing) at end of run;
``--trace-buffer-events`` sizes the per-replica ring buffer.

``--fabric {local,mock}`` promotes the fleet across process
boundaries: replicas become fabric workers (real subprocesses, or
deterministic in-process mocks) launched through a
``SchedulerBackend`` and driven over the shared-filesystem mailbox —
the same gateway, health ladder, and salvage machinery, with the
model rebuilt bit-identically in each worker from the declarative
spec.  ``--spool DIR`` picks the spool directory; ``--trace-out``
then merges gateway- and worker-side events into one fleet trace
(``scripts/trace_report.py --fleet``).
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq-len", type=int, default=128)
    ap.add_argument("--max-slots", type=int, default=4,
                    help="continuous-batching slots per replica")
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--fabric", choices=("local", "mock"), default=None,
                    help="launch replicas as fabric workers behind the "
                         "shared-filesystem mailbox instead of in-process "
                         "engines: 'local' = real subprocess workers "
                         "(LocalProcessBackend), 'mock' = deterministic "
                         "in-process workers (MockBackend); requires "
                         "--smoke — workers rebuild bit-identical weights "
                         "from the declarative smoke spec.  Subprocess "
                         "workers run on the CPU (JAX_PLATFORMS=cpu): a "
                         "chip belongs to one process, and this fleet is "
                         "smoke-size only")
    ap.add_argument("--spool", default=None, metavar="DIR",
                    help="fabric spool directory "
                         "(default: results/fabric-spool)")
    ap.add_argument("--greedy", action="store_true")
    ap.add_argument("--greedy-tie-eps", type=float, default=1e-2,
                    help="deterministic greedy tie break: pick the "
                         "lowest token id within eps of the max logit, "
                         "making argmax layout-stable under paged/dense "
                         "summation-order noise (on by default; pass 0 "
                         "to opt out and restore raw argmax)")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--metrics-json", default=None,
                    help="export full per-replica + merged telemetry JSON")
    ap.add_argument("--metrics-out", default=None,
                    help="export only the gateway-merged totals summary "
                         "JSON at end of run")
    ap.add_argument("--trace-out", default=None,
                    help="enable request-lifecycle tracing; writes "
                         "PATH.jsonl (merged events) + PATH.chrome.json "
                         "(Perfetto) at end of run")
    ap.add_argument("--trace-buffer-events", type=int, default=None,
                    help="per-replica trace ring-buffer depth "
                         "(default 65536; oldest events drop first)")
    ap.add_argument("--paged", action="store_true",
                    help="paged attention: block-resident KV gathered "
                         "through block tables (Pallas kernel)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV pool size in blocks (paged only; below "
                         "worst case = memory oversubscription)")
    ap.add_argument("--prefill-batch", type=int, default=4,
                    help="max co-admitted prompts per scheduler round "
                         "(batched multi-slot prefill; 1 = one-at-a-time)")
    ap.add_argument("--prefill-token-budget", type=int, default=None,
                    help="max executed prefill token positions per "
                         "scheduler step (SplitFuse-style interleaving: "
                         "bounds decode latency jitter under admission "
                         "bursts; default: unbudgeted wave-at-once)")
    ap.add_argument("--prefix-cache-blocks", type=int, default=64,
                    help="per-replica prefix-store KV blocks (0 disables)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="open every prompt with this many shared tokens")
    ap.add_argument("--tenant", action="append", default=None,
                    metavar="NAME",
                    help="tenant label(s); repeat or comma-separate — "
                         "requests are assigned round-robin and get "
                         "per-tenant SLO percentiles (default: 'default')")
    ap.add_argument("--slo-config", default=None, metavar="PATH",
                    help="JSON SLO policy file: {\"default\": {...}, "
                         "\"tenants\": {name: {...}}} with thresholds "
                         "like ttft_p95_ms / gap_p95_ms; breaches land "
                         "in the trace as slo_breach events")
    ap.add_argument("--profile", action="store_true",
                    help="print each replica's recompile telemetry "
                         "(kernel times: take a jax.profiler trace, where "
                         "the step phases are program spans)")
    ap.add_argument("--metrics-interval-steps", type=int, default=None,
                    metavar="N",
                    help="with --metrics-out: atomically re-write the "
                         "totals snapshot every N scheduler steps, so a "
                         "killed capsule leaves a readable last snapshot")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from repro.configs import get_config, get_smoke_config
    from repro.models import transformer as T
    from repro.serving import (ReplicaGateway, Request, SamplingParams,
                               ServingEngine, SLOConfig, atomic_write_json)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family == "encdec":
        raise SystemExit("serve launcher targets decoder LMs")
    tenants = [t for arg in (args.tenant or ["default"])
               for t in arg.split(",") if t]
    slo_config = (SLOConfig.from_json(args.slo_config)
                  if args.slo_config else None)
    fabric_backend = None
    spool = None
    if args.fabric:
        if not args.smoke:
            raise SystemExit("--fabric requires --smoke: workers rebuild "
                             "bit-identical weights from the declarative "
                             "smoke-config spec")
        if args.profile or args.slo_config:
            raise SystemExit("--fabric replicas live in other processes; "
                             "--profile / --slo-config introspection is "
                             "in-process only")
        from repro.serving import (LocalProcessBackend, MockBackend,
                                   collect_fabric_traces,
                                   launch_fabric_replicas, shutdown_fabric)
        backend_cls = {"local": LocalProcessBackend, "mock": MockBackend}
        fabric_backend = backend_cls[args.fabric]()
        spool = Path(args.spool or "results/fabric-spool")
        model_spec = {"config": args.arch, "seed": 0,
                      "engine": {"max_seq_len": args.max_seq_len,
                                 "max_slots": args.max_slots,
                                 "prefill_batch": args.prefill_batch,
                                 "greedy_tie_eps": args.greedy_tie_eps}}
        gateway = launch_fabric_replicas(
            args.replicas, fabric_backend, spool, model_spec=model_spec,
            tracing=True)
        print(f"run config: arch={cfg.name} replicas={args.replicas} "
              f"fabric={args.fabric} spool={spool} "
              f"max_slots={args.max_slots} max_seq_len={args.max_seq_len} "
              f"prefill_batch={args.prefill_batch}")
        for rep in gateway.replicas:
            print(f"fabric replica {rep.name}: {rep.capsule['backend']} "
                  f"job {rep.capsule['job_id']} "
                  f"(partition {rep.capsule['partition']})")
    else:
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        engines = [ServingEngine(cfg, params,
                                 max_seq_len=args.max_seq_len,
                                 max_slots=args.max_slots, rng_seed=r,
                                 prefix_cache_blocks=args.prefix_cache_blocks,
                                 paged=args.paged,
                                 num_blocks=args.num_blocks,
                                 prefill_batch=args.prefill_batch,
                                 greedy_tie_eps=args.greedy_tie_eps)
                   for r in range(args.replicas)]
        gateway = ReplicaGateway.from_engines(
            engines, prefill_token_budget=args.prefill_token_budget,
            tracing=args.trace_out is not None,
            trace_buffer_events=args.trace_buffer_events,
            slo_config=slo_config)
        print(f"run config: arch={cfg.name} replicas={args.replicas} "
              f"max_slots={args.max_slots} max_seq_len={args.max_seq_len} "
              f"paged={args.paged} num_blocks={args.num_blocks} "
              f"prefill_batch={engines[0].prefill_batch} "
              f"prefill_chunk={engines[0].prefill_chunk} "
              f"prefill_token_budget={args.prefill_token_budget} "
              f"prefix_cache_blocks={args.prefix_cache_blocks}")

    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size, args.shared_prefix,
                          dtype=np.int32)
    handles = [gateway.submit(Request(
        np.concatenate([shared,
                        rng.integers(0, cfg.vocab_size,
                                     int(rng.integers(4, 12)),
                                     dtype=np.int32)]),
        SamplingParams(max_new_tokens=args.max_new, greedy=args.greedy,
                       temperature=args.temperature),
        tenant=tenants[i % len(tenants)]))
        for i in range(args.requests)]
    # drain manually so periodic snapshots can flush mid-run: a killed
    # capsule then leaves the last atomic snapshot, not nothing
    gateway.draining = True
    for rep in gateway.replicas:
        rep.scheduler.draining = True
    steps = 0
    while gateway.has_work:
        gateway.step()
        steps += 1
        if (args.metrics_out and args.metrics_interval_steps
                and steps % args.metrics_interval_steps == 0):
            atomic_write_json(args.metrics_out,
                              gateway.stats()["totals"])

    for i, h in enumerate(handles):
        rep = gateway.replicas[h[0]]
        print(f"req {i} [{rep.name}]: {gateway.result(h).tolist()}")
    stats = gateway.stats()
    tot = stats["totals"]
    print(f"{tot['total_new_tokens']} tokens over "
          f"{tot['requests_completed']} requests on "
          f"{tot['replicas']} replica(s): "
          f"{tot['tokens_per_s']:.1f} tok/s, "
          f"ttft p95 {tot['ttft_ms_p95']:.1f} ms, "
          f"latency p95 {tot['latency_ms_p95']:.1f} ms, "
          f"slot occupancy {tot['slot_occupancy']:.2f}")
    dg = tot.get("decode_gap_ms", {})
    if dg.get("count"):
        print(f"decode jitter: inter-token gap p50 {dg['p50']:.2f} ms, "
              f"p95 {dg['p95']:.2f} ms, max {dg['max']:.2f} ms "
              f"over {dg['count']} gaps")
    pc = tot.get("prefix_cache", {})
    if pc.get("hits", 0) or pc.get("misses", 0):
        print(f"prefix cache: hit rate {pc['hit_rate']:.2f}, "
              f"{pc['cached_tokens_served']}/{pc['prompt_tokens']} prompt "
              f"tokens served from cache, {pc['evictions']} evictions")
    if len(tenants) > 1 or tenants != ["default"]:
        for name, ts in sorted(tot.get("tenants", {}).items()):
            print(f"tenant {name}: {ts['requests_completed']} requests, "
                  f"{ts['tokens_per_s']:.1f} tok/s, "
                  f"ttft p95 {ts['ttft_ms']['p95']:.1f} ms, "
                  f"gap p95 {ts['decode_gap_ms']['p95']:.2f} ms, "
                  f"queue wait p95 {ts['queue_wait_ms']['p95']:.2f} ms")
    if slo_config is not None:
        for rep in gateway.replicas:
            mon = rep.scheduler.tracer.slo
            s = mon.summary()
            print(f"SLO [{rep.name}]: {s['breaches']} breach(es), "
                  f"active: {s['active'] or 'none'}")
    if args.profile:
        for rep in gateway.replicas:
            rs = rep.scheduler.engine.recompiles.summary()
            print(f"recompiles [{rep.name}]: {rs['compiles_total']} "
                  f"compilations, {rs['post_warm_recompiles']} post-warm, "
                  f"churning: {rs['churning'] or 'none'}")
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(stats, f, indent=2, sort_keys=True, default=str)
        print(f"metrics -> {args.metrics_json}")
    if args.metrics_out:
        out = atomic_write_json(args.metrics_out, stats["totals"])
        print(f"merged metrics summary -> {out}")
    if args.trace_out:
        if fabric_backend is not None:
            # worker streams land in the spool only at clean exit — stop
            # the fleet first, then merge gateway + worker events (no
            # chrome export: worker clocks are per-process monotonic)
            shutdown_fabric(gateway)
            n_ev = collect_fabric_traces(gateway, spool,
                                         f"{args.trace_out}.jsonl")
            print(f"fabric trace: {n_ev} merged events -> "
                  f"{args.trace_out}.jsonl (inspect: python "
                  f"scripts/trace_report.py --fleet "
                  f"{args.trace_out}.jsonl)")
        else:
            jsonl = gateway.export_trace_jsonl(f"{args.trace_out}.jsonl")
            chrome = gateway.export_chrome_trace(
                f"{args.trace_out}.chrome.json")
            n_ev = sum(tr.emitted_events for tr in gateway.tracers)
            n_drop = sum(tr.dropped_events for tr in gateway.tracers)
            print(f"trace: {n_ev} events ({n_drop} dropped by ring) -> "
                  f"{jsonl} + {chrome} "
                  f"(inspect: python scripts/trace_report.py {jsonl})")
    if fabric_backend is not None:
        shutdown_fabric(gateway)    # idempotent if the trace path ran


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
