#!/usr/bin/env sh
# One-invocation verify recipe: the repo's tier-1 test command (ROADMAP.md),
# then fast smokes of the prefix-cache benchmark (cold/warm TTFT + the
# bit-identity assertion inside it), the paged-attention benchmark
# (paged > dense concurrency at equal KV bytes, undersized-pool run with
# no drops / no leaked pins, greedy bit-identity — each is asserted), and
# the batched-prefill and interleaved-prefill benchmarks via
# `benchmarks.run --check`, which also validates every emitted
# BENCH_*.json artifact (bit_identical_outputs true where present,
# nonzero completed requests) so a silently-broken benchmark fails the
# build.  The tracing benchmark (quick mode) asserts enabled-tracing
# wall clock within 5% of disabled and emits results/trace_sample.jsonl,
# which trace_report.py --validate then schema-checks (every event: ts,
# kind from the documented enum, step and/or rid) and renders with the
# SLO + profile sections, failing on any empty one.  The slo benchmark
# asserts the full observatory (per-tenant SLO monitor + span-timed step
# phases + recompile tracker) stays within the same 5% budget, bit-identical,
# with zero post-warm recompilations.
# Usage: scripts/ci.sh [extra pytest args]
# CI runs the full suite (including the slow-marked interleaved
# scheduler stress sweep); pass `-m "not slow"` for the quick tier.
set -e
cd "$(dirname "$0")/.."
# static gate first: repro-lint (src/repro/analysis) fails the build on
# any finding not in scripts/lint_baseline.json — hot-path syncs,
# recompile hazards, Pallas launch bugs, tracing-schema drift, and
# leak-shaped lifecycles are cheaper to catch before anything runs
python scripts/lint.py src benchmarks
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q "$@"
# invoked directly (not via benchmarks.run) so a failure fails the build
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.prefix_cache
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.paged_attention
# --check exits nonzero on a FAILED row or an unhealthy BENCH_*.json;
# fault_tolerance kills 1 of 3 replicas mid-burst and asserts every
# salvaged request completes bit-identical (salvage rate gated by
# _check_faults on BENCH_faults.json); fabric repeats the claim across
# real process boundaries — 3 subprocess workers over the mailbox
# transport, one SIGKILLed mid-burst (same gate, BENCH_fabric.json)
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.run \
    --only batched_prefill,interleaved,tracing,slo,fault_tolerance,fabric \
    --check
# trace JSONL schema + report gate on the sample the tracing benchmark
# just wrote: every event validates AND no report section (including the
# requested SLO/profile ones) is empty
python scripts/trace_report.py --slo --profile --validate \
    results/trace_sample.jsonl
# same gate on the fault-tolerance trace: the failure-handling section
# (health transitions, failovers, retries) must be populated
python scripts/trace_report.py --faults --validate \
    results/trace_faults.jsonl
# fleet gate on the merged cross-process fabric trace: per-replica
# worker streams plus the gateway's failover timeline must be populated
python scripts/trace_report.py --fleet --validate \
    results/trace_fabric.jsonl
