#!/usr/bin/env sh
# One-invocation verify recipe: the repro-lint gate, the repo's tier-1
# test command (ROADMAP.md), then the failover gates.  Serving speed is
# measured on the chip by perfbench/, never here: the steps below check
# behaviour only.
#   - benchmarks.run --check runs fault_tolerance (kills 1 of 3 replicas
#     mid-burst; every salvaged request completes bit-identical) and
#     fabric (the same claim across real process boundaries: 3
#     subprocess workers over the mailbox transport, one SIGKILLed
#     mid-burst), then validates their BENCH_faults.json /
#     BENCH_fabric.json (salvage rate 1.0, no typed failures, nonzero
#     completed requests, bit_identical_outputs true).
#   - trace_report.py --validate schema-checks the two traces they
#     write and fails on an empty failure-handling or fleet section.
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
# --check exits nonzero on a FAILED row or an unhealthy BENCH_*.json
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.run \
    --only fault_tolerance,fabric --check
# schema + report gate on the fault-tolerance trace: the
# failure-handling section (health transitions, failovers, retries)
# must be populated
python scripts/trace_report.py --faults --validate \
    results/trace_faults.jsonl
# fleet gate on the merged cross-process fabric trace: per-replica
# worker streams plus the gateway's failover timeline must be populated
python scripts/trace_report.py --fleet --validate \
    results/trace_fabric.jsonl
