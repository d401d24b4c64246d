#!/usr/bin/env python3
"""Readings that the correctness limits are set from (run on the chip).

    python3 perfbench/tools/readings.py --workload <name> --seeds 1,2,3 \
        --seconds <s> [--out <file.jsonl>]

Runs the cell once per seed, in one process, and for each run prints the
widest served-token logit gap of the program and of the float8 control
over the same sampled requests, with the run's end-to-end metrics.  The
benchmark's own runs never compute the control.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    chips = harness.find_chips(cell.workload["chips"])
    harness.use_compile_cache()
    out = open(args.out, "a") if args.out else None
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = harness.run(cell, seed, args.seconds, False, chips=chips,
                          check_modes=("f32", "fp8"),
                          log=lambda s: print(s, file=sys.stderr, flush=True))
        rd = res.pop("_check")
        line = {"workload": args.workload, "seed": seed,
                "logit_gap": rd["logit_gap"],
                "logit_gap_fp8": rd["logit_gap_fp8"],
                "per_request": rd["per_request"],
                "per_request_fp8": rd["per_request_fp8"],
                "tokens": rd["tokens"], "requests": rd["requests"],
                "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                "attempted": res["attempted"], "failed": res["failed"],
                "device": res["device"]}
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
