#!/usr/bin/env python3
"""Find the highest session rate a chat cell sustains (run on the chip).

    python3 perfbench/tools/sweep.py --workload <name> --rates 0.2,0.4 \
        --seconds <s> --seeds 1,2

Runs the cell once per offered rate and seed, in one process, and prints
for each run the end-to-end metrics and the backlog: the median time to
first token of the requests due in the first and in the last third of the
window, and the queue depth when the window closed.  A rate is sustained
when, on every seed, the last third waits no longer than the first.  The cell then offers a fixed rate,
set in its mix from this sweep; the benchmark never searches for one.
"""
import argparse
import copy
import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="1")
    args = ap.parse_args()
    base = harness.load_cell(args.workload)
    chips = harness.find_chips(base.workload["chips"])
    harness.use_compile_cache()
    seeds = [int(x) for x in args.seeds.split(",")]
    for rate in [float(r) for r in args.rates.split(",")]:
        for seed in seeds:
            cell = copy.deepcopy(base)
            cell.mix["traffic"]["session_rate"] = rate
            res = harness.run(
                cell, seed, args.seconds, False, chips=chips, check_modes=(),
                log=lambda s: print(s, file=sys.stderr, flush=True))
            smp = res["_samples"]
            w0, w1 = smp["window"]
            third = (w1 - w0) / 3
            first = [w for d, w in smp["first_token"] if d < w0 + third]
            last = [w for d, w in smp["first_token"] if d >= w1 - third]
            a, b = (statistics.median(x) if x else None
                    for x in (first, last))
            print(json.dumps({
                "rate": rate, "seed": seed,
                "ttft_first_third_ms": a, "ttft_last_third_ms": b,
                "sustained": None if None in (a, b) else b <= a,
                "queue_at_close": smp["queue_at_close"],
                "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                "attempted": res["attempted"], "failed": res["failed"]}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
