#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cells, configurations, traffic mixes and metrics are the ones
BENCHMARK.json names; see perfbench/harness.py.  Without a TPU (or with
fewer chips than the cell asks for) it exits nonzero and prints no
result.  The last line of standard output is the result as one JSON
object; the numbers the correctness check compared, each beside its
limit, are the last lines of standard error.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main())
