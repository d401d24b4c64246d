"""How ``correct`` is decided for a served model.

Once the window has closed, a sample of the requests the program finished
is drawn from the seed, the longest of them always in it.  The plain
float32 reference (``references/<config's reference>.py``) runs once over
each prompt followed by its served tokens; for every served token the gap
by which the reference's logit of that token lies below the reference's
best logit at that position is read.  The widest gap over the sample is
compared with the cell's limit in ``limits/<workload>.json``.  Every
served token is greedy, so a sound program serves, at every position, a
token the reference ranks at or near the top; the tie margin of the
program's greedy sampler and bfloat16 rounding set how near.

The control (``mode="fp8"``, never run by the benchmark's own runs) reads
the same gap for the token the reference computed in float8 puts first.
"""
from __future__ import annotations

import importlib
import json
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent


def pick(finished: list, seed: int, spec: dict, w0: float) -> list:
    """Requests to compare: the longest finished request due in the window
    and others drawn from ``seed``, until ``spec["requests"]`` requests and
    ``spec["tokens"]`` served tokens are in (or the candidates run out;
    requests finished before the window are taken only if those due in it
    fall short)."""
    cands = [r for r in finished if r.due >= w0]
    if sum(r.max_new for r in cands) < spec["tokens"]:
        cands = list(finished)
    if not cands:
        return []
    cands.sort(key=lambda r: r.key)
    longest = max(cands, key=lambda r: (len(r.prompt) + r.max_new, r.key))
    rest = [r for r in cands if r is not longest]
    order = np.random.default_rng([seed & 0xFFFFFFFFFFFFFFFF, 5]
                                  ).permutation(len(rest))
    out = [longest]
    for i in order:
        if (len(out) >= spec["requests"]
                and sum(r.max_new for r in out) >= spec["tokens"]):
            break
        out.append(rest[i])
    return out


def reference(config: dict):
    return importlib.import_module(
        f"perfbench.references.{config['reference']}")


def readings(weights, config: dict, sample: list,
             modes=("f32",)) -> Dict[str, object]:
    """Per-request widest gaps of the served tokens (``logit_gap``) and,
    for ``"fp8"`` in ``modes``, of the control's first choices."""
    ref = reference(config)
    gaps: List[float] = []
    ctl: List[float] = []
    n_tok = 0
    for r in sample:
        served = np.asarray(r.tokens[:r.max_new], np.int32)
        seq = np.concatenate([np.asarray(r.prompt, np.int32), served[:-1]])
        rows = np.arange(len(r.prompt) - 1, len(seq))
        want = ref.logits(weights, config, seq, rows, mode="f32")
        best = want.max(axis=1)
        gaps.append(float(np.max(best - want[np.arange(len(rows)), served])))
        n_tok += len(served)
        if "fp8" in modes:
            low = ref.logits(weights, config, seq, rows, mode="fp8")
            pick_ = low.argmax(axis=1)
            ctl.append(float(np.max(best - want[np.arange(len(rows)),
                                                pick_])))
    out: Dict[str, object] = {
        "logit_gap": max(gaps) if gaps else None,
        "requests": len(sample), "tokens": n_tok, "per_request": gaps}
    if "fp8" in modes:
        out["logit_gap_fp8"] = max(ctl) if ctl else None
        out["per_request_fp8"] = ctl
    return out


def limits(workload: str) -> Optional[dict]:
    p = HERE / "limits" / f"{workload}.json"
    return json.loads(p.read_text()) if p.exists() else None


def verdict(readings: dict, lim: Optional[dict], spec: dict, failed: int):
    """(correct, checks): every number compared beside its limit."""
    gap = readings["logit_gap"]
    checks = {
        "logit_gap": {"value": gap,
                      "limit": lim["logit_gap"] if lim else None},
        "tokens_compared": {"value": readings["tokens"],
                            "limit": spec["tokens"]},
        "requests_failed": {"value": failed, "limit": 0},
    }
    ok = (lim is not None and gap is not None
          and gap <= lim["logit_gap"]
          and readings["tokens"] >= spec["tokens"] and failed == 0)
    return ok, checks
