#!/usr/bin/env python
"""Offline text report / schema validator for serving trace JSONL files.

The serving stack's tracing is file-based by design (capsules on the
secure cluster cannot host a collector endpoint): the operator copies a
``*.jsonl`` event log out of the allocation and inspects it offline.
This script is the no-GUI half of that workflow — the Chrome trace file
covers the visual half in Perfetto.

Modes
-----
``python scripts/trace_report.py TRACE.jsonl``
    Render a text summary: top stall causes (admission stalls by reason,
    ``out_of_blocks`` by context), per-request critical path (queue wait
    -> time-to-first-token -> decode, with preemption counts), and
    prefill-budget utilization per engine step.  ``--slo`` adds the
    per-tenant SLO section (TTFT / inter-token-gap percentiles derived
    from the events, plus every ``slo_breach``); ``--profile`` adds the
    step-phase timing and ``recompile`` telemetry section; ``--faults``
    adds the failure-handling section (per-replica health transitions,
    failovers with salvage counts, retries, terminal request failures,
    and degradation edges); ``--fleet`` adds the per-replica rollup for
    merged cross-process fabric traces (one stream per worker process,
    clocks per-process monotonic).  ``--json PATH`` additionally writes
    the whole report machine-readable.

    A section with zero matching events is reported as EMPTY with a
    warning (a trace that yields an empty report used to read as a
    healthy run); the exit code stays 0 unless ``--validate`` is given.

``python scripts/trace_report.py --validate TRACE.jsonl [...]``
    CI gate: every line must parse as JSON and satisfy
    :func:`repro.serving.tracing.validate_event` — numeric ``ts``,
    ``kind`` from the documented ``EVENT_KINDS`` enum, integer ``step``
    and/or ``rid``, ``rid`` mandatory for request-scoped kinds.  Also
    fails (exit nonzero) when a core report section — request spans,
    engine steps — or an explicitly requested one (``--slo`` /
    ``--profile`` / ``--faults``) is empty.
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import Counter, defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.serving.metrics import _pct  # noqa: E402
from repro.serving.tracing import (EVENT_KINDS, SPANS,  # noqa: E402
                                   validate_event)


def load_events(path: Path) -> List[dict]:
    events = []
    with path.open() as f:
        for line in f:
            line = line.strip()
            if line:
                events.append(json.loads(line))
    return events


# ---------------------------------------------------------------------------
# --validate
# ---------------------------------------------------------------------------

def validate_file(path: Path, max_errors: int = 10) -> int:
    """Returns the number of schema violations (prints the first few)."""
    errors = 0
    with path.open() as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except json.JSONDecodeError as e:
                errors += 1
                if errors <= max_errors:
                    print(f"{path}:{lineno}: not JSON: {e}")
                continue
            err = validate_event(ev)
            if err is not None:
                errors += 1
                if errors <= max_errors:
                    print(f"{path}:{lineno}: {err}")
    if errors > max_errors:
        print(f"{path}: ... and {errors - max_errors} more violations")
    return errors


# ---------------------------------------------------------------------------
# report sections (each returns a machine-readable dict; "populated"
# means the trace held at least one event the section is made of)
# ---------------------------------------------------------------------------

def _span_key(ev: dict) -> Tuple[str, int]:
    return (ev.get("replica", ""), ev["rid"])


def _fmt_ms(dt: Optional[float]) -> str:
    return f"{dt * 1e3:9.2f}" if dt is not None else "        -"


def _stats_ms(xs: List[float]) -> Dict[str, float]:
    return {"p50": _pct(xs, 0.5) * 1e3, "p95": _pct(xs, 0.95) * 1e3,
            "max": max(xs, default=0.0) * 1e3,
            "mean": sum(xs) / len(xs) * 1e3 if xs else 0.0,
            "count": len(xs)}


def _request_spans(events: List[dict]) -> Dict[Tuple[str, int],
                                               Dict[str, object]]:
    spans: Dict[Tuple[str, int], Dict[str, object]] = defaultdict(dict)
    for ev in events:
        if "rid" not in ev or ev["rid"] < 0:
            continue
        sp = spans[_span_key(ev)]
        k = ev["kind"]
        if k == "submit":
            sp.setdefault(k, ev["ts"])
            sp["tenant"] = ev.get("tenant", "default")
        elif k in ("first_token", "retire"):
            sp.setdefault(k, ev["ts"])
        elif k == "admit":
            # first admission only: a resumed re-admit is not queue wait
            sp.setdefault("admit", ev["ts"])
        elif k == "preempt":
            sp["preempts"] = int(sp.get("preempts", 0)) + 1
        elif k == "decode":
            sp["decodes"] = int(sp.get("decodes", 0)) + 1
            sp.setdefault("decode_ts", []).append(ev["ts"])
        if k == "retire":
            sp["n_tokens"] = ev.get("n_tokens", 0)
            sp["reason"] = ev.get("reason", "?")
    return spans


def stalls_section(events: List[dict], top: int) -> dict:
    stalls: Counter = Counter()
    for ev in events:
        if ev["kind"] == "admission_stall":
            stalls[f"admission_stall:{ev.get('reason', '?')}"] += 1
        elif ev["kind"] == "out_of_blocks":
            stalls[f"out_of_blocks:{ev.get('context', '?')}"] += 1
        elif ev["kind"] == "preempt":
            stalls["preempt" + (":mid_prefill" if ev.get("mid_prefill")
                                else ":decode")] += 1
    print("\n== top stall causes ==")
    if not stalls:
        print("  none recorded")
    for cause, n in stalls.most_common(top):
        print(f"  {n:6d}  {cause}")
    return dict(stalls)


def requests_section(events: List[dict], top: int) -> dict:
    spans = _request_spans(events)

    def total(sp: Dict[str, object]) -> float:
        if "submit" in sp and "retire" in sp:
            return float(sp["retire"]) - float(sp["submit"])  # type: ignore
        return -1.0

    print("\n== per-request critical path (slowest first) ==")
    out = []
    print("  replica/rid       queue ms   ttft ms  decode ms  total ms"
          "  toks  preempts  reason")
    ranked = sorted(spans.items(), key=lambda kv: -total(kv[1]))
    for (replica, rid), sp in ranked:
        sub = sp.get("submit")
        adm = sp.get("admit")
        ft = sp.get("first_token")
        ret = sp.get("retire")
        queue = (adm - sub) if sub is not None and adm is not None else None
        ttft = (ft - sub) if sub is not None and ft is not None else None
        dec = (ret - ft) if ft is not None and ret is not None else None
        tot = (ret - sub) if sub is not None and ret is not None else None
        out.append({"replica": replica, "rid": rid,
                    "tenant": sp.get("tenant", "default"),
                    "queue_ms": queue * 1e3 if queue is not None else None,
                    "ttft_ms": ttft * 1e3 if ttft is not None else None,
                    "total_ms": tot * 1e3 if tot is not None else None,
                    "n_tokens": sp.get("n_tokens"),
                    "preempts": sp.get("preempts", 0),
                    "reason": sp.get("reason")})
    for (replica, rid), sp in ranked[:top]:
        sub, adm = sp.get("submit"), sp.get("admit")
        ft, ret = sp.get("first_token"), sp.get("retire")
        queue = (adm - sub) if sub is not None and adm is not None else None
        ttft = (ft - sub) if sub is not None and ft is not None else None
        dec = (ret - ft) if ft is not None and ret is not None else None
        tot = (ret - sub) if sub is not None and ret is not None else None
        label = f"{replica}/req{rid}" if replica else f"req{rid}"
        print(f"  {label:<16s} {_fmt_ms(queue)} {_fmt_ms(ttft)}"
              f" {_fmt_ms(dec)} {_fmt_ms(tot)}"
              f"  {sp.get('n_tokens', '?'):>4}"
              f"  {sp.get('preempts', 0):>8}"
              f"  {sp.get('reason', '?')}")
    if len(ranked) > top:
        print(f"  ... and {len(ranked) - top} more requests")
    return {"requests": out}


def steps_section(events: List[dict], top: int) -> dict:
    steps = [ev for ev in events if ev["kind"] == "engine_step"]
    budgeted = [ev for ev in steps if ev.get("budget", 0) > 0
                and ev.get("prefill_executed", 0) > 0]
    print("\n== engine steps ==")
    print(f"  {len(steps)} steps recorded, "
          f"{sum(1 for ev in steps if ev.get('decoded'))} decoded, "
          f"{len(budgeted)} ran budgeted prefill")
    data: dict = {"steps": len(steps),
                  "decoded": sum(1 for ev in steps if ev.get("decoded")),
                  "budgeted": len(budgeted)}
    if budgeted:
        utils = [ev["prefill_executed"] / ev["budget"] for ev in budgeted]
        data["budget_utilization_mean"] = sum(utils) / len(utils)
        print(f"  budget utilization: mean {sum(utils) / len(utils):.2f}, "
              f"min {min(utils):.2f}, max {max(utils):.2f} "
              f"(>1.0 = first chunk round of a step always runs whole)")
        print("  step  executed/budget  util   free_blocks  queue  active")
        for ev in budgeted[:top]:
            print(f"  {ev['step']:>4}  {ev['prefill_executed']:>8}/"
                  f"{ev['budget']:<6}  {ev['prefill_executed'] / ev['budget']:4.2f}"
                  f"   {ev.get('free_blocks', '?'):>10}"
                  f"  {ev.get('queue_depth', '?'):>5}"
                  f"  {ev.get('active', '?'):>6}")
        if len(budgeted) > top:
            print(f"  ... and {len(budgeted) - top} more budgeted steps")
    if steps:
        last = steps[-1]
        print(f"  final gauges: free_blocks={last.get('free_blocks', '?')} "
              f"free_slots={last.get('free_slots', '?')} "
              f"queue_depth={last.get('queue_depth', '?')} "
              f"inflight={last.get('inflight', '?')} "
              f"prefix_pins={last.get('prefix_pins', '?')}")
    return data


def slo_section(events: List[dict], top: int) -> dict:
    """Per-tenant TTFT / inter-token gap / queue wait derived from the
    request spans (tenant comes off the ``submit`` events), plus every
    ``slo_breach`` transition in the trace."""
    spans = _request_spans(events)
    per: Dict[str, Dict[str, List[float]]] = defaultdict(
        lambda: {"ttft": [], "gap": [], "queue": [], "requests": []})
    for sp in spans.values():
        tenant = str(sp.get("tenant", "default"))
        per[tenant]["requests"].append(1.0)
        sub, adm, ft = sp.get("submit"), sp.get("admit"), sp.get("first_token")
        if sub is not None and ft is not None:
            per[tenant]["ttft"].append(ft - sub)
        if sub is not None and adm is not None:
            per[tenant]["queue"].append(adm - sub)
        dts = sp.get("decode_ts", [])
        prev = ft
        for ts in dts:
            if prev is not None:
                per[tenant]["gap"].append(ts - prev)
            prev = ts
    breaches = [ev for ev in events if ev["kind"] == "slo_breach"]
    print("\n== SLO (per tenant) ==")
    data: dict = {"tenants": {}, "breaches": []}
    if not per:
        print("  no tenant-labelled requests recorded")
    else:
        print("  tenant            reqs  ttft p50/p95 ms    gap p50/p95 ms"
              "   queue p50/p95 ms")
        for tenant in sorted(per):
            d = per[tenant]
            ttft, gap, q = (_stats_ms(d["ttft"]), _stats_ms(d["gap"]),
                            _stats_ms(d["queue"]))
            data["tenants"][tenant] = {
                "requests": len(d["requests"]),
                "ttft_ms": ttft, "decode_gap_ms": gap, "queue_wait_ms": q}
            print(f"  {tenant:<16s} {len(d['requests']):>5} "
                  f"  {ttft['p50']:7.2f}/{ttft['p95']:<7.2f}"
                  f"   {gap['p50']:6.2f}/{gap['p95']:<7.2f}"
                  f"   {q['p50']:6.2f}/{q['p95']:<7.2f}")
    if breaches:
        print(f"  {len(breaches)} SLO transition(s):")
        for ev in breaches[:top]:
            state = "RECOVERED" if ev.get("recovered") else "BREACH"
            print(f"    step {ev.get('step', '?'):>4}  {state:<9s} "
                  f"{ev.get('tenant', '?')}/{ev.get('metric', '?')}: "
                  f"observed {ev.get('observed', 0.0):.2f} vs "
                  f"threshold {ev.get('threshold', 0.0):.2f}")
        if len(breaches) > top:
            print(f"    ... and {len(breaches) - top} more")
    else:
        print("  no SLO breaches recorded")
    data["breaches"] = [{k: ev.get(k) for k in
                         ("step", "tenant", "metric", "observed",
                          "threshold", "recovered")} for ev in breaches]
    return data


def profile_section(events: List[dict], top: int) -> dict:
    """Step-phase wall percentiles from the ``engine_step`` events plus
    jit ``recompile`` telemetry.  Each phase is a program span
    (``tracing.SPANS``) timed on the host clock: it measures the host's
    time in the phase, dispatch included; device time is in the
    profiler's trace, joined on ``step``."""
    steps = [ev for ev in events if ev["kind"] == "engine_step"]
    print("\n== profile ==")
    data: dict = {"phases": {}, "recompiles": {}}
    if not steps:
        print("  no engine_step events recorded")
    else:
        print("  phase                   p50 ms    p95 ms    max ms   total s")
        for phase, field in SPANS.items():
            if field is None:
                continue
            durs = [ev.get(field, 0.0) for ev in steps]
            st = _stats_ms(durs)
            st["total_s"] = sum(durs)
            data["phases"][phase] = st
            print(f"  {phase:<22s} {st['p50']:7.3f}  {st['p95']:8.3f}"
                  f"  {st['max']:8.3f}  {st['total_s']:8.3f}")
    rec = [ev for ev in events if ev["kind"] == "recompile"]
    if rec:
        by_prog: Dict[str, List[dict]] = defaultdict(list)
        for ev in rec:
            by_prog[str(ev.get("program", "?"))].append(ev)
        print(f"  {len(rec)} recompile warning(s) — shape churn:")
        for prog, evs in sorted(by_prog.items()):
            post = sum(1 for e in evs if e.get("post_warm"))
            data["recompiles"][prog] = {"warnings": len(evs),
                                        "post_warm": post}
            print(f"    {prog}: {len(evs)} novel signature(s), "
                  f"{post} post-warm — pad the wobbling dimension")
    else:
        print("  no recompile warnings (stable shapes)")
    return data


def faults_section(events: List[dict], top: int) -> dict:
    """Failure-handling timeline: health transitions, failovers with
    salvage counts, retries and terminal failures per replica, rejoins,
    and degradation (overload) edges."""
    health = [ev for ev in events if ev["kind"] == "replica_health"]
    failovers = [ev for ev in events if ev["kind"] == "replica_failover"]
    retries = [ev for ev in events if ev["kind"] == "replica_retry"]
    rejoins = [ev for ev in events if ev["kind"] == "replica_rejoin"]
    failed = [ev for ev in events if ev["kind"] == "request_failed"]
    overloads = [ev for ev in events if ev["kind"] in ("overload_shed",
                                                       "overload_cap")]
    print("\n== faults / failover ==")
    fault_events = (health + failovers + retries + rejoins + failed
                    + overloads)
    data: dict = {"fault_events": len(fault_events), "replicas": {},
                  "transitions": [], "failed_requests": [],
                  "overload": []}
    if not fault_events:
        print("  no fault-handling events recorded")
        return data
    per: Dict[str, Dict[str, int]] = defaultdict(
        lambda: {"transitions": 0, "failovers": 0, "salvaged": 0,
                 "retries_in": 0, "failed": 0, "rejoins": 0})
    for ev in health:
        per[ev.get("replica", "?")]["transitions"] += 1
    for ev in failovers:
        d = per[ev.get("replica", "?")]
        d["failovers"] += 1
        d["salvaged"] += (ev.get("salvaged_inflight", 0)
                          + ev.get("salvaged_queued", 0))
    for ev in retries:
        # stamped with the replica that *received* the retried request
        per[ev.get("replica", "?")]["retries_in"] += 1
    for ev in rejoins:
        per[ev.get("replica", "?")]["rejoins"] += 1
    for ev in failed:
        per[ev.get("replica", "?")]["failed"] += 1
    print("  replica           transitions  failovers  salvaged  "
          "retries-in  failed  rejoins")
    for name in sorted(per):
        d = per[name]
        data["replicas"][name] = dict(d)
        print(f"  {name:<16s} {d['transitions']:>12} {d['failovers']:>10}"
              f" {d['salvaged']:>9} {d['retries_in']:>11}"
              f" {d['failed']:>7} {d['rejoins']:>8}")
    if health:
        print(f"  {len(health)} health transition(s):")
        for ev in health[:top]:
            print(f"    {ev.get('replica', '?')}: {ev.get('old', '?')} -> "
                  f"{ev.get('new', '?')} ({ev.get('reason', '?')})")
        if len(health) > top:
            print(f"    ... and {len(health) - top} more")
    data["transitions"] = [{k: ev.get(k) for k in
                            ("replica", "old", "new", "reason")}
                           for ev in health]
    for ev in failed[:top]:
        print(f"    FAILED req{ev.get('rid', '?')} on "
              f"{ev.get('replica', '?')}: {ev.get('reason', '?')} after "
              f"{ev.get('attempts', '?')} attempt(s)")
    data["failed_requests"] = [{k: ev.get(k) for k in
                                ("replica", "rid", "reason", "attempts")}
                               for ev in failed]
    for ev in overloads[:top]:
        if ev["kind"] == "overload_shed":
            state = "RECOVERED" if ev.get("recovered") else "DEGRADED"
            print(f"    {state}: {ev.get('reason', '?')} "
                  f"(queue depth {ev.get('queue_depth', '?')})")
        else:
            print(f"    CAPPED req{ev.get('rid', '?')} "
                  f"({ev.get('tenant', '?')}): max_new "
                  f"{ev.get('orig_max_new', '?')} -> "
                  f"{ev.get('capped_max_new', '?')}")
    data["overload"] = [{k: ev.get(k) for k in
                         ("kind", "reason", "recovered", "queue_depth",
                          "tenant", "orig_max_new", "capped_max_new")}
                        for ev in overloads]
    return data


def fleet_section(events: List[dict], top: int) -> dict:
    """Per-replica rollup of a merged cross-process fabric trace: each
    worker exports its own stream (per-process monotonic clock, so spans
    are only meaningful within a replica) and the gateway contributes
    the failover timeline.  One row per replica — events, requests,
    completions, decode steps, failovers and retries received — plus the
    gateway's cross-replica failure counts."""
    per: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"events": 0, "requests": 0, "completed": 0,
                 "engine_steps": 0, "failovers": 0, "retries_in": 0,
                 "health_transitions": 0, "span_ms": 0.0})
    ts_range: Dict[str, List[float]] = defaultdict(list)
    for ev in events:
        name = ev.get("replica", "")
        d = per[name]
        d["events"] += 1
        ts_range[name].append(ev["ts"])
        k = ev["kind"]
        if k == "submit":
            d["requests"] += 1
        elif k == "retire":
            d["completed"] += 1
        elif k == "engine_step":
            d["engine_steps"] += 1
        elif k == "replica_failover":
            d["failovers"] += 1
        elif k == "replica_retry":
            d["retries_in"] += 1
        elif k == "replica_health":
            d["health_transitions"] += 1
    print("\n== fleet (per replica; clocks are per-process) ==")
    data: dict = {"replicas": {}, "failovers": 0, "retries": 0}
    if not per:
        print("  no replica-stamped events recorded")
        return data
    print("  replica           events  reqs  done  steps  failovers  "
          "retries-in  health  span ms")
    for name in sorted(per):
        d = per[name]
        tss = ts_range[name]
        d["span_ms"] = (max(tss) - min(tss)) * 1e3 if tss else 0.0
        data["replicas"][name or "(unstamped)"] = dict(d)
        data["failovers"] += int(d["failovers"])
        data["retries"] += int(d["retries_in"])
        print(f"  {name or '(unstamped)':<16s} {int(d['events']):>7}"
              f" {int(d['requests']):>5} {int(d['completed']):>5}"
              f" {int(d['engine_steps']):>6} {int(d['failovers']):>10}"
              f" {int(d['retries_in']):>11} {int(d['health_transitions']):>7}"
              f" {d['span_ms']:>8.1f}")
    print(f"  fleet: {len(per)} replica stream(s), "
          f"{data['failovers']} failover(s), "
          f"{data['retries']} retried request(s) received")
    return data


def report(events: List[dict], top: int = 10, slo: bool = False,
           profile: bool = False, faults: bool = False,
           fleet: bool = False) -> Tuple[dict, List[str]]:
    """Print the text report; returns ``(machine-readable data, names of
    empty sections)``.  A section is *empty* when the trace held zero of
    the events it is built from — distinct from a healthy zero (e.g. no
    stalls recorded is good news, so stalls never count as empty)."""
    data: dict = {"events": len(events)}
    if not events:
        print("empty trace: no events")
        return data, ["events"]
    t0 = min(ev["ts"] for ev in events)
    kinds = Counter(ev["kind"] for ev in events)
    replicas = sorted({ev.get("replica", "") for ev in events})
    print(f"{len(events)} events, {len(kinds)} kinds, "
          f"replicas: {', '.join(r or '(unstamped)' for r in replicas)}, "
          f"span {(max(ev['ts'] for ev in events) - t0) * 1e3:.1f} ms")
    data["kinds"] = dict(kinds)

    empty: List[str] = []
    data["stalls"] = stalls_section(events, top)
    data["requests"] = requests_section(events, top)
    if not data["requests"]["requests"]:
        empty.append("requests")
    data["engine_steps"] = steps_section(events, top)
    if not data["engine_steps"]["steps"]:
        empty.append("engine_steps")
    if slo:
        data["slo"] = slo_section(events, top)
        if not data["slo"]["tenants"]:
            empty.append("slo")
    if profile:
        data["profile"] = profile_section(events, top)
        if not data["profile"]["phases"]:
            empty.append("profile")
    if faults:
        data["faults"] = faults_section(events, top)
        if not data["faults"]["fault_events"]:
            empty.append("faults")
    if fleet:
        data["fleet"] = fleet_section(events, top)
        if not data["fleet"]["replicas"]:
            empty.append("fleet")
    if empty:
        print(f"\nwarning: empty report section(s): {', '.join(empty)} — "
              "the trace had zero matching events "
              "(fails under --validate)")
    return data, empty


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("traces", nargs="+", type=Path,
                    help="trace JSONL file(s)")
    ap.add_argument("--validate", action="store_true",
                    help="schema-check + fail on empty report sections")
    ap.add_argument("--slo", action="store_true",
                    help="add the per-tenant SLO section")
    ap.add_argument("--profile", action="store_true",
                    help="add the step-phase / recompilation section")
    ap.add_argument("--faults", action="store_true",
                    help="add the failure-handling section (health "
                         "transitions, failovers, retries, overload)")
    ap.add_argument("--fleet", action="store_true",
                    help="add the per-replica fleet section for merged "
                         "cross-process fabric traces")
    ap.add_argument("--json", type=Path, default=None, metavar="PATH",
                    help="also write the report machine-readable")
    ap.add_argument("--top", type=int, default=10,
                    help="rows per report section (default 10)")
    args = ap.parse_args(argv)

    bad = 0
    all_data: Dict[str, dict] = {}
    for path in args.traces:
        if len(args.traces) > 1 or args.validate:
            print(f"\n### {path}")
        if args.validate:
            n_events = sum(1 for line in path.open() if line.strip())
            errors = validate_file(path)
            bad += errors
            status = "OK" if errors == 0 else f"{errors} violations"
            print(f"{path}: {n_events} events, "
                  f"{len(EVENT_KINDS)} known kinds: {status}")
        data, empty = report(load_events(path), top=args.top,
                             slo=args.slo, profile=args.profile,
                             faults=args.faults, fleet=args.fleet)
        all_data[str(path)] = data
        if args.validate and empty:
            print(f"{path}: FAIL — empty section(s): {', '.join(empty)}")
            bad += len(empty)
    if args.json is not None:
        payload = (next(iter(all_data.values()))
                   if len(all_data) == 1 else all_data)
        args.json.parent.mkdir(parents=True, exist_ok=True)
        args.json.write_text(json.dumps(payload, indent=2, sort_keys=True,
                                        default=str) + "\n")
        print(f"\nwrote {args.json}")
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
