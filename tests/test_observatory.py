"""Serving observatory: per-tenant SLO monitoring, bounded percentile
windows, step/kernel profiling, recompilation telemetry, and the
trace_report SLO/profile sections.

Unit layers (SlidingWindow / TenantStats / SLOMonitor /
RecompilationTracker / StepProfiler) run against injected clocks; the
end-to-end tests drive real scheduler runs on the smoke model and pin
the contracts the benchmark relies on: tenant labels thread
submit -> scheduler -> summary -> merge, breach transitions land in the
trace as valid events, profiling is inert on outputs, and steady-state
serving never recompiles post-warm while injected shape churn does.
"""
import json

import jax
import numpy as np
import pytest

from repro.serving import (RecompilationTracker, Request, SamplingParams,
                           Scheduler, ServingEngine, ServingMetrics,
                           SLOConfig, SLOMonitor, SLOPolicy, SlidingWindow,
                           StepProfiler, TenantStats, Tracer,
                           atomic_write_json, merge_summaries,
                           merge_window_summaries, validate_event)
from repro.serving.metrics import _pct


@pytest.fixture(scope="module")
def qwen():
    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    cfg = get_smoke_config("qwen2-0.5b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(qwen, *, slots=3, seq=48, block=8, chunk=8, prefill_batch=2,
            **kw):
    cfg, params = qwen
    return ServingEngine(cfg, params, max_seq_len=seq, max_slots=slots,
                         kv_block_size=block, prefill_chunk=chunk,
                         prefill_batch=prefill_batch, **kw)


def _prompt(rng, cfg, n):
    return rng.integers(0, cfg.vocab_size, n, dtype=np.int32)


def _trace_report():
    import importlib
    import sys
    from pathlib import Path
    scripts = str(Path(__file__).resolve().parents[1] / "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    return importlib.import_module("trace_report")


def _ticker(dt=1.0):
    t = [0.0]

    def clock():
        t[0] += dt
        return t[0]
    return clock


# ---------------------------------------------------------------------------
# SlidingWindow: bounded memory, exact small-N percentiles (satellite a)
# ---------------------------------------------------------------------------

def test_sliding_window_small_n_matches_exact_percentiles():
    """Below the cap the ring holds everything: percentiles must equal
    the unbounded ``_pct`` over the full sample list, bit for bit."""
    w = SlidingWindow(window=64)
    xs = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.0]
    for x in xs:
        w.add(x)
    for q in (0.0, 0.25, 0.5, 0.9, 0.95, 1.0):
        assert w.percentile(q) == _pct(xs, q)
    s = w.summary()
    assert s["count"] == len(xs)
    assert s["max"] == max(xs)
    assert s["mean"] == pytest.approx(sum(xs) / len(xs))


def test_sliding_window_caps_memory_but_keeps_totals_exact():
    w = SlidingWindow(window=16)
    n = 1000
    for i in range(n):
        w.add(float(i))
    assert len(w.ring) == 16                     # bounded
    assert w.count == n and w.peak == float(n - 1)
    assert w.mean == pytest.approx(sum(range(n)) / n)
    # percentiles are over the most recent 16 samples only
    assert w.percentile(0.5) == _pct([float(i) for i in range(n - 16, n)],
                                     0.5)
    with pytest.raises(ValueError, match="window"):
        SlidingWindow(window=0)


def test_merge_window_summaries_skips_empty_windows():
    busy = SlidingWindow(8)
    for x in (10.0, 20.0, 30.0):
        busy.add(x)
    idle = SlidingWindow(8)
    merged = merge_window_summaries([busy.summary(), idle.summary()])
    assert merged == busy.summary()              # idle contributed nothing
    assert merge_window_summaries([])["count"] == 0


# ---------------------------------------------------------------------------
# ServingMetrics: bounded per-request samples (satellite a)
# ---------------------------------------------------------------------------

def test_metrics_sample_cap_bounds_dicts_totals_stay_exact():
    m = ServingMetrics(clock=_ticker(), sample_cap=4)
    for rid in range(20):
        m.record_submit(rid)
        m.record_admit(rid)
        m.record_first_token(rid)
        m.record_finish(rid, 2, "length")
    # only the most recent 4 finished rids keep per-request entries
    assert len(m._submit) == 4 and len(m._finish) == 4
    assert set(m._finish) == {16, 17, 18, 19}
    # running totals never evicted
    s = m.summary()
    assert s["requests_completed"] == 20
    assert s["total_new_tokens"] == 40
    assert s["finish_reasons"] == {"length": 20}
    assert s["queue_wait_ms"]["count"] == 20     # window count is all-time
    with pytest.raises(ValueError, match="sample_cap"):
        ServingMetrics(sample_cap=0)


def test_metrics_below_cap_percentiles_unchanged_by_cap():
    """Small runs must see byte-identical numbers whatever the cap: the
    cap only changes behavior beyond ``sample_cap`` finished requests."""
    def run(cap):
        m = ServingMetrics(clock=_ticker(0.5), sample_cap=cap)
        for rid in range(6):
            m.record_submit(rid, tenant="t")
            m.record_admit(rid)
            m.record_first_token(rid)
            m.record_finish(rid, 3, "length")
        return m.summary()

    small, big = run(8), run(4096)
    assert small["ttft_ms"] == big["ttft_ms"]
    assert small["queue_wait_ms"] == big["queue_wait_ms"]
    assert small["tenants"] == big["tenants"]


def test_atomic_write_json_leaves_no_tmp(tmp_path):
    out = tmp_path / "nested" / "totals.json"
    p = atomic_write_json(out, {"a": 1, "path": tmp_path})
    assert p == out
    assert json.loads(out.read_text())["a"] == 1
    assert list(tmp_path.glob("**/*.tmp")) == []
    # overwrite is atomic too (same name, replaced content)
    atomic_write_json(out, {"a": 2})
    assert json.loads(out.read_text())["a"] == 2


# ---------------------------------------------------------------------------
# tenant threading + merge (satellite c)
# ---------------------------------------------------------------------------

def test_tenant_stats_thread_through_metrics():
    m = ServingMetrics(clock=_ticker())
    m.record_submit(0, tenant="a")
    m.record_submit(1, tenant="b")
    m.record_admit(0)
    m.record_admit(1)
    m.record_first_token(0)
    m.record_first_token(1)
    m.record_decode_tokens([0, 1])
    m.record_decode_tokens([0, 1])
    m.record_finish(0, 3, "length")
    m.record_finish(1, 3, "length")
    t = m.summary()["tenants"]
    assert set(t) == {"a", "b"}
    for name in ("a", "b"):
        assert t[name]["requests_completed"] == 1
        assert t[name]["ttft_ms"]["count"] == 1
        assert t[name]["queue_wait_ms"]["count"] == 1
        assert t[name]["decode_gap_ms"]["count"] == 2
        assert t[name]["ttft_ms"]["p95"] > 0


def test_merge_summaries_disjoint_tenants_pass_through():
    def mk(tenant):
        m = ServingMetrics(clock=_ticker())
        m.record_submit(0, tenant=tenant)
        m.record_admit(0)
        m.record_first_token(0)
        m.record_finish(0, 4, "length")
        return m.summary()

    sa, sb = mk("a"), mk("b")
    merged = merge_summaries([sa, sb])["tenants"]
    assert set(merged) == {"a", "b"}
    assert merged["a"] == sa["tenants"]["a"]     # disjoint: unchanged
    assert merged["b"] == sb["tenants"]["b"]


def test_merge_summaries_overlapping_tenants_merge_windows():
    def mk(ttft_dt):
        m = ServingMetrics(clock=_ticker(ttft_dt))
        m.record_submit(0, tenant="shared")
        m.record_admit(0)
        m.record_first_token(0)
        m.record_finish(0, 4, "length")
        return m.summary()

    fast, slow = mk(0.1), mk(0.9)
    merged = merge_summaries([fast, slow])["tenants"]["shared"]
    assert merged["requests_completed"] == 2
    assert merged["new_tokens"] == 8
    # percentile merge is the conservative max across replicas
    assert merged["ttft_ms"]["p95"] == pytest.approx(
        max(fast["tenants"]["shared"]["ttft_ms"]["p95"],
            slow["tenants"]["shared"]["ttft_ms"]["p95"]))
    assert merged["ttft_ms"]["count"] == 2


def test_zero_decode_replica_does_not_dilute_tenant_jitter():
    """PR 5 regression extended to tenants: an idle replica (zero decode
    gaps, zero tenant samples) must leave both the fleet jitter numbers
    and the per-tenant windows of the busy replica exactly unchanged."""
    busy = ServingMetrics(clock=_ticker(0.25))
    busy.record_submit(0, tenant="t")
    busy.record_admit(0)
    busy.record_first_token(0)
    for _ in range(3):
        busy.record_decode_tokens([0])
        busy.sample_gauges(0, 1, 2)
    busy.record_finish(0, 4, "length")
    bs = busy.summary()
    idle = ServingMetrics(clock=lambda: 0.0).summary()
    merged = merge_summaries([bs, idle])
    assert merged["decode_gap_ms"] == bs["decode_gap_ms"]
    assert merged["tenants"]["t"]["decode_gap_ms"] == \
        bs["tenants"]["t"]["decode_gap_ms"]
    assert merged["tenants"]["t"]["ttft_ms"] == bs["tenants"]["t"]["ttft_ms"]


# ---------------------------------------------------------------------------
# SLO policies + monitor
# ---------------------------------------------------------------------------

def test_slo_config_json_roundtrip_and_unknown_key_rejection(tmp_path):
    doc = {"default": {"ttft_p95_ms": 500.0, "min_samples": 4},
           "tenants": {"premium": {"ttft_p95_ms": 200.0,
                                   "min_tokens_per_s": 10.0}}}
    path = tmp_path / "slo.json"
    path.write_text(json.dumps(doc))
    cfg = SLOConfig.from_json(path)
    assert cfg.default.ttft_p95_ms == 500.0
    assert cfg.default.min_samples == 4
    assert cfg.policy_for("premium").ttft_p95_ms == 200.0
    assert cfg.policy_for("premium").min_tokens_per_s == 10.0
    assert cfg.policy_for("anyone-else") is cfg.default
    # roundtrip through to_dict parses back to the same policies
    again = SLOConfig.from_dict(cfg.to_dict())
    assert again.default == cfg.default
    assert again.tenants == cfg.tenants
    with pytest.raises(ValueError, match="unknown SLO policy keys"):
        SLOPolicy.from_dict({"ttft_p95": 1.0})   # typo'd key fails loudly


def _stats_with(ttft_ms_samples, completed=0, tokens=0, span=None):
    ts = TenantStats()
    for x in ttft_ms_samples:
        ts.ttft_ms.add(x)
    ts.completed = completed
    ts.new_tokens = tokens
    if span is not None:
        ts.first_submit_ts, ts.last_finish_ts = 0.0, span
    return ts


def test_slo_monitor_edge_triggered_breach_and_recovery():
    cfg = SLOConfig(SLOPolicy(ttft_p95_ms=100.0, min_samples=2))
    mon = SLOMonitor(cfg)
    bad = {"t": _stats_with([150.0, 160.0])}
    trans = mon.evaluate(bad)
    assert len(trans) == 1 and trans[0]["recovered"] is False
    assert trans[0]["metric"] == "ttft_p95_ms"
    assert mon.breaches == 1
    # sustained breach: no new transition, no new count
    assert mon.evaluate(bad) == []
    assert mon.breaches == 1
    assert mon.active_breaches() == [{"tenant": "t",
                                      "metric": "ttft_p95_ms"}]
    # recovery is one transition with the flag set
    good = {"t": _stats_with([150.0, 160.0] + [10.0] * 30)}
    trans = mon.evaluate(good)
    assert len(trans) == 1 and trans[0]["recovered"] is True
    assert mon.breaches == 1                      # recoveries don't count
    assert mon.active_breaches() == []
    assert mon.summary()["breaches"] == 1


def test_slo_monitor_min_samples_gates_verdicts():
    mon = SLOMonitor(SLOConfig(SLOPolicy(ttft_p95_ms=1.0, min_samples=8)))
    thin = {"t": _stats_with([999.0] * 7)}        # breach-worthy but thin
    assert mon.evaluate(thin) == []
    thin["t"].ttft_ms.add(999.0)                  # 8th sample: verdict
    assert len(mon.evaluate(thin)) == 1


def test_slo_monitor_throughput_lower_bound():
    pol = SLOPolicy(min_tokens_per_s=100.0, min_samples=1)
    mon = SLOMonitor(SLOConfig(pol))
    slow = {"t": _stats_with([], completed=2, tokens=10, span=1.0)}
    trans = mon.evaluate(slow)
    assert len(trans) == 1 and trans[0]["metric"] == "min_tokens_per_s"
    fast = {"t": _stats_with([], completed=2, tokens=1000, span=1.0)}
    assert mon.evaluate(fast)[0]["recovered"] is True


# ---------------------------------------------------------------------------
# recompilation telemetry
# ---------------------------------------------------------------------------

def test_recompilation_tracker_counts_and_warm_semantics():
    rt = RecompilationTracker()
    assert rt.observe("decode", ((4,), (4,))) is True    # first compile
    assert rt.observe("decode", ((4,), (4,))) is False   # cache hit
    assert rt.observe("decode", ((5,), (5,))) is True    # second signature
    assert rt.compiles("decode") == 2 and rt.compiles() == 2
    assert rt.post_warm_recompiles == 0                  # not warm yet
    rt.mark_warm()
    assert rt.observe("decode", ((6,), (6,))) is True
    assert rt.post_warm_recompiles == 1
    s = rt.summary()
    assert s["warm"] and s["compiles_total"] == 3
    assert s["programs"]["decode"] == {"signatures": 3, "post_warm": 1}
    assert "decode" in s["churning"]


def test_recompile_warnings_reach_the_tracer():
    rt = RecompilationTracker()
    tr = Tracer(enabled=True, clock=_ticker())
    rt.observe("p", (1,), tracer=tr)          # first signature: silent
    assert [e["kind"] for e in tr.snapshot()] == []
    rt.observe("p", (2,), tracer=tr)          # churn before warm: warns
    rt.mark_warm()
    rt.observe("q", (1,), tracer=tr)          # post-warm novelty: warns
    evs = tr.snapshot()
    assert [e["kind"] for e in evs] == ["recompile", "recompile"]
    assert evs[0]["post_warm"] is False and evs[1]["post_warm"] is True
    for ev in evs:
        assert validate_event(ev) is None


def test_steady_state_zero_postwarm_then_injected_churn_warns(qwen):
    """The benchmark's recompile contract as a test: replaying the same
    workload after ``mark_warm`` must be signature-stable, and a decode
    batch whose padding wobbles must raise the counter AND emit tracer
    warnings."""
    cfg, _ = qwen
    eng = _engine(qwen, paged=True)
    rng = np.random.default_rng(11)
    prompts = [_prompt(rng, cfg, n) for n in (5, 17, 9)]

    def serve():
        sched = Scheduler(eng, tracer=Tracer())
        for p in prompts:
            sched.submit(Request(p, SamplingParams(max_new_tokens=3,
                                                   greedy=True)))
        sched.run()

    serve()
    assert eng.recompiles.compiles() > 0
    eng.recompiles.mark_warm()
    serve()                                    # steady state: same shapes
    assert eng.recompiles.post_warm_recompiles == 0, (
        f"replaying an identical workload recompiled: "
        f"{eng.recompiles.summary()}")
    # inject the classic variable-batch bug: sample batches sized past
    # anything serving produced (> max_slots rows) genuinely recompile
    eng.tracer = Tracer(enabled=True)
    V = cfg.vocab_size
    for k in (4, 5):                           # max_slots is 3
        eng.sample_tokens(np.zeros((k, V), np.float32),
                          np.zeros(k, np.float32), np.ones(k, bool))
    assert eng.recompiles.post_warm_recompiles >= 2
    warns = [e for e in eng.tracer.snapshot() if e["kind"] == "recompile"]
    assert len(warns) >= 2
    assert all(w["program"] == "sample" and w["post_warm"] for w in warns)
    assert "sample" in eng.recompiles.churning_programs()


# ---------------------------------------------------------------------------
# step profiler
# ---------------------------------------------------------------------------

def test_step_profiler_windows():
    prof = StepProfiler(window=4)
    for i in range(10):
        prof.record_step(0.001, 0.002 * i, 0.003, 0.0)
    s = prof.summary()
    assert s["steps"] == 10
    assert s["admit_ms"]["count"] == 10
    assert s["admit_ms"]["p50"] == pytest.approx(1.0)
    assert s["prefill_ms"]["max"] == pytest.approx(18.0)
    assert s["sample_ms"]["p95"] == 0.0


def test_profiling_populates_phases_and_is_inert_on_outputs(qwen):
    cfg, _ = qwen
    rng = np.random.default_rng(12)
    prompts = [_prompt(rng, cfg, n) for n in (7, 13)]
    eng = _engine(qwen, paged=True)

    def serve(profile):
        sched = Scheduler(eng, tracer=Tracer(), profile=profile)
        rids = [sched.submit(Request(p, SamplingParams(max_new_tokens=3,
                                                       greedy=True)))
                for p in prompts]
        sched.run()
        return [sched.output(r) for r in rids], sched.profiler

    plain_out, none_prof = serve(False)
    prof_out, prof = serve(True)
    assert none_prof is None
    for a, b in zip(plain_out, prof_out):
        np.testing.assert_array_equal(a, b)    # profiling is inert
    s = prof.summary()
    assert s["steps"] > 0
    for phase in ("admit", "prefill", "decode", "sample"):
        st = s[f"{phase}_ms"]
        assert st["count"] == s["steps"]
        assert st["max"] >= 0.0
    # the decode phase of a real run takes measurable device time
    assert s["decode_ms"]["max"] > 0.0


def test_profile_paged_kernels_structure(qwen):
    from repro.serving import profile_paged_kernels
    eng = _engine(qwen, paged=True)
    profs = profile_paged_kernels(eng, reps=1)
    assert set(profs) == {"paged_attention", "paged_prefill"}
    for prof in profs.values():
        assert prof["wall_ms_median"] > 0.0
        assert prof["flops"] > 0.0
        assert prof["bytes_accessed"] > 0.0
        assert prof["arithmetic_intensity"] > 0.0
        # the CPU has no published peak: fractions are "not measured"
        assert prof["device"] == "cpu"
        assert prof["fraction_of_peak_flops"] is None
        assert prof["fraction_of_peak_bw"] is None
    with pytest.raises(ValueError, match="paged"):
        profile_paged_kernels(_engine(qwen))   # dense engine refused


# ---------------------------------------------------------------------------
# end-to-end: tenants + SLO breaches through a real run, then the report
# ---------------------------------------------------------------------------

def test_observatory_end_to_end_and_trace_report(qwen, tmp_path, capsys):
    cfg, _ = qwen
    rng = np.random.default_rng(13)
    # impossible TTFT bound so the run provably breaches
    slo = SLOConfig.from_dict({
        "default": {"ttft_p95_ms": 1e9},
        "tenants": {"gold": {"ttft_p95_ms": 1e-6, "min_samples": 1}}})
    tracer = Tracer(enabled=True, slo=SLOMonitor(slo))
    sched = Scheduler(_engine(qwen, paged=True), tracer=tracer,
                      profile=True)
    for i in range(4):
        sched.submit(Request(
            _prompt(rng, cfg, int(rng.integers(5, 20))),
            SamplingParams(max_new_tokens=3, greedy=True),
            tenant="gold" if i % 2 == 0 else "basic"))
    sched.run()

    # tenant labels threaded end-to-end into the summary
    t = sched.metrics.summary()["tenants"]
    assert set(t) == {"gold", "basic"}
    assert sum(x["requests_completed"] for x in t.values()) == 4
    assert all(x["ttft_ms"]["count"] == 2 for x in t.values())
    assert all(x["queue_wait_ms"]["count"] == 2 for x in t.values())
    # only the tenant with the impossible policy breached
    assert tracer.slo.breaches >= 1
    assert {b["tenant"] for b in tracer.slo.active_breaches()} == {"gold"}
    breaches = [e for e in tracer.snapshot() if e["kind"] == "slo_breach"]
    assert breaches and all(validate_event(e) is None for e in breaches)
    assert all(e["tenant"] == "gold" for e in breaches)

    # the exported trace renders the SLO + profile report sections
    jsonl = tracer.export_jsonl(tmp_path / "obs.jsonl")
    trace_report = _trace_report()
    out_json = tmp_path / "report.json"
    rc = trace_report.main([str(jsonl), "--slo", "--profile",
                            "--validate", "--json", str(out_json)])
    assert rc == 0, capsys.readouterr().out
    data = json.loads(out_json.read_text())
    assert set(data["slo"]["tenants"]) == {"gold", "basic"}
    assert data["slo"]["breaches"]
    assert all(b["tenant"] == "gold" for b in data["slo"]["breaches"])
    assert set(data["profile"]["phases"]) == {"admit", "prefill",
                                              "decode", "sample"}
    assert data["requests"]["requests"]
    capsys.readouterr()                        # drain the report text


def test_trace_report_empty_sections_warn_and_fail_validate(tmp_path,
                                                            capsys):
    trace_report = _trace_report()
    # a schema-valid trace with engine steps but zero request spans
    path = tmp_path / "steps_only.jsonl"
    path.write_text(json.dumps({"ts": 0.0, "kind": "engine_step",
                                "step": 0}) + "\n")
    rc = trace_report.main([str(path)])
    out = capsys.readouterr().out
    assert rc == 0                              # warn-only by default
    assert "empty report section(s): requests" in out
    rc = trace_report.main([str(path), "--validate"])
    out = capsys.readouterr().out
    assert rc == 1                              # CI mode fails
    assert "FAIL" in out and "requests" in out
    # requesting --slo on a tenant-less trace is an empty section too
    assert trace_report.main([str(path), "--slo", "--validate"]) != 0
    capsys.readouterr()


def test_serve_launcher_observatory_flags(qwen, tmp_path, capsys):
    """The CLI path (satellite b): --tenant/--slo-config/--profile/
    --metrics-out with periodic atomic flushes."""
    from repro.launch import serve
    slo_path = tmp_path / "slo.json"
    slo_path.write_text(json.dumps(
        {"default": {"ttft_p95_ms": 1e-6, "min_samples": 1}}))
    metrics = tmp_path / "totals.json"
    serve.main(["--arch", "qwen2-0.5b", "--smoke", "--requests", "3",
                "--max-new", "2", "--greedy", "--max-slots", "3",
                "--max-seq-len", "48", "--tenant", "a,b",
                "--slo-config", str(slo_path), "--profile",
                "--metrics-out", str(metrics),
                "--metrics-interval-steps", "1"])
    out = capsys.readouterr().out
    assert "tenant a:" in out and "tenant b:" in out
    assert "SLO [replica0]:" in out
    assert "profile [replica0]:" in out and "recompiles [replica0]:" in out
    totals = json.loads(metrics.read_text())
    assert totals["requests_completed"] == 3
    assert set(totals["tenants"]) == {"a", "b"}
    assert totals["slo_breaches"] >= 1
    assert list(tmp_path.glob("*.tmp")) == []   # atomic flushes cleaned up
