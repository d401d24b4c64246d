"""Request-lifecycle tracing: event schema, ring buffer, preemption
observability, Chrome/JSONL exporters, gateway trace merge, program
spans in the profiler's trace joined to the ring on ``step``, and the
``merge_summaries`` edge-case contract.

Also hosts the executable form of the ROADMAP near-tie caveat: a
slow-marked sweep asserting that any paged-vs-dense greedy divergence
happens only at near-tie top-2 logits (page-wise online-softmax
summation order), never at a decisive argmax.
"""
import collections
import glob
import json

import jax
import numpy as np
import pytest

from repro.serving import (EVENT_KINDS, SPANS, ReplicaGateway, Request,
                           SamplingParams, Scheduler, ServingEngine,
                           Tracer, export_chrome_trace, merge_summaries,
                           merge_traces, to_chrome_trace, validate_event)


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


def _serve_traced(qwen, prompts, max_news, **eng_kw):
    tracer = Tracer(enabled=True)
    sched = Scheduler(_engine(qwen, **eng_kw), tracer=tracer)
    cfg, _ = qwen
    rids = [sched.submit(Request(p, SamplingParams(max_new_tokens=m,
                                                   greedy=True)))
            for p, m in zip(prompts, max_news)]
    sched.run()
    return tracer, sched, rids


# ---------------------------------------------------------------------------
# tracer mechanics (no model needed)
# ---------------------------------------------------------------------------

def test_disabled_tracer_records_no_events_but_feeds_metrics():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    tr = Tracer(enabled=False, clock=clock)
    tr.submit(0)
    tr.first_token(0)
    tr.retire(0, 5, "length")
    tr.prefix_probe(1, 4, 10)
    assert len(tr.events) == 0 and tr.emitted_events == 0
    s = tr.metrics.summary()
    assert s["requests_completed"] == 1
    assert s["prefix_cache"]["hits"] == 1
    assert s["prefix_cache"]["cached_tokens_served"] == 4


def test_ring_buffer_bounds_and_counts_drops():
    tr = Tracer(enabled=True, buffer_events=4, clock=lambda: 0.0)
    for rid in range(10):
        tr.submit(rid)
    assert len(tr.events) == 4
    assert tr.emitted_events == 10 and tr.dropped_events == 6
    assert [ev["rid"] for ev in tr.events] == [6, 7, 8, 9]  # oldest drop
    with pytest.raises(ValueError, match="buffer_events"):
        Tracer(buffer_events=0)


def test_event_schema_and_validator():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    tr = Tracer(enabled=True, clock=clock)
    tr.submit(3)
    tr.bind_slot(0, 3)
    tr.block_alloc(0, 2, 10)           # resolves rid through the binding
    tr.engine_step(decoded=False, queue_depth=1, active=0, max_slots=2,
                   admitted=0, completed=0, prefill_executed=0, budget=None,
                   free_blocks=10, free_slots=2, inflight=0, prefix_pins=0)
    evs = tr.snapshot()
    assert [e["kind"] for e in evs] == ["submit", "block_alloc",
                                       "engine_step"]
    assert evs[1]["rid"] == 3
    assert evs[0]["ts"] < evs[1]["ts"] < evs[2]["ts"]   # monotonic clock
    assert evs[0]["step"] == 0 and tr.current_step == 1  # step advanced
    for ev in evs:
        assert ev["kind"] in EVENT_KINDS
        assert validate_event(ev) is None
    # the validator actually rejects malformed events
    assert validate_event({"kind": "submit", "rid": 1}) is not None  # no ts
    assert validate_event({"ts": 1.0, "kind": "nope", "step": 0}) is not None
    assert validate_event({"ts": 1.0, "kind": "submit", "step": 0}) \
        is not None                     # request-scoped kind without rid
    assert validate_event({"ts": 1.0, "kind": "engine_step"}) is not None
    # gauges only sampled on decoded steps (pre-tracing semantics)
    assert tr.metrics.decode_steps == 0


def test_unknown_kind_cannot_be_exported_silently(tmp_path):
    tr = Tracer(enabled=True, clock=lambda: 1.0)
    tr.submit(0)
    path = tr.export_jsonl(tmp_path / "t.jsonl")
    [line] = path.read_text().splitlines()
    ev = json.loads(line)
    assert ev["replica"] == "replica0"       # exporter stamps the replica
    assert validate_event(ev) is None


# ---------------------------------------------------------------------------
# traced serving runs
# ---------------------------------------------------------------------------

def test_traced_run_covers_lifecycle_and_validates(qwen, tmp_path):
    cfg, _ = qwen
    rng = np.random.default_rng(0)
    prompts = [_prompt(rng, cfg, n) for n in (5, 19, 11)]
    tracer, sched, rids = _serve_traced(qwen, prompts, [4, 2, 3],
                                        paged=True, prefix_cache_blocks=16)
    evs = tracer.snapshot()
    for ev in evs:
        assert validate_event(ev) is None, ev
    kinds = {e["kind"] for e in evs}
    assert {"submit", "prefix_probe", "admit", "prefill_advance",
            "first_token", "decode", "retire", "block_alloc", "block_free",
            "prefix_insert", "engine_step"} <= kinds
    for rid in rids:
        span = [e["kind"] for e in evs if e.get("rid") == rid]
        assert span[0] == "submit" and span[-1] == "retire"
        assert "first_token" in span
        # submit < admit < first_token < retire within the span
        order = [span.index(k) for k in ("submit", "admit", "first_token",
                                         "retire")]
        assert order == sorted(order)
    # one engine_step per scheduler step, step ids dense from 0
    steps = [e for e in evs if e["kind"] == "engine_step"]
    assert [e["step"] for e in steps] == list(range(len(steps)))
    assert sum(1 for e in steps if e["decoded"]) == \
        sched.metrics.decode_steps
    # every span's phase field is on every step, non-negative, and a
    # decoded step spent time in its decode span
    for e in steps:
        for k in filter(None, SPANS.values()):
            assert e[k] >= 0.0
        if e["decoded"]:
            assert e["dur_decode_s"] > 0.0
        assert e["dur_step_s"] >= e["dur_admit_s"] + e["dur_decode_s"]
    # JSONL round-trips through the file exporter
    path = tracer.export_jsonl(tmp_path / "run.jsonl")
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(lines) == len(evs)
    assert all(validate_event(e) is None for e in lines)


def test_preempted_request_trace_and_single_count(qwen):
    """The regression satellite: a recompute-preempted request's span
    shows preempt -> re-admit (``resumed=True``) -> resume-from-prefix
    (warm ``prefix_probe``) in that order, while the metrics still
    count exactly one submit and one finish for it."""
    cfg, _ = qwen
    rng = np.random.default_rng(3)
    shared = _prompt(rng, cfg, 16)
    p_a = np.concatenate([shared, _prompt(rng, cfg, 7)])
    p_b = np.concatenate([shared, _prompt(rng, cfg, 21)])
    # same geometry as the interleaved preemption test: A's decode
    # growth past pos 24 forces the pool dry while B is mid-prefill
    tracer = Tracer(enabled=True)
    eng = _engine(qwen, paged=True, num_blocks=8, chunk=4,
                  prefix_cache_blocks=16)
    sched = Scheduler(eng, prefill_token_budget=8, tracer=tracer)
    r_a = sched.submit(Request(p_a, SamplingParams(max_new_tokens=12,
                                                   greedy=True)))
    while not sched.active:
        sched.step()
    r_b = sched.submit(Request(p_b, SamplingParams(max_new_tokens=2,
                                                   greedy=True)))
    sched.run()
    assert sched.preemptions >= 1

    evs = [e for e in tracer.snapshot() if e.get("rid") == r_b]
    kinds = [e["kind"] for e in evs]
    assert kinds.count("submit") == 1 and kinds.count("retire") == 1
    i_admit0 = kinds.index("admit")
    assert evs[i_admit0]["resumed"] is False
    i_pre = kinds.index("preempt")
    assert evs[i_pre]["mid_prefill"] is True
    # the re-admission comes after the preemption, flagged resumed, and
    # its probe hit the prefix A's completed prefill had cached
    i_admit1 = next(i for i in range(i_pre, len(kinds))
                    if kinds[i] == "admit")
    assert evs[i_admit1]["resumed"] is True
    i_probe1 = next(i for i in range(i_pre, len(kinds))
                    if kinds[i] == "prefix_probe")
    assert i_pre < i_probe1 < i_admit1
    assert evs[i_probe1]["hit"] and evs[i_probe1]["cached_len"] >= 16
    assert kinds.index("submit") < i_admit0 < i_pre < i_admit1 \
        < kinds.index("retire")

    # metrics: one submit / one finish per request despite the cycle
    s = sched.metrics.summary()
    assert s["requests_completed"] == 2
    assert len(sched.metrics._submit) == 2
    assert len(sched.metrics._finish) == 2
    # the pool-dry admission stall was recorded with its cause
    stalls = [e for e in tracer.snapshot()
              if e["kind"] == "admission_stall"]
    oob = [e for e in tracer.snapshot() if e["kind"] == "out_of_blocks"]
    assert stalls or oob
    _ = r_a


def test_chrome_trace_spans_and_counters(qwen, tmp_path):
    cfg, _ = qwen
    rng = np.random.default_rng(1)
    tracer, _sched, rids = _serve_traced(
        qwen, [_prompt(rng, cfg, 6), _prompt(rng, cfg, 13)], [3, 2],
        paged=True)
    doc = to_chrome_trace({tracer.name: tracer.snapshot()})
    evs = doc["traceEvents"]
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"
             and e["name"] == "process_name"}
    assert names == {"replica0"}
    for rid in rids:
        span = f"replica0/req{rid}"
        sevs = [e for e in evs if e.get("id") == span]
        phs = [e["ph"] for e in sevs]
        assert phs[0] == "b" and phs[-1] == "e"
        assert phs.count("n") >= 4           # submit/admit/decode/retire
        assert all(e["ts"] >= 0 for e in sevs)
    assert any(e["ph"] == "X" and e["cat"] == "engine" for e in evs)
    assert any(e["ph"] == "C" and e["name"] == "free_blocks" for e in evs)
    # the exporter writes valid JSON
    path = export_chrome_trace({tracer.name: tracer.snapshot()},
                               tmp_path / "t.chrome.json")
    assert json.loads(path.read_text())["traceEvents"]


def test_gateway_merges_replica_traces_on_shared_clock(qwen, tmp_path):
    cfg, _ = qwen
    rng = np.random.default_rng(2)
    gw = ReplicaGateway.from_engines(
        [_engine(qwen, paged=True, prefix_cache_blocks=16)
         for _ in range(2)], tracing=True)
    handles = [gw.submit(Request(_prompt(rng, cfg, 9),
                                 SamplingParams(max_new_tokens=2,
                                                greedy=True)))
               for _ in range(4)]
    gw.drain()
    assert {h[0] for h in handles} == {0, 1}     # both replicas used
    merged = gw.trace_events()
    assert {e["replica"] for e in merged} == {"replica0", "replica1"}
    ts = [e["ts"] for e in merged]
    assert ts == sorted(ts)                      # one shared timeline
    # the routing decision was traced with a reason on the target replica
    routes = [e for e in merged if e["kind"] == "route"]
    assert len(routes) == 4
    assert all(e["reason"] in ("prefix_affinity", "hash_owner",
                               "least_loaded") for e in routes)
    # exporters: merged JSONL validates; chrome has 2 processes
    jsonl = gw.export_trace_jsonl(tmp_path / "gw.jsonl")
    for line in jsonl.read_text().splitlines():
        assert validate_event(json.loads(line)) is None
    chrome = json.loads(
        gw.export_chrome_trace(tmp_path / "gw.chrome.json").read_text())
    pids = {e["pid"] for e in chrome["traceEvents"]}
    assert len(pids) == 2
    # merge_traces on an explicit tracer list matches the gateway view
    assert merge_traces(gw.tracers) == merged


def test_tracing_is_inert_on_outputs(qwen):
    """Turning tracing on must not perturb the computation: greedy
    outputs bit-identical traced vs untraced."""
    cfg, _ = qwen
    rng = np.random.default_rng(4)
    prompts = [_prompt(rng, cfg, n) for n in (7, 21)]

    def serve(tracer):
        sched = Scheduler(_engine(qwen, paged=True), tracer=tracer)
        rids = [sched.submit(Request(p, SamplingParams(max_new_tokens=3,
                                                       greedy=True)))
                for p in prompts]
        sched.run()
        return [sched.output(r) for r in rids]

    for a, b in zip(serve(None), serve(Tracer(enabled=True))):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# program spans on the profiler's clock
# ---------------------------------------------------------------------------

def _profile(tmp_path, fn):
    """Run ``fn`` under the profiler (Python tracer off) and return the
    program spans it recorded: ``(name, start_s, end_s, stats)``."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    spans = [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
              dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name in SPANS]
    return out, spans


def test_program_spans_join_the_ring_on_step(qwen, tmp_path):
    """Every span lands in the profiler's trace with its stats; engine
    and KV spans nest in a scheduler step; each step span's ``step``
    names exactly one ``engine_step`` event, one offset maps the ring's
    clock onto the profiler's for every step, and the ring's phase
    durations are the spans' own."""
    cfg, _ = qwen
    rng = np.random.default_rng(7)
    shared = _prompt(rng, cfg, 16)
    gw = ReplicaGateway.from_engines(
        [_engine(qwen, paged=True, prefix_cache_blocks=16)], tracing=True)

    def serve():
        # the second prompt resumes from the prefix the first one saved
        for n in (5, 9):
            gw.submit(Request(np.concatenate([shared, _prompt(rng, cfg, n)]),
                              SamplingParams(max_new_tokens=3, greedy=True)))
            while gw.has_work:
                gw.step()

    _, spans = _profile(tmp_path, serve)
    assert {n for n, *_ in spans} == set(SPANS)
    steps = [sp for sp in spans if sp[0] == "scheduler.step"]
    for name, s, e, stats in spans:
        assert "step" in stats or name == "gateway.step"
        if name.startswith(("engine.", "kv.")):
            assert any(s0 <= s and e <= e0 for _, s0, e0, _ in steps), name
    assert all(st["replicas"] == 1 for n, _, _, st in spans
               if n == "gateway.step")
    ring = [ev for ev in gw.tracers[0].snapshot()
            if ev["kind"] == "engine_step"]
    by_step = collections.Counter(ev["step"] for ev in ring)
    assert len(steps) == len(ring)
    assert all(by_step[st["step"]] == 1 for _, _, _, st in steps)
    ring = {ev["step"]: ev for ev in ring}
    offsets = [ring[st["step"]]["ts"] - ring[st["step"]]["dur_step_s"] - s
               for _, s, _, st in steps]
    assert max(offsets) - min(offsets) < 1e-3
    per_step = collections.defaultdict(float)
    for name, s, e, stats in spans:
        if SPANS[name]:
            per_step[stats["step"], SPANS[name]] += e - s
    for step, ev in ring.items():
        for field in filter(None, SPANS.values()):
            assert ev[field] == pytest.approx(per_step[step, field],
                                              abs=5e-4), (step, field)


def test_tokens_identical_with_profiler_on_and_off(qwen, tmp_path):
    """Spans only annotate: greedy outputs are bit-identical whether or
    not a profiler trace is being taken, and the profiled run recorded
    its spans."""
    cfg, _ = qwen
    rng = np.random.default_rng(12)
    prompts = [_prompt(rng, cfg, n) for n in (7, 13)]
    eng = _engine(qwen, paged=True)

    def serve():
        sched = Scheduler(eng, tracer=Tracer())
        rids = [sched.submit(Request(p, SamplingParams(max_new_tokens=3,
                                                       greedy=True)))
                for p in prompts]
        sched.run()
        return [sched.output(r) for r in rids]

    plain = serve()
    profiled, spans = _profile(tmp_path, serve)
    for a, b in zip(plain, profiled):
        np.testing.assert_array_equal(a, b)
    assert {"scheduler.step", "engine.decode", "engine.sample"} <= {
        n for n, *_ in spans}


def test_span_without_ring_keeps_no_phase_time():
    """A disabled tracer's spans only annotate; an enabled one sums each
    span's time into the step's ``engine_step`` fields."""
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    off, on = Tracer(clock=clock), Tracer(enabled=True, clock=clock)
    for tr in (off, on):
        with tr.span("scheduler.step"):
            with tr.span("engine.decode", live_rows=2) as sp:
                sp.set(ctx_tokens=9)
            with tr.span("engine.decode", live_rows=2):
                pass
        tr.engine_step(decoded=True, queue_depth=0, active=2, max_slots=2,
                       admitted=0, completed=0, prefill_executed=0,
                       budget=None, free_blocks=0, free_slots=0, inflight=0,
                       prefix_pins=0)
    assert off._phase_s == {} and not off.events
    [ev] = on.snapshot()
    assert ev["dur_decode_s"] == 2.0          # two spans of one tick each
    assert ev["dur_step_s"] == 5.0
    assert ev["dur_admit_s"] == 0.0 and on._phase_s == {}


# ---------------------------------------------------------------------------
# merge_summaries edge-case contract (satellite)
# ---------------------------------------------------------------------------

def _no_nans(obj):
    if isinstance(obj, dict):
        return all(_no_nans(v) for v in obj.values())
    if isinstance(obj, (int, float)):
        return obj == obj                    # NaN != NaN
    return True


def test_merge_summaries_empty_returns_sentinel():
    assert merge_summaries([]) == {"replicas": 0}


def test_merge_summaries_single_replica_passthrough():
    from repro.serving import ServingMetrics
    t = [0.0]

    def clock():
        t[0] += 0.5
        return t[0]

    m = ServingMetrics(clock=clock)
    m.record_submit(0)
    m.record_first_token(0)
    m.record_finish(0, 4, "length")
    s = m.summary()
    merged = merge_summaries([s])
    assert merged["replicas"] == 1
    assert merged["requests_completed"] == 1
    assert merged["total_new_tokens"] == 4
    assert merged["ttft_ms_p95"] == s["ttft_ms"]["p95"]
    assert merged["latency_ms_p95"] == s["latency_ms"]["p95"]
    assert _no_nans(merged)


def test_merge_summaries_idle_fleet_no_nan():
    from repro.serving import ServingMetrics
    idle = [ServingMetrics(clock=lambda: 0.0).summary() for _ in range(3)]
    merged = merge_summaries(idle)
    assert merged["replicas"] == 3
    assert merged["requests_completed"] == 0
    assert merged["ttft_ms_p95"] == 0.0
    assert _no_nans(merged)


def test_merge_summaries_partial_dicts_do_not_raise():
    merged = merge_summaries([{"requests_completed": 2},
                              {"total_new_tokens": 7}])
    assert merged["replicas"] == 2
    assert merged["requests_completed"] == 2
    assert merged["total_new_tokens"] == 7
    assert _no_nans(merged)


# ---------------------------------------------------------------------------
# ROADMAP carry-over, made executable: the near-tie argmax caveat
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_paged_vs_dense_divergence_only_at_near_ties(qwen):
    """The known caveat (see ROADMAP.md): the paged decode kernel's
    page-wise online-softmax summation order can legitimately flip a
    greedy argmax against the dense path when the top-2 logits are a
    near-tie (~1e-3 apart).  This pins the caveat down as a property:
    wherever paged and dense greedy outputs diverge on random
    workloads, the dense logits at the first divergent position must be
    a near-tie between the two chosen tokens — a decisive-argmax
    divergence would be a real kernel bug, and fails here.

    The tie-break is now ON by default (``greedy_tie_eps=1e-2``), so
    this test arms ``greedy_tie_eps=0.0`` explicitly: it exercises the
    raw-argmax opt-out path, which is where the caveat still lives."""
    cfg, _ = qwen
    NEAR_TIE = 1e-2                    # generous bound over the ~1e-3 seen
    divergences = 0
    for seed in (31, 32, 33):
        rng = np.random.default_rng(seed)
        prompts = [_prompt(rng, cfg, int(rng.integers(3, 24)))
                   for _ in range(4)]
        max_news = [int(rng.integers(2, 8)) for _ in prompts]

        def serve(paged):
            sched = Scheduler(_engine(qwen, paged=paged,
                                      greedy_tie_eps=0.0))
            rids = [sched.submit(Request(p, SamplingParams(
                max_new_tokens=m, greedy=True)))
                for p, m in zip(prompts, max_news)]
            sched.run()
            return [sched.output(r) for r in rids]

        dense_outs = serve(False)
        paged_outs = serve(True)
        for prompt, d_out, p_out in zip(prompts, dense_outs, paged_outs):
            if np.array_equal(d_out, p_out):
                continue
            divergences += 1
            j = int(np.argmax(np.asarray(d_out) != np.asarray(p_out)))
            # recompute the logits that produced position j with a
            # fresh dense prefill of prompt + the agreed tokens
            agreed = np.concatenate(
                [prompt, np.asarray(d_out[:j], np.int32)])
            ref_eng = _engine(qwen)
            slot, logits = ref_eng.prefill_into_slots([agreed])[0]
            ref_eng.free_slot(slot)
            logits = np.asarray(logits, np.float64)
            top2 = np.sort(logits)[-2:]
            gap = float(top2[1] - top2[0])
            assert gap < NEAR_TIE, (
                f"seed {seed}: paged/dense diverged at pos {j} with a "
                f"DECISIVE top-2 logit gap {gap:.4f} (dense tok "
                f"{d_out[j]}, paged tok {p_out[j]}) — not the near-tie "
                f"caveat, a real kernel divergence")
            # both chosen tokens sit within the near-tie band of the max
            for tok in (int(d_out[j]), int(p_out[j])):
                assert logits.max() - logits[tok] < NEAR_TIE
    # zero divergences is fine: the caveat is probabilistic.  The test's
    # value is that any divergence that does occur is proven benign.


@pytest.mark.slow
def test_greedy_tie_eps_makes_layouts_bit_identical(qwen):
    """The caveat retired (ROADMAP carry-over): with the deterministic
    tie-break epsilon armed, greedy argmax picks the lowest token id
    within eps of the max, so the paged kernel's page-order summation
    noise (~1e-3, well inside eps=1e-2) can no longer flip a near-tie —
    the exact same workloads as the divergence test above must now be
    bit-identical across layouts."""
    cfg, _ = qwen
    TIE_EPS = 1e-2                     # matches the NEAR_TIE bound above
    for seed in (31, 32, 33):
        rng = np.random.default_rng(seed)
        prompts = [_prompt(rng, cfg, int(rng.integers(3, 24)))
                   for _ in range(4)]
        max_news = [int(rng.integers(2, 8)) for _ in prompts]

        def serve(paged):
            sched = Scheduler(_engine(qwen, paged=paged,
                                      greedy_tie_eps=TIE_EPS))
            rids = [sched.submit(Request(p, SamplingParams(
                max_new_tokens=m, greedy=True)))
                for p, m in zip(prompts, max_news)]
            sched.run()
            return [sched.output(r) for r in rids]

        dense_outs = serve(False)
        paged_outs = serve(True)
        for i, (d_out, p_out) in enumerate(zip(dense_outs, paged_outs)):
            assert np.array_equal(d_out, p_out), (
                f"seed {seed} request {i}: paged/dense greedy outputs "
                f"still diverge with greedy_tie_eps={TIE_EPS} "
                f"(dense {list(d_out)}, paged {list(p_out)})")
