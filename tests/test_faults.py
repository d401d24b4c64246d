"""Fault-tolerant serving fleet (PR 9): deterministic fault injection,
replica health + failover, retry with backoff, load-shedding
degradation, and the chaos harness — every request either completes
bit-identical to a fault-free greedy oracle or surfaces a typed
failure, with zero leaked slots / blocks / pins on the survivors."""
import jax
import numpy as np
import pytest

from repro.serving import (CapacityError, ClusterRegistry,
                           DegradationPolicy, FaultInjector, FaultPlan,
                           FaultSpec, HealthConfig, HealthMonitor,
                           InjectedFault, Mailbox, MailboxError,
                           MockBackend, Overloaded, ReplicaCrashed,
                           ReplicaGateway, Request, RequestFailed,
                           RetryPolicy, SamplingParams, Scheduler,
                           ServingEngine, SlurmBackend, WorkerSpec,
                           launch_capsule_replicas,
                           launch_fabric_replicas, shutdown_fabric)
from repro.serving.fabric import COMPLETED, PENDING, RUNNING, Partition
from repro.serving.health import DEAD, DEGRADED, HEALTHY, QUARANTINED


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


def _assert_no_leaks(sched):
    eng = sched.engine
    assert not sched.queue and not sched.active and not sched.prefilling
    assert not eng._inflight
    assert eng.kv.pool.in_use == 0
    assert eng.kv.free_slot_count == eng.max_slots
    if eng.prefix_cache is not None:
        eng.prefix_cache.evict(10 ** 9)
        assert eng.kv.prefix_pool.in_use == 0, "leaked prefix pins"


_ORACLE_CACHE = {}

# greedy_tie_eps armed by default in every fault/failover path: a
# salvaged request resumes in a different batch composition, and only
# eps-tolerant argmax keeps that bit-identical to the fault-free run
TIE_EPS = 1e-2


def _oracle(qwen, prompt, max_new, *, seq=48):
    """Solo fault-free greedy run of one prompt — the bit-identity
    reference a failed-over request must still reproduce."""
    key = (tuple(int(x) for x in prompt), max_new, seq)
    if key not in _ORACLE_CACHE:
        eng = _engine(qwen, seq=seq, greedy_tie_eps=TIE_EPS)
        sched = Scheduler(eng)
        rid = sched.submit(Request(prompt, SamplingParams(
            max_new_tokens=max_new, greedy=True)))
        sched.run()
        _ORACLE_CACHE[key] = sched.output(rid)
    return _ORACLE_CACHE[key]


def _requests(cfg, rng, n, max_new=6):
    return [Request(_prompt(rng, cfg, int(rng.integers(3, 12))),
                    SamplingParams(max_new_tokens=max_new, greedy=True))
            for _ in range(n)]


# ---------------------------------------------------------------------------
# fault plans / injectors (pure — no engine)
# ---------------------------------------------------------------------------

def test_fault_spec_validation():
    with pytest.raises(ValueError, match="probability"):
        FaultSpec(kind="raise", probability=1.5)
    with pytest.raises(ValueError, match="kind"):
        FaultSpec(kind="explode")
    with pytest.raises(ValueError, match="site"):
        FaultSpec(kind="stall", site="decode")
    with pytest.raises(ValueError, match="latency_s"):
        FaultSpec(kind="slow", latency_s=0.0)
    with pytest.raises(ValueError, match="duration"):
        FaultSpec(kind="raise", duration=0)


def test_fault_plan_is_deterministic():
    a = FaultPlan.random(seed=11, replicas=["r0", "r1", "r2"])
    b = FaultPlan.random(seed=11, replicas=["r0", "r1", "r2"])
    assert a.specs == b.specs
    c = FaultPlan.random(seed=12, replicas=["r0", "r1", "r2"])
    assert a.specs != c.specs


def test_injector_stall_crash_and_replay():
    inj = FaultInjector([FaultSpec(kind="stall", at_step=1, duration=2)],
                        replica="r0")
    assert [inj.on_step() for _ in range(4)] == \
        ["ok", "stall", "stall", "ok"]
    assert inj.fired == [(1, "stall", "step"), (2, "stall", "step")]
    # reset() replays the schedule exactly
    inj.reset()
    assert [inj.on_step() for _ in range(4)] == \
        ["ok", "stall", "stall", "ok"]

    inj = FaultInjector([FaultSpec(kind="crash", at_step=0)], replica="r0")
    with pytest.raises(ReplicaCrashed):
        inj.on_step()
    with pytest.raises(ReplicaCrashed):   # a crash is sticky
        inj.on_step()

    inj = FaultInjector([FaultSpec(kind="raise", at_step=0, site="prefill")],
                        replica="r0")
    assert inj.on_step() == "ok"          # step-site untouched
    # the prefill-site fault fires at the step it was armed for
    inj2 = FaultInjector([FaultSpec(kind="raise", at_step=0,
                                    site="prefill")], replica="r0")
    with pytest.raises(InjectedFault):
        inj2.on_engine_op("prefill")


def test_plan_filters_by_replica():
    plan = FaultPlan([FaultSpec(kind="stall", replica="r1", at_step=0),
                      FaultSpec(kind="raise", replica="*", at_step=5)])
    assert len(plan.injector_for("r0").specs) == 1       # the wildcard
    assert len(plan.injector_for("r1").specs) == 2


# ---------------------------------------------------------------------------
# health ladder (pure)
# ---------------------------------------------------------------------------

def test_health_ladder_and_recovery():
    m = HealthMonitor(HealthConfig(degraded_after=2, quarantine_after=4))
    assert m.state == HEALTHY and m.routable
    assert m.record_step(False) is None                  # 1 bad: still ok
    tr = m.record_step(False)
    assert tr == {"from": HEALTHY, "to": DEGRADED,
                  "reason": "no_progress", "consecutive_bad": 2}
    assert m.routable                                    # degraded routes
    tr = m.record_step(True)                             # progress heals
    assert tr["to"] == HEALTHY and m.consecutive_bad == 0
    for _ in range(3):
        m.record_step(False)
    tr = m.record_step(False)
    assert tr["to"] == QUARANTINED and not m.routable and m.alive
    tr = m.mark_rejoined()
    assert tr["to"] == HEALTHY and m.rejoins == 1
    tr = m.record_failure("ReplicaCrashed()", fatal=True)
    assert tr["to"] == DEAD and not m.alive and m.failures == 1


def test_health_config_validation():
    with pytest.raises(ValueError):
        HealthConfig(degraded_after=0)
    with pytest.raises(ValueError):
        HealthConfig(degraded_after=4, quarantine_after=4)


# ---------------------------------------------------------------------------
# tentpole: crash failover is bit-identical to the fault-free oracle
# ---------------------------------------------------------------------------

def test_crash_failover_bit_identical_and_single_counted(qwen):
    """Kill one of three replicas mid-burst: every request still
    completes with the fault-free greedy oracle's exact tokens, the
    merged metrics count each logical request exactly once (retries as
    retries, one TTFT sample each), and the survivors leak nothing."""
    cfg, _ = qwen
    rng = np.random.default_rng(3)
    plan = FaultPlan([FaultSpec(kind="crash", replica="replica1",
                                at_step=3)])
    gw = ReplicaGateway.from_engines(
        [_engine(qwen, greedy_tie_eps=TIE_EPS) for _ in range(3)],
        tracing=True, fault_plan=plan)
    reqs = _requests(cfg, rng, 6)
    handles = [gw.submit(r) for r in reqs]
    gw.drain()

    for h, r in zip(handles, reqs):
        out = gw.result(h)
        assert not isinstance(out, RequestFailed), out
        np.testing.assert_array_equal(
            out, _oracle(qwen, r.prompt, r.params.max_new_tokens))

    assert gw.health[1].state == DEAD
    stats = gw.stats()
    assert stats["fleet"]["failovers"] == 1
    assert stats["fleet"]["requests_failed"] == 0
    # single-count invariants: 6 logical submits, 6 completions, the
    # re-submits counted as retries, exactly one TTFT sample each
    tot = stats["totals"]
    assert tot["requests_submitted"] == 6
    assert tot["requests_completed"] == 6
    assert tot["requests_retried"] >= 1
    assert sum(len(rep.scheduler.metrics.ttft_s())
               for rep in gw.replicas) == 6
    # replica_* events are on the merged timeline
    kinds = {e["kind"] for e in gw.trace_events()}
    assert {"replica_health", "replica_failover",
            "replica_retry"} <= kinds
    for i, rep in enumerate(gw.replicas):
        if i != 1:                      # the dead capsule's pool died
            _assert_no_leaks(rep.scheduler)


def test_failover_preserves_emitted_prefix(qwen):
    """A request salvaged *mid-decode* resumes with its emitted-so-far
    tokens (recompute resume) — the final output is one contiguous
    sequence, not a restart."""
    cfg, _ = qwen
    rng = np.random.default_rng(5)
    prompt = _prompt(rng, cfg, 6)
    plan = FaultPlan([FaultSpec(kind="crash", replica="replica0",
                                at_step=4)])
    gw = ReplicaGateway.from_engines(
        [_engine(qwen, greedy_tie_eps=TIE_EPS) for _ in range(2)],
        tracing=True, fault_plan=plan)
    h = gw.submit(Request(prompt, SamplingParams(max_new_tokens=10,
                                                 greedy=True)))
    # step until the crash fires and the request lands on replica1
    for _ in range(40):
        if not gw.has_work:
            break
        gw.step()
    assert gw.health[0].state == DEAD
    out = gw.result(h)
    assert not isinstance(out, RequestFailed)
    rec = gw._requests[h]
    assert rec.attempts == 1 and rec.current[0] == 1
    np.testing.assert_array_equal(out, _oracle(qwen, prompt, 10))


# ---------------------------------------------------------------------------
# satellite: drain() no longer hangs on a wedged replica
# ---------------------------------------------------------------------------

def test_stalled_replica_is_quarantined_and_drain_completes(qwen):
    """Regression for the drain()/run() hang: a replica whose step()
    returns True without doing anything is detected by the progress
    watchdog, quarantined, and its work re-homed — drain returns."""
    cfg, _ = qwen
    rng = np.random.default_rng(7)
    plan = FaultPlan([FaultSpec(kind="stall", replica="replica0",
                                at_step=1, duration=200)])
    gw = ReplicaGateway.from_engines(
        [_engine(qwen, greedy_tie_eps=TIE_EPS) for _ in range(2)],
        tracing=True, fault_plan=plan,
        health=HealthConfig(degraded_after=2, quarantine_after=4,
                            auto_rejoin=False))
    reqs = _requests(cfg, rng, 4, max_new=4)
    handles = [gw.submit(r) for r in reqs]
    gw.drain()                           # must not hang
    assert gw.health[0].state == QUARANTINED
    for h, r in zip(handles, reqs):
        out = gw.result(h)
        assert not isinstance(out, RequestFailed)
        np.testing.assert_array_equal(
            out, _oracle(qwen, r.prompt, r.params.max_new_tokens))
    _assert_no_leaks(gw.replicas[1].scheduler)


def test_watchdog_raises_when_health_cannot_quarantine(qwen):
    """With quarantine effectively disabled, the run() watchdog raises
    after stall_patience no-progress steps instead of spinning forever
    — the old failure mode, now loud."""
    plan = FaultPlan([FaultSpec(kind="stall", replica="replica0",
                                at_step=0, duration=10 ** 6)])
    gw = ReplicaGateway.from_engines(
        [_engine(qwen)], fault_plan=plan, stall_patience=6,
        health=HealthConfig(degraded_after=10 ** 6,
                            quarantine_after=10 ** 6 + 1))
    gw.submit(Request(np.array([1, 2, 3], np.int32),
                      SamplingParams(max_new_tokens=2, greedy=True)))
    with pytest.raises(RuntimeError, match="no progress"):
        gw.run()


# ---------------------------------------------------------------------------
# retry budget / typed failures
# ---------------------------------------------------------------------------

def test_exhausted_requests_fail_typed_not_hang(qwen):
    """Single replica crashes: no survivor to retry on, so every
    request resolves to a typed RequestFailed from result() — and a
    fresh submit raises Overloaded."""
    plan = FaultPlan([FaultSpec(kind="crash", replica="replica0",
                                at_step=2)])
    gw = ReplicaGateway.from_engines(
        [_engine(qwen)], tracing=True, fault_plan=plan)
    h = gw.submit(Request(np.array([1, 2, 3, 4], np.int32),
                          SamplingParams(max_new_tokens=8, greedy=True)))
    gw.drain()
    out = gw.result(h)
    assert isinstance(out, RequestFailed)
    assert out.reason in ("no_routable_replica", "retry_budget_exhausted")
    assert out.handle == h and out.attempts >= 1
    assert gw.stats()["totals"]["requests_failed"] == 1
    assert "request_failed" in {e["kind"] for e in gw.trace_events()}
    gw.draining = False                  # re-open admission: still no
    with pytest.raises(Overloaded):      # routable replica to take it
        gw.submit(Request(np.array([1], np.int32)))


def test_retry_backoff_schedule():
    p = RetryPolicy(max_retries=3, backoff_base_steps=2, backoff_factor=3)
    assert [p.backoff_steps(a) for a in (1, 2, 3)] == [2, 6, 18]


# ---------------------------------------------------------------------------
# quarantine exit / rejoin
# ---------------------------------------------------------------------------

def test_quarantined_replica_rejoins_and_serves(qwen):
    """A transient stall quarantines the replica; after the cooldown it
    auto-rejoins (fresh scheduler, same engine, exhausted fault NOT
    replayed) and serves new traffic again."""
    cfg, _ = qwen
    rng = np.random.default_rng(9)
    plan = FaultPlan([FaultSpec(kind="stall", replica="replica0",
                                at_step=1, duration=6)])
    gw = ReplicaGateway.from_engines(
        [_engine(qwen, greedy_tie_eps=TIE_EPS) for _ in range(2)],
        tracing=True, fault_plan=plan,
        health=HealthConfig(degraded_after=2, quarantine_after=3,
                            rejoin_cooldown_steps=2))
    reqs = _requests(cfg, rng, 3, max_new=4)
    handles = [gw.submit(r) for r in reqs]
    gw.drain()
    for h, r in zip(handles, reqs):
        out = gw.result(h)
        assert not isinstance(out, RequestFailed)
        np.testing.assert_array_equal(
            out, _oracle(qwen, r.prompt, r.params.max_new_tokens))
    # keep stepping until the cooldown elapses and replica0 rejoins
    for _ in range(10):
        if gw.health[0].state == HEALTHY:
            break
        gw.step()
    assert gw.health[0].state == HEALTHY and gw.health[0].rejoins == 1
    kinds = {e["kind"] for e in gw.trace_events()}
    assert "replica_rejoin" in kinds
    # the rejoined replica serves again (admission was re-opened by the
    # fresh scheduler carrying the drain flag of the gateway — reset it
    # for the post-drain continuation of this test)
    gw.draining = False
    for rep in gw.replicas:
        rep.scheduler.draining = False
    r2 = Request(_prompt(rng, cfg, 5),
                 SamplingParams(max_new_tokens=3, greedy=True))
    h2 = gw.submit(r2)
    gw.drain()
    out = gw.result(h2)
    assert not isinstance(out, RequestFailed)
    np.testing.assert_array_equal(out, _oracle(qwen, r2.prompt, 3))


# ---------------------------------------------------------------------------
# degradation ladder
# ---------------------------------------------------------------------------

def test_overload_sheds_and_shrinks_budget_then_recovers(qwen):
    cfg, _ = qwen
    rng = np.random.default_rng(13)
    gw = ReplicaGateway.from_engines(
        [_engine(qwen)], tracing=True,
        degradation=DegradationPolicy(shed_queue_depth=3,
                                      recover_steps=2,
                                      budget_shrink=0.5),
        prefill_token_budget=8)
    reqs = _requests(cfg, rng, 6, max_new=2)
    handles = [gw.submit(r) for r in reqs]
    gw.step()                                      # ladder arms
    assert gw.degraded
    sched = gw.replicas[0].scheduler
    assert sched.prefill_token_budget == 4         # shrunk
    with pytest.raises(Overloaded):                # shedding at submit
        gw.submit(reqs[0])
    assert gw.shed_requests == 1
    gw.drain()
    assert not gw.degraded                         # queue emptied
    assert sched.prefill_token_budget == 8         # restored
    for h in handles:
        assert not isinstance(gw.result(h), RequestFailed)
    stats = gw.stats()
    assert stats["totals"]["requests_shed"] == 1
    assert stats["fleet"]["degraded_transitions"] == 1
    evs = [e for e in gw.trace_events() if e["kind"] == "overload_shed"]
    assert [e["active"] for e in evs] == [True, False]  # edge-triggered


def test_degraded_caps_breached_tenant_max_new(qwen):
    cfg, _ = qwen
    gw = ReplicaGateway.from_engines(
        [_engine(qwen)], tracing=True,
        degradation=DegradationPolicy(max_new_cap=3))
    # force the degraded state + an active breach for tenant "bulk"
    gw.degraded = True
    gw._breached_tenants = lambda: {"bulk"}
    h = gw.submit(Request(np.array([1, 2, 3], np.int32),
                          SamplingParams(max_new_tokens=12, greedy=True),
                          tenant="bulk"))
    gw.drain()
    out = gw.result(h)
    assert not isinstance(out, RequestFailed) and len(out) == 3
    assert gw.capped_requests == 1
    caps = [e for e in gw.trace_events() if e["kind"] == "overload_cap"]
    assert caps and caps[0]["orig_max_new"] == 12 \
        and caps[0]["capped_max_new"] == 3


# ---------------------------------------------------------------------------
# satellite: result() / launch_capsule_replicas error paths
# ---------------------------------------------------------------------------

def test_result_unknown_and_unfinished_handles(qwen):
    gw = ReplicaGateway.from_engines([_engine(qwen)])
    with pytest.raises(KeyError, match="unknown request handle"):
        gw.result((0, 99))
    with pytest.raises(KeyError, match="malformed request handle"):
        gw.result("nope")
    h = gw.submit(Request(np.array([1, 2, 3], np.int32),
                          SamplingParams(max_new_tokens=2, greedy=True)))
    with pytest.raises(RuntimeError, match="not finished"):
        gw.result(h)
    gw.drain()
    assert len(gw.result(h)) == 2


def test_launch_capsule_replicas_error_paths(qwen, tmp_path):
    with pytest.raises(ValueError, match="at least one replica"):
        launch_capsule_replicas(0, lambda: _engine(qwen), tmp_path)
    with pytest.raises(TypeError, match="callable"):
        launch_capsule_replicas(1, "not-a-factory", tmp_path)

    def exploding_factory():
        raise RuntimeError("model weights missing")

    with pytest.raises(RuntimeError, match="model weights missing"):
        launch_capsule_replicas(1, exploding_factory, tmp_path)


# ---------------------------------------------------------------------------
# chaos harness: random fault plans, every request resolves correctly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_chaos_random_faults_resolve_every_request(qwen, seed):
    """Random seeded fault schedule over a 2-replica fleet: after
    drain, every handle resolves to either the fault-free oracle's
    exact tokens or a typed RequestFailed — no hangs, no leaks on
    routable survivors, no double-counted submits."""
    cfg, _ = qwen
    rng = np.random.default_rng(100 + seed)
    plan = FaultPlan.random(seed=seed, replicas=["replica0", "replica1"],
                            n_faults=3, max_step=8)
    gw = ReplicaGateway.from_engines(
        [_engine(qwen, greedy_tie_eps=TIE_EPS) for _ in range(2)],
        tracing=True, fault_plan=plan,
        health=HealthConfig(degraded_after=2, quarantine_after=3,
                            rejoin_cooldown_steps=4))
    reqs = _requests(cfg, rng, 5, max_new=5)
    handles = []
    for i, r in enumerate(reqs):
        try:
            handles.append((gw.submit(r), r))
        except Overloaded:
            handles.append((None, r))
        if i % 2:
            gw.step()                   # interleave bursts with steps
    gw.drain()

    completed = 0
    for h, r in handles:
        if h is None:
            continue
        out = gw.result(h)
        if isinstance(out, RequestFailed):
            assert out.reason
            continue
        completed += 1
        np.testing.assert_array_equal(
            out, _oracle(qwen, r.prompt, r.params.max_new_tokens))
    submitted = sum(1 for h, _ in handles if h is not None)
    tot = gw.stats()["totals"]
    assert tot["requests_submitted"] == submitted
    assert tot["requests_completed"] == completed
    assert tot["requests_failed"] == submitted - completed
    for i, rep in enumerate(gw.replicas):
        if gw.health[i].routable:
            _assert_no_leaks(rep.scheduler)


# ---------------------------------------------------------------------------
# cross-process fabric: MockBackend drives the real worker + mailbox
# code deterministically — the same paths LocalProcessBackend runs as
# subprocesses in benchmarks/fabric.py
# ---------------------------------------------------------------------------

def _mock_fleet(qwen, tmp_path, n=2, *, backend_kw=None, **gateway_kw):
    backend = MockBackend(
        engine_factory=lambda name: _engine(qwen, greedy_tie_eps=TIE_EPS),
        **(backend_kw or {}))
    gateway_kw.setdefault("tracing", True)
    gw = launch_fabric_replicas(n, backend, tmp_path / "spool",
                                **gateway_kw)
    return backend, gw


def _kill_when_inflight(gw, backend, victim, *, action=None):
    """Step until the victim's heartbeat shows in-flight work, then pull
    the chaos lever (default: SIGKILL analogue)."""
    for _ in range(100):
        gw.step()
        if victim.active or victim.prefilling:
            (action or backend.kill)(victim.handle)
            return
    pytest.fail("victim never reported in-flight work")


def test_fabric_mock_round_trip_bit_identical(qwen, tmp_path):
    """Fault-free mock fleet: every request crosses the mailbox twice
    (submit in, result out) and still matches the solo oracle exactly;
    shutdown releases the registry capacity and finalizes the workers."""
    cfg, _ = qwen
    rng = np.random.default_rng(21)
    backend, gw = _mock_fleet(qwen, tmp_path)
    assert backend.registry.free_nodes("general") == 6    # 2 of 8 committed
    reqs = _requests(cfg, rng, 5, max_new=4)
    handles = [gw.submit(r) for r in reqs]
    gw.drain()
    for h, r in zip(handles, reqs):
        out = gw.result(h)
        assert not isinstance(out, RequestFailed)
        np.testing.assert_array_equal(
            out, _oracle(qwen, r.prompt, r.params.max_new_tokens))
    shutdown_fabric(gw)
    assert backend.registry.free_nodes("general") == 8
    for rep in gw.replicas:
        status = (tmp_path / "spool" / rep.name / "status.json")
        assert status.exists()


def test_fabric_crash_failover_bit_identical(qwen, tmp_path):
    """Kill a mock worker while its heartbeat shows in-flight requests:
    the gateway sees the job FAIL, marks the replica DEAD, salvages from
    the last heartbeat's emitted map, and the failed-over outputs stay
    bit-identical to the oracle."""
    cfg, _ = qwen
    rng = np.random.default_rng(23)
    backend, gw = _mock_fleet(qwen, tmp_path)
    reqs = _requests(cfg, rng, 6, max_new=5)
    handles = [gw.submit(r) for r in reqs]
    victim = gw.replicas[0].scheduler
    _kill_when_inflight(gw, backend, victim)
    gw.drain()
    assert gw.health[0].state == DEAD
    assert gw.stats()["fleet"]["failovers"] == 1
    for h, r in zip(handles, reqs):
        out = gw.result(h)
        assert not isinstance(out, RequestFailed)
        np.testing.assert_array_equal(
            out, _oracle(qwen, r.prompt, r.params.max_new_tokens))
    kinds = {e["kind"] for e in gw.trace_events()}
    assert {"replica_health", "replica_failover", "replica_retry"} <= kinds


def test_fabric_stale_heartbeats_quarantine_and_salvage(qwen, tmp_path):
    """A wedged worker (process alive, heartbeat seq frozen — a hung
    filesystem client) stops making observable progress: the ladder
    quarantines it and its work re-homes bit-identically."""
    cfg, _ = qwen
    rng = np.random.default_rng(25)
    backend, gw = _mock_fleet(
        qwen, tmp_path,
        health=HealthConfig(degraded_after=2, quarantine_after=4,
                            auto_rejoin=False))
    reqs = _requests(cfg, rng, 5, max_new=4)
    handles = [gw.submit(r) for r in reqs]
    victim = gw.replicas[0].scheduler
    _kill_when_inflight(gw, backend, victim, action=backend.stall)
    gw.drain()
    assert gw.health[0].state == QUARANTINED
    for h, r in zip(handles, reqs):
        out = gw.result(h)
        assert not isinstance(out, RequestFailed)
        np.testing.assert_array_equal(
            out, _oracle(qwen, r.prompt, r.params.max_new_tokens))


def test_fabric_quarantined_replica_respawns_and_serves(qwen, tmp_path):
    """Quarantine auto-rejoin on a remote replica goes through
    respawn(): the old job is cancelled, a *fresh worker job* is
    submitted for the same spec, and the relaunched replica serves new
    traffic."""
    cfg, _ = qwen
    rng = np.random.default_rng(27)
    backend, gw = _mock_fleet(
        qwen, tmp_path,
        health=HealthConfig(degraded_after=2, quarantine_after=3,
                            rejoin_cooldown_steps=2))
    reqs = _requests(cfg, rng, 4, max_new=4)
    handles = [gw.submit(r) for r in reqs]
    victim = gw.replicas[0].scheduler
    old_job = victim.handle.job_id
    _kill_when_inflight(gw, backend, victim, action=backend.stall)
    gw.drain()
    for h, r in zip(handles, reqs):
        out = gw.result(h)
        assert not isinstance(out, RequestFailed)
        np.testing.assert_array_equal(
            out, _oracle(qwen, r.prompt, r.params.max_new_tokens))
    for _ in range(10):
        if gw.health[0].state == HEALTHY:
            break
        gw.step()
    assert gw.health[0].state == HEALTHY and gw.health[0].rejoins == 1
    assert gw.replicas[0].scheduler.handle.job_id != old_job
    gw.draining = False
    for rep in gw.replicas:
        rep.scheduler.draining = False
    r2 = Request(_prompt(rng, cfg, 5),
                 SamplingParams(max_new_tokens=3, greedy=True))
    h2 = gw.submit(r2)
    gw.drain()
    out = gw.result(h2)
    assert not isinstance(out, RequestFailed)
    np.testing.assert_array_equal(out, _oracle(qwen, r2.prompt, 3))


def test_fabric_mock_fault_plan_crash(qwen, tmp_path):
    """The PR 9 chaos harness extends across the (simulated) process
    boundary: a FaultPlan crash wired into a mock worker's scheduler
    surfaces as a FAILED job -> DEAD replica -> bit-identical failover."""
    cfg, _ = qwen
    rng = np.random.default_rng(29)
    plan = FaultPlan([FaultSpec(kind="crash", replica="replica0",
                                at_step=2)])
    backend, gw = _mock_fleet(qwen, tmp_path,
                              backend_kw={"fault_plan": plan})
    reqs = _requests(cfg, rng, 4, max_new=4)
    handles = [gw.submit(r) for r in reqs]
    gw.drain()
    assert gw.health[0].state == DEAD
    assert "crash" in (gw.replicas[0].scheduler.handle.error or "").lower() \
        or gw.replicas[0].scheduler.handle.error
    for h, r in zip(handles, reqs):
        out = gw.result(h)
        assert not isinstance(out, RequestFailed)
        np.testing.assert_array_equal(
            out, _oracle(qwen, r.prompt, r.params.max_new_tokens))


# ---------------------------------------------------------------------------
# mailbox transport fault cases: truncated / partial messages, corrupt
# heartbeats, duplicate results — typed failures or idempotent no-ops
# ---------------------------------------------------------------------------

def test_mailbox_truncated_message_is_typed_and_lossless(tmp_path):
    mb = Mailbox(tmp_path / "spool", "r0")
    mb.post_to_worker("drain")
    (mb.inbox / "00000002.99.json").write_text('{"kind": "sub')  # truncated
    with pytest.raises(MailboxError, match="corrupt"):
        mb.collect_inbox()
    # nothing was consumed: the valid message sorted before the corrupt
    # one must still be delivered once the spool is repaired
    with pytest.raises(MailboxError):
        mb.collect_inbox()
    (mb.inbox / "00000002.99.json").unlink()
    assert [m["kind"] for m in mb.collect_inbox()] == ["drain"]
    # a message that parses but has no 'kind' is malformed, same typing
    (mb.inbox / "00000003.99.json").write_text('{"rid": 1}')
    with pytest.raises(MailboxError, match="no 'kind'"):
        mb.collect_inbox()


def test_mailbox_inflight_tmp_files_are_invisible(tmp_path):
    """A crashed writer leaves a ``.tmp`` file mid-write; readers must
    never see it — atomic rename means a ``*.json`` is complete by
    construction."""
    mb = Mailbox(tmp_path / "spool", "r0")
    (mb.inbox / "00000001.99.json.tmp").write_text('{"kind": "sub')
    assert mb.collect_inbox() == []
    mb.post_to_worker("stop")
    assert [m["kind"] for m in mb.collect_inbox()] == ["stop"]


def test_mailbox_corrupt_heartbeat_is_typed(tmp_path):
    mb = Mailbox(tmp_path / "spool", "r0")
    assert mb.read_heartbeat() is None          # no heartbeat yet: None
    mb.write_heartbeat({"seq": 1})
    assert mb.read_heartbeat() == {"seq": 1}
    mb.heartbeat_path.write_text('{"seq": ')    # spool corruption
    with pytest.raises(MailboxError, match="corrupt heartbeat"):
        mb.read_heartbeat()
    mb.heartbeat_path.write_text('[1, 2]')      # parses, wrong shape
    with pytest.raises(MailboxError, match="not an object"):
        mb.read_heartbeat()


def test_fabric_corrupt_spool_climbs_health_ladder(qwen, tmp_path):
    """A corrupt message file in a live replica's outbox surfaces as a
    MailboxError every gateway step — a transient (non-fatal) failure
    that climbs the ladder to QUARANTINED, after which the victim's work
    re-homes and completes bit-identically."""
    cfg, _ = qwen
    rng = np.random.default_rng(31)
    backend, gw = _mock_fleet(
        qwen, tmp_path,
        health=HealthConfig(degraded_after=2, quarantine_after=4,
                            auto_rejoin=False))
    reqs = _requests(cfg, rng, 4, max_new=4)
    handles = [gw.submit(r) for r in reqs]
    victim = gw.replicas[0].scheduler
    # disk fault: an unparseable message lands in the victim's outbox
    (victim.mailbox.outbox / "00000000.0.json").write_text("garbage")
    gw.drain()
    assert gw.health[0].state == QUARANTINED
    assert "MailboxError" in gw.health[0].last_error
    for h, r in zip(handles, reqs):
        out = gw.result(h)
        assert not isinstance(out, RequestFailed)
        np.testing.assert_array_equal(
            out, _oracle(qwen, r.prompt, r.params.max_new_tokens))


def test_fabric_duplicate_result_is_idempotent(qwen, tmp_path):
    """A slow worker racing its own failover can deliver a result for a
    request the gateway already resolved elsewhere — the duplicate must
    be dropped, not clobber the canonical output."""
    cfg, _ = qwen
    rng = np.random.default_rng(33)
    backend, gw = _mock_fleet(qwen, tmp_path, n=1)
    r = Request(_prompt(rng, cfg, 5),
                SamplingParams(max_new_tokens=4, greedy=True))
    h = gw.submit(r)
    gw.drain()
    out1 = np.asarray(gw.result(h))
    rs = gw.replicas[0].scheduler
    # forge a late duplicate with different tokens for the finished rid
    rs.mailbox.post_to_gateway("result", rid=0, tokens=[1, 2, 3])
    rs.step()
    np.testing.assert_array_equal(rs.done[0], out1)
    np.testing.assert_array_equal(gw.result(h), out1)


# ---------------------------------------------------------------------------
# registry + slurm backend lifecycle (no engine)
# ---------------------------------------------------------------------------

def test_fabric_capacity_validated_before_submit(tmp_path):
    reg = ClusterRegistry()
    reg.add(Partition("tiny", nodes=2))
    backend = SlurmBackend(registry=reg)
    spool = tmp_path / "spool"
    for i in range(2):
        backend.submit(WorkerSpec(replica=f"replica{i}", spool=spool,
                                  partition="tiny"))
    with pytest.raises(CapacityError, match="0 free of 2"):
        backend.submit(WorkerSpec(replica="replica2", spool=spool,
                                  partition="tiny"))
    assert len(backend.jobs) == 2          # the refused submit left no job
    with pytest.raises(CapacityError, match="unknown partition"):
        backend.submit(WorkerSpec(replica="replica3", spool=spool,
                                  partition="gpu"))
    assert reg.summary() == [{"partition": "tiny", "nodes": 2,
                              "committed": 2, "free": 0}]


def test_fabric_slurm_backend_renders_and_tracks_lifecycle(tmp_path):
    """SlurmBackend renders a real sbatch script through core/slurm
    (shell-quoted worker argv, fabric env) and tracks the job off the
    worker's spool signals: heartbeat -> RUNNING, status -> COMPLETED."""
    import json as _json
    backend = SlurmBackend()
    spool = tmp_path / "spool"
    spec = WorkerSpec(replica="replica0", spool=spool,
                      model_spec={"seed": 3}, image_dir="/tmp/caps/img")
    h = backend.submit(spec)
    script = (spool / "jobs" / f"{h.job_id}-replica0.sbatch").read_text()
    assert "#SBATCH --job-name=fabric-replica0" in script
    assert "ch-run /tmp/caps/img" in script
    assert "-m repro.serving.fabric.worker" in script
    assert "--image-dir /tmp/caps/img" in script
    assert "'{\"seed\": 3}'" in script      # JSON blob shell-quoted
    assert f"export REPRO_FABRIC_SPOOL={str(spool)}" in script
    assert backend.poll(h) == PENDING
    mb = Mailbox(spool, "replica0")
    mb.write_heartbeat({"seq": 1})
    assert backend.poll(h) == RUNNING
    (mb.home / "status.json").write_text(
        _json.dumps({"state": "completed", "error": ""}))
    assert backend.poll(h) == COMPLETED
    assert backend.registry.free_nodes("general") == 8   # released
    backend.cancel(h)                                    # idempotent
    assert backend.poll(h) == COMPLETED
