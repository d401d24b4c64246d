"""Run one cell of the benchmark: set up, warm up, measure, check.

A cell names a configuration (``configs/<name>.json``, whose ``family``
names ``families/<family>.py``) and a traffic mix
(``mixes/<traffic>.json``); each metric is read by ``metrics/<name>.py``.
The harness finds all of them by the names in ``BENCHMARK.json``.

The timed path is the program's own: ``ReplicaGateway.submit`` and
``ReplicaGateway.step`` over one paged replica, built from the mix's
engine settings.  Requests are timed from their due time, tokens from the
gateway step that produced them.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = HERE / ".cache" / "jax"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# The cell, as BENCHMARK.json and the data files describe it
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    spec: dict
    workload: dict
    config: dict
    mix: dict

    @property
    def name(self) -> str:
        return self.workload["name"]

    def metrics(self, kind: str) -> List[dict]:
        """The cell's end-to-end (``kind="end_to_end"``) or per-layer
        metrics: those without a ``workloads`` key, and those that list
        this cell."""
        return [m for m in self.spec[kind]
                if self.name in m.get("workloads", [self.name])]


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_cell(name: str, spec: Optional[dict] = None) -> Cell:
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    wl = {w["name"]: w for w in spec["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r}; known: {sorted(wl)}")
    w = wl[name]
    cf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = load_json(ROOT / cf["file"])
    if "family" not in config:
        raise KeyError(f"{cf['file']} names no model family: a configuration "
                       f"file needs a 'family' key (families/<family>.py)")
    mix = load_json(HERE / "mixes" / f"{w['traffic']}.json")
    return Cell(spec, w, config, mix)


def reader(metric: str):
    """``metrics/<metric>.py``'s ``read(ctx)``."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in peaks.json")
    return table[device_kind]


# ---------------------------------------------------------------------------
# Device, compile cache, compile counting
# ---------------------------------------------------------------------------

def use_compile_cache() -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` if set
    (JAX reads it), otherwise a fixed directory inside the checkout.
    Every program is cached, however short its compile."""
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", d)
    Path(d).mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return d


def find_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chip(s); JAX sees {len(devs)}")
    return devs[:n]


class CompileCounter:
    """Counts JAX tracing/lowering/compilation events while ``on``."""

    def __init__(self):
        import jax
        self.on = False
        self.count = 0

        def listen(event, duration, **_):
            if self.on and event.startswith("/jax/core/compile/"):
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


# ---------------------------------------------------------------------------
# Recording live lengths of the engine's calls (traced window only)
# ---------------------------------------------------------------------------

@dataclass
class WorkLog:
    on: bool = False
    decode_ctx: List[int] = field(default_factory=list)
    prefill_rows: List[tuple] = field(default_factory=list)


def record_work(engine, log: WorkLog) -> None:
    """Wrap the engine's decode and paged-prefill calls so that, while
    ``log.on``, each call's live lengths are recorded: the context of each
    live decode row (allocated slots not mid-prefill), and each prefill
    row's (start, real query length)."""
    import jax
    decode = engine.decode_once
    prefill = engine._prefill_paged

    def decode_once(tokens, positions):
        if log.on:
            live = set(engine.kv.block_table) - set(engine._inflight)
            log.decode_ctx.extend(int(positions[s]) + 1 for s in live)
        return decode(tokens, positions)

    def prefill_paged(params, toks, starts, qlens, cache, tables):
        if log.on:
            st, ql = jax.device_get((starts, qlens))
            log.prefill_rows.extend(zip(st.tolist(), ql.tolist()))
        return prefill(params, toks, starts, qlens, cache, tables)

    engine.decode_once = decode_once
    engine._prefill_paged = prefill_paged


# ---------------------------------------------------------------------------
# The client: submits due requests, stamps every token
# ---------------------------------------------------------------------------

class Client:
    def __init__(self, gateway, gen, origin: float):
        self.gw, self.gen, self.origin = gateway, gen, origin
        self.inflight: list = []
        self.finished: list = []
        self.all: list = []
        self.accepting = True
        self.step_walls: List[tuple] = []      # (start, end), relative

    def now(self) -> float:
        return time.perf_counter() - self.origin

    def submit_due(self) -> None:
        from repro.serving import Request, SamplingParams
        if not self.accepting:
            return
        for r in self.gen.due_until(self.now()):
            idx, rid = self.gw.submit(Request(
                r.prompt, SamplingParams(max_new_tokens=r.max_new,
                                         greedy=True)))
            st = self.gw.replicas[idx].scheduler.queue[-1]
            if st.rid != rid:
                raise RuntimeError(f"request {rid} is not at the tail of "
                                   f"the queue it was routed to")
            r.tokens = st.emitted       # the scheduler appends each token
            self.inflight.append(r)
            self.all.append(r)

    def collect(self) -> None:
        """Stamp the tokens the last step produced; hand finished requests
        back to the generator."""
        t = self.now()
        keep = []
        for r in self.inflight:
            while len(r.times) < len(r.tokens):
                r.times.append(t)
            if r.done:
                self.finished.append(r)
                self.gen.on_finish(r, t)
            else:
                keep.append(r)
        self.inflight = keep

    def run_until(self, until: float, stop=None, annotate: bool = False):
        """Drive the gateway until ``until`` (relative seconds), or until
        ``stop()`` is true; sleep while nothing is due."""
        import jax
        while True:
            now = self.now()
            if now >= until or (stop is not None and stop()):
                return
            self.submit_due()
            if self.gw.has_work:
                s = self.now()
                if annotate:
                    with jax.profiler.TraceAnnotation("pb.step"):
                        self.gw.step()
                else:
                    self.gw.step()
                self.step_walls.append((s, self.now()))
                self.collect()
            else:
                nxt = self.gen.next_due() if self.accepting else until
                wait = min(nxt, until) - self.now()
                if wait > 0:
                    time.sleep(wait)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

@dataclass
class Ctx:
    """What a metric reader reads."""
    dims: dict
    peaks: dict
    window: tuple                 # (start, end), relative seconds
    client: Client
    stats0: dict
    stats1: dict
    setup_s: float
    closed: float                 # when the wait for first tokens ended
    trace: object = None          # trace.Trace of the traced part
    trace_window: tuple = None    # (start, end), relative seconds
    work: WorkLog = None

    def due_in_window(self) -> list:
        a, b = self.window
        return [r for r in self.client.all if a <= r.due < b]

    def steps_in(self, a: float, b: float) -> List[tuple]:
        return [s for s in self.client.step_walls if s[0] >= a and s[1] <= b]


def warm_shapes(engine, gateway, vocab: int) -> None:
    """Compile every program the cell's traffic uses before the traffic
    clock starts: paged prefill, decode, the prefix-store copies (a
    repeated prompt resumes from the prefix cache), and the stacking and
    sampling of every batch of first tokens up to ``max_slots``."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serving import Request, SamplingParams
    bs = engine.kv.block_size
    p = (np.arange(3 * bs + 5) * 7919 % vocab).astype(np.int32)
    for _ in range(2):
        gateway.submit(Request(p, SamplingParams(max_new_tokens=2,
                                                 greedy=True)))
        gateway.run()
    row = jnp.zeros((vocab,), jnp.float32)
    for k in range(1, engine.max_slots + 1):
        # the scheduler stacks the first-token logits of the rows that
        # finished prefill in a step, then samples them in one call
        engine.sample_tokens(jnp.stack([row] * k), np.ones(k, np.float32),
                             np.ones(k, bool))


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        chips: Optional[list] = None, check_modes=("f32",),
        log=print, t_start: Optional[float] = None,
        chip_peaks: Optional[dict] = None,
        limits: Optional[dict] = None) -> dict:
    """One run of a cell.  Returns the result dict (without printing it),
    with the readings of the correctness check under ``"_check"`` and the
    window and each due request's (due time, first-token wait) under
    ``"_samples"``.  ``chip_peaks`` stands in for the peaks of the device
    JAX runs on (tests on the CPU, which has none); ``limits`` for the
    cell's own ``limits/<workload>.json``."""
    import jax

    from perfbench import check, model, readers, traffic
    from perfbench import trace as trace_mod
    from repro.serving import ReplicaGateway, ServingEngine

    t_start = time.perf_counter() if t_start is None else t_start
    dev = (chips or jax.devices())[0]
    mix = cell.mix
    n = model.dims(cell.config)
    cfg = model.program_config(cell.config)
    weights = model.make_weights(cell.config, seed)
    jax.block_until_ready(weights)
    eng_kw = {k: mix["engine"][k] for k in model.ENGINE_KEYS
              if k in mix["engine"]}
    engine = ServingEngine(cfg, weights, **eng_kw)
    gateway = ReplicaGateway.from_engines(
        [engine], prefill_token_budget=mix["scheduler"][
            "prefill_token_budget"])
    work = WorkLog()
    record_work(engine, work)
    compiles = CompileCounter()
    warm_shapes(engine, gateway, n["V"])

    warm = float(mix["warmup_s"])
    gen = traffic.make(mix, n["V"], seed, warm + seconds + 60)
    origin = time.perf_counter()
    client = Client(gateway, gen, origin)
    client.run_until(warm)
    sched = gateway.replicas[0].scheduler
    w0 = client.now()
    setup_s = time.perf_counter() - t_start
    stats0 = gateway.stats()
    compiles.on = True

    tr = None
    tw = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="pb_trace_")
        tsec = min(float(mix["trace_s"]), seconds)
        # the Python tracer would slow every host step several fold and
        # change what is measured; the runtime's own host events stay
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        work.on = True
        a = client.now()
        client.run_until(w0 + tsec, annotate=True)
        jax.block_until_ready(engine.kv.cache)
        b = client.now()
        work.on = False
        jax.profiler.stop_trace()
        tw = (a, b)
    client.run_until(w0 + seconds)
    w1 = client.now()
    queue_close = len(sched.queue)
    compiles.on = False
    stats1 = gateway.stats()

    # requests due in the window wait for their first token
    wait = float(mix.get("first_token_wait_s", 0))
    if wait > 0:
        client.accepting = False
        pending = [r for r in client.all if w0 <= r.due < w1]
        client.run_until(w1 + wait,
                         stop=lambda: all(r.times for r in pending))
    t_end = client.now()
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    if trace:
        log(f"reading the trace of {tw[1] - tw[0]:.3f} s")
        tr = trace_mod.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)

    ctx = Ctx(n, chip_peaks or peaks(dev.device_kind), (w0, w1),
              client, stats0, stats1, setup_s, t_end, tr, tw, work)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics(kind):
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    due = ctx.due_in_window()
    # a request due in the window that never got its first token is a
    # miss where the mix waits for first tokens; elsewhere it is queued
    failed = stats1["fleet"]["requests_failed"] + (
        sum(1 for r in due if not r.times) if wait > 0 else 0)
    n_tok = sum(1 for r in client.all for t in r.times if w0 <= t < w1)
    log(f"samples: {len(due)} requests due in the window "
        f"({sum(1 for r in due if r.done)} finished, {failed} failed, "
        f"{sum(1 for r in due if not r.times)} without a first token "
        f"{t_end - w1:.1f} s after it closed), {n_tok} tokens "
        f"in the window, {len(ctx.steps_in(w0, w1))} gateway steps, "
        f"{compiles.count} compile events in the window, setup "
        f"{setup_s:.3f} s")

    samples = {"window": (w0, w1), "queue_at_close": queue_close,
               "first_token": readers.first_token_ms(ctx)}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(chips or [dev]), "memory_peak_bytes": mem}
    result = {"correct": False, "attempted": len(due), "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = trace_mod.busy_seconds(tr)
        device["window_s"] = tw[1] - tw[0]
        result["breakdown"] = {"device_ops": trace_mod.top_ops(tr),
                               "idle_gaps": trace_mod.idle_gaps(tr)}

    # correctness, once the window is closed and the program's state freed
    sample = check.pick(client.finished, seed, mix["check"], w0)
    del gateway, sched, client.gw, engine, ctx
    gc.collect()
    readings = (check.readings(weights, cell.config, sample, check_modes)
                if check_modes else {"logit_gap": None, "tokens": 0})
    limits = limits or check.limits(cell.name)
    result["correct"], result["checks"] = check.verdict(
        readings, limits, mix["check"], failed)
    result["_check"] = readings
    result["_samples"] = samples
    return result


def main(argv=None) -> int:
    import argparse
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        chips = find_chips(cell.workload["chips"])
    except NoChip as e:
        print(f"perfbench: {e}; no result", file=sys.stderr)
        return 2
    use_compile_cache()
    res = run(cell, args.seed, args.seconds, bool(args.trace), chips=chips,
              log=lambda s: print(s, file=sys.stderr, flush=True),
              t_start=t_start)
    res.pop("_check")
    res.pop("_samples")
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0
