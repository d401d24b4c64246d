#!/usr/bin/env python3
"""Chip smoke test: serve qwen2-0.5b at its published widths on a TPU.

    python chip_smoke.py              # one chip: the paged serving path
    python chip_smoke.py --chips 4    # four one-chip replicas vs one

One chip: qwen2-0.5b (24 layers, d_model 896, 14/2 heads, vocab 151,936)
with random weights from ``--seed`` is served through the normal path,
``ServingEngine`` -> ``Scheduler`` -> ``ReplicaGateway``, built as
``repro.launch.serve`` builds it with ``--paged``.  Eight greedy requests
that open with one 256-token shared prefix (so prompts span many pages,
several prefill chunks and prefix-cache hits) each produce 32 tokens.
The script fails unless every request completed, none failed or was
retried, every replica ended HEALTHY, the compiled paged prefill and
decode programs hold the Pallas kernels (``tpu_custom_call``), and the
served first-token logits of two prompts agree with ``T.forward`` on the
same prompts within ``LOGIT_TOL``.

``--chips 4``: four paged replicas behind one gateway, each engine's
parameters and KV pool on its own device, serve the same 8 requests plus
8 more; the outputs must equal a one-replica run of the same requests in
this process, and the logit check must pass on a request replica 3
served.

Every phase runs in this one process: a chip belongs to one process at a
time.  The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``; it is printed only when every check
passed.  Without a TPU the script exits nonzero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-0.5b"
MAX_SEQ_LEN = 1024
MAX_SLOTS = 8
PREFILL_BATCH = 4
PREFIX_CACHE_BLOCKS = 64           # the serve launcher's default
SHARED_PREFIX = 256
MAX_NEW = 32
N_REQUESTS = 8
# Largest |served - reference| first-token logit allowed, relative to
# the reference's largest |logit|.  Both paths run bf16 matmuls over
# the same bf16 K/V; they differ in summation order (page-wise online
# softmax against one softmax), and a bf16 rounding (2**-8 relative)
# that flips compounds over the residual layers.  On the CPU at the
# smoke widths the ratio was at most 0.0056 with 2 layers and 0.013 with
# 24; the bound leaves about 4x room for the chip's own rounding.
LOGIT_TOL = 0.05


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def make_prompts(vocab: int, n: int, seed: int):
    """The serve launcher's prompt recipe: one shared prefix, then 4-11
    random tokens per request."""
    import numpy as np
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, SHARED_PREFIX, dtype=np.int32)
    return [np.concatenate([shared, rng.integers(
        0, vocab, int(rng.integers(4, 12)), dtype=np.int32)])
        for _ in range(n)]


def capture_first_logits(engines):
    """Record the first-token logits each engine's prefill hands the
    scheduler, keyed by the prompt: the logits the served tokens were
    sampled from."""
    import numpy as np
    seen = {}
    for idx, eng in enumerate(engines):
        def advance(*a, _orig=eng.advance_prefill, _idx=idx, **kw):
            done = _orig(*a, **kw)
            for cur in done:
                seen[tuple(cur.tokens.tolist())] = (
                    _idx, np.asarray(cur.last_logits))
            return done
        eng.advance_prefill = advance
    return seen


def serve(cfg, params, devices, prompts, fault_plan=None):
    """Serve ``prompts`` greedily through one gateway over one paged
    replica per entry of ``devices`` (None = JAX's default device).
    Returns (outputs, first-token logits by prompt, engines, stats).
    ``fault_plan`` injects replica faults, which must fail the smoke."""
    import numpy as np

    from repro.serving import (ReplicaGateway, Request, SamplingParams,
                               ServingEngine)
    from repro.serving.health import HEALTHY

    engines = [ServingEngine(cfg, params, max_seq_len=MAX_SEQ_LEN,
                             max_slots=MAX_SLOTS, rng_seed=r,
                             prefix_cache_blocks=PREFIX_CACHE_BLOCKS,
                             paged=True, num_blocks=None,
                             prefill_batch=PREFILL_BATCH, device=dev)
               for r, dev in enumerate(devices)]
    gateway = ReplicaGateway.from_engines(engines, prefill_token_budget=None,
                                          fault_plan=fault_plan)
    logits = capture_first_logits(engines)
    handles = [gateway.submit(Request(
        p, SamplingParams(max_new_tokens=MAX_NEW, greedy=True)))
        for p in prompts]
    try:
        gateway.drain()
    except Exception as e:          # noqa: BLE001 — reported, then fails
        raise SmokeFailure(f"gateway drain raised {e!r}; "
                           f"{_health_report(gateway)}") from e
    stats = gateway.stats()
    fleet = stats["fleet"]
    outputs = [gateway.result(h) for h in handles]
    bad = [m for m in gateway.health if m.state != HEALTHY or m.failures]
    check(not bad and fleet["failovers"] == 0
          and fleet["requests_failed"] == 0
          and fleet["requests_retried"] == 0,
          f"fleet unhealthy: failed={fleet['requests_failed']} "
          f"retried={fleet['requests_retried']} "
          f"failovers={fleet['failovers']}; {_health_report(gateway)}")
    done = [o for o in outputs if isinstance(o, np.ndarray)
            and len(o) == MAX_NEW]
    check(len(done) == len(prompts)
          and stats["totals"]["requests_completed"] == len(prompts),
          f"{len(done)}/{len(prompts)} requests completed with "
          f"{MAX_NEW} tokens")
    return outputs, logits, engines, stats


def _health_report(gateway) -> str:
    parts = []
    for rep, mon in zip(gateway.replicas, gateway.health):
        first = (mon.transitions[0]["reason"] if mon.transitions
                 else "no transition")
        parts.append(f"{rep.name}: state={mon.state} "
                     f"failures={mon.failures} first transition: {first}; "
                     f"last error: {mon.last_error or 'none'}")
    return " | ".join(parts)


def logit_gap(cfg, params, prompt, served):
    """Largest |served - T.forward| first-token logit difference, and the
    reference's largest |logit|."""
    import jax
    import numpy as np

    from repro.models import transformer as T
    ref = jax.jit(lambda p, t: T.forward(p, cfg, {"tokens": t},
                                         last_only=True)[0][0, -1])
    want = np.asarray(ref(params, np.asarray(prompt)[None]))
    return (float(np.max(np.abs(served - want))),
            float(np.max(np.abs(want))))


def check_logits(cfg, params, logits, prompt, label):
    idx, served = logits[tuple(prompt.tolist())]
    gap, scale = logit_gap(cfg, params, prompt, served)
    print(f"logit check [{label}, replica{idx}, {len(prompt)} tokens]: "
          f"max |served - T.forward| = {gap!r}, max |logit| = {scale!r}, "
          f"ratio {gap / scale!r} (tolerance {LOGIT_TOL})")
    check(gap <= LOGIT_TOL * scale,
          f"first-token logits of {label} differ from T.forward by {gap} "
          f"(> {LOGIT_TOL} x {scale})")
    return idx


def kernel_programs(engine):
    """Compile the engine's paged prefill and decode programs at its
    serving shapes and return their compiled HLO text by name."""
    import jax.numpy as jnp
    import numpy as np

    kv, Bp, C = engine.kv, engine.prefill_batch, engine.prefill_chunk
    rows = np.zeros(Bp, np.int32)
    prefill = engine._prefill_paged.lower(
        engine.params, jnp.zeros((Bp, C), jnp.int32), jnp.asarray(rows),
        jnp.asarray(rows), kv.cache,
        jnp.asarray(np.full((Bp, kv.blocks_per_slot), kv.trash_block,
                            np.int32)))
    slots = np.zeros(engine.max_slots, np.int32)
    decode = engine._step.lower(engine.params, {
        "tokens": jnp.asarray(slots, jnp.int32)[:, None],
        "positions": jnp.asarray(slots, jnp.int32),
        "cache": kv.cache, "block_tables": kv.device_block_tables()})
    return {"prefill_paged": prefill.compile().as_text(),
            "decode_step": decode.compile().as_text()}


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling."""

    def __init__(self):
        import jax
        self.seconds = 0.0

        def listen(event, duration, **_):
            if event.startswith("/jax/core/compile/"):
                self.seconds += duration
        jax.monitoring.register_event_duration_secs_listener(listen)


def init_params(cfg, seed: int):
    import jax

    from repro.models import transformer as T
    return jax.jit(lambda k: T.init_params(cfg, k))(jax.random.PRNGKey(seed))


def one_chip(cfg, seed: int) -> None:
    import jax

    clock = CompileClock()
    params = init_params(cfg, seed)
    prompts = make_prompts(cfg.vocab_size, N_REQUESTS, seed)
    t0 = time.perf_counter()
    outputs, logits, engines, stats = serve(cfg, params, [None], prompts)
    wall = time.perf_counter() - t0
    tot = stats["totals"]
    pc = tot["prefix_cache"]
    print(f"served {tot['requests_completed']}/{len(prompts)} requests, "
          f"{tot['total_new_tokens']} tokens, in {wall!r} s; "
          f"{clock.seconds!r} s of compilation so far, weight init "
          f"included")
    print(f"prompt lengths {[len(p) for p in prompts]}; prefix cache hit "
          f"rate {pc['hit_rate']!r}, {pc['cached_tokens_served']}/"
          f"{pc['prompt_tokens']} prompt tokens served from cache")
    check(pc["hits"] > 0, "no prefix-cache hit on a shared 256-token prefix")
    # prompt 0 prefilled the shared prefix; the last one resumed from it
    check_logits(cfg, params, logits, prompts[0], "request 0")
    check_logits(cfg, params, logits, prompts[-1],
                 f"request {len(prompts) - 1}")
    for name, text in kernel_programs(engines[0]).items():
        n = text.count("tpu_custom_call")
        print(f"compiled {name}: {n} tpu_custom_call op(s)")
        check(n > 0, f"compiled {name} holds no Pallas kernel")
    mem = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use {mem.get('peak_bytes_in_use', 'not reported')}")


def four_chips(cfg, seed: int) -> None:
    import jax
    import numpy as np

    devices = jax.devices()[:4]
    check(len(devices) == 4, f"--chips 4 needs 4 devices, JAX sees "
                             f"{len(jax.devices())}")
    clock = CompileClock()
    params = init_params(cfg, seed)
    prompts = (make_prompts(cfg.vocab_size, N_REQUESTS, seed)
               + make_prompts(cfg.vocab_size, N_REQUESTS, seed + 1))
    t0 = time.perf_counter()
    ref_out, _, _, _ = serve(cfg, params, [None], prompts)
    print(f"one replica: {len(prompts)} requests in "
          f"{time.perf_counter() - t0!r} s")
    t0 = time.perf_counter()
    outputs, logits, engines, stats = serve(cfg, params, devices, prompts)
    print(f"four replicas: {len(prompts)} requests in "
          f"{time.perf_counter() - t0!r} s; {clock.seconds!r} s of "
          f"compilation in all")
    routed = {name: rep["routed"] for name, rep in stats["replicas"].items()}
    print(f"routed per replica: {routed}")
    placed = []
    for eng in engines:
        devs = {d for leaf in jax.tree.leaves((eng.params, eng.kv.cache))
                for d in leaf.devices()}
        check(len(devs) == 1, f"an engine's arrays span {devs}")
        placed.append(devs.pop())
    print(f"engine devices: {[str(d) for d in placed]}")
    check(len(set(placed)) == 4, f"engines share devices: {placed}")
    same = [bool(np.array_equal(a, b)) for a, b in zip(outputs, ref_out)]
    print(f"outputs equal to the one-replica run: {sum(same)}/{len(same)}")
    check(all(same), f"outputs differ from one replica at requests "
                     f"{[i for i, s in enumerate(same) if not s]}")
    on3 = [p for p in prompts if logits[tuple(p.tolist())][0] == 3]
    check(on3, "replica 3 served no request")
    check_logits(cfg, params, logits, on3[0], "a replica-3 request")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the serving path on one chip; 4: four "
                         "one-chip replicas against one replica")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); this "
              f"script never falls back to another device", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache

    cache = Path(use_compile_cache())
    held = len(list(cache.iterdir())) if cache.is_dir() else 0
    print(f"compile cache: {cache} ({held} entries at start)")
    cfg = get_config(ARCH)
    print(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.num_heads}/{cfg.num_kv_heads} heads, vocab "
          f"{cfg.vocab_size}; {dev.device_kind} x {len(jax.devices())}")
    try:
        (four_chips if args.chips == 4 else one_chip)(cfg, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
