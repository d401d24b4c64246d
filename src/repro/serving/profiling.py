"""In-capsule performance profiling for the serving stack.

The capsule cannot run an external profiler daemon, so the three things
an operator needs to localize a slowdown are built in:

* :class:`StepProfiler` — device-accurate step-phase timing.  The
  scheduler's phase timestamps normally measure *dispatch* (JAX is
  async); with profiling on, the scheduler brackets each phase with
  ``block_until_ready`` so the deltas are wall time the device actually
  spent in admit / prefill / decode / sample.  Windows are bounded
  (:class:`~repro.serving.slo.SlidingWindow`).

* :func:`profile_kernel` / :func:`profile_paged_kernels` — per-kernel
  profiles for the paged attention kernels at serving shapes: compiled
  ``cost_analysis()`` FLOPs/bytes plus measured wall time, reduced to
  achieved fractions of the device's published peaks
  (``repro.launch.mesh.PEAKS``, keyed by ``device_kind``; a device not
  in the table raises).  On the CPU the kernels run in interpret mode
  and have no peak, so the fractions are ``None`` ("not measured") —
  the *structure* (flops > 0, bytes > 0, wall > 0) is what tests pin.

* :class:`RecompilationTracker` — jit recompilation telemetry.  XLA's
  jit cache keys on argument shapes/dtypes; a serving loop that lets a
  batch dimension wobble (e.g. sizing the decode batch to the number of
  *live* slots instead of padding to ``max_slots``) silently recompiles
  every few steps — the classic variable-batch serving bug.  The engine
  reports each jitted program's argument signature here; a signature
  never seen before counts as a compilation, and any compilation after
  :meth:`~RecompilationTracker.mark_warm` (or beyond the first signature
  per program) emits a ``recompile`` warning event through the tracer.
  Steady-state serving must report **zero** post-warm recompiles — the
  benchmark asserts it.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Tuple

from repro.serving.slo import SlidingWindow

PHASES = ("admit", "prefill", "decode", "sample")


class StepProfiler:
    """Bounded per-phase timing windows, fed by ``Scheduler.step()``
    when the scheduler is constructed with ``profile=True``."""

    def __init__(self, window: int = 512):
        self.phases: Dict[str, SlidingWindow] = {
            p: SlidingWindow(window) for p in PHASES}
        self.steps = 0

    def record_step(self, admit_s: float, prefill_s: float,
                    decode_s: float, sample_s: float) -> None:
        self.steps += 1
        for name, dur in zip(PHASES, (admit_s, prefill_s,
                                      decode_s, sample_s)):
            self.phases[name].add(dur * 1e3)

    def summary(self) -> Dict[str, object]:
        out: Dict[str, object] = {"steps": self.steps}
        for name, win in self.phases.items():
            out[f"{name}_ms"] = win.summary()
        return out


def profile_kernel(fn: Callable, *args, name: str, reps: int = 5,
                   clock=time.perf_counter, **kwargs) -> Dict[str, object]:
    """Profile one jitted program at the given arguments.

    Lowers+compiles once for ``cost_analysis()`` (FLOPs / bytes
    accessed), then times ``reps`` executions bracketed by
    ``block_until_ready`` and reports the median wall plus achieved
    fractions of the device's compute and bandwidth peaks (``None`` on
    the CPU, which has no published peak: not measured)."""
    import jax

    from repro.launch.mesh import device_peaks

    dev = jax.devices()[0]
    peaks = None if dev.platform == "cpu" else device_peaks(dev.device_kind)
    compiled = jax.jit(fn).lower(*args, **kwargs).compile() \
        if not hasattr(fn, "lower") else fn.lower(*args, **kwargs).compile()
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    jax.block_until_ready(fn(*args, **kwargs))      # warm the jit cache
    walls: List[float] = []
    for _ in range(max(reps, 1)):
        t0 = clock()
        jax.block_until_ready(fn(*args, **kwargs))
        walls.append(clock() - t0)
    walls.sort()
    wall = walls[len(walls) // 2]
    achieved_flops = flops / wall if wall > 0 else 0.0
    achieved_bw = bytes_accessed / wall if wall > 0 else 0.0
    return {
        "name": name,
        "device": dev.device_kind,
        "reps": len(walls),
        "wall_ms_median": wall * 1e3,
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "achieved_tflops": achieved_flops / 1e12,
        "achieved_gbps": achieved_bw / 1e9,
        "fraction_of_peak_flops": (achieved_flops / peaks["flops_bf16"]
                                   if peaks else None),
        "fraction_of_peak_bw": (achieved_bw / peaks["hbm_bytes_per_s"]
                                if peaks else None),
        "arithmetic_intensity": (flops / bytes_accessed
                                 if bytes_accessed > 0 else 0.0),
    }


def profile_paged_kernels(engine, reps: int = 3,
                          chunk: int = 8) -> Dict[str, Dict[str, object]]:
    """Profile ``paged_decode_attention`` and ``paged_prefill_attention``
    at the engine's own serving shapes (its batch width, page geometry
    and head layout), on synthetic operands.  Requires a paged engine."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    if not getattr(engine, "paged", False):
        raise ValueError("kernel profiling requires a paged engine")
    cfg, kv = engine.cfg, engine.kv
    B = engine.max_slots
    H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    pages, page = kv.pool.num_blocks + 1, kv.block_size   # + trash block
    rng = np.random.default_rng(0)
    q1 = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((pages, page, KV, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((pages, page, KV, D)), jnp.float32)
    tables = jnp.asarray(
        np.arange(B * kv.blocks_per_slot, dtype=np.int32).reshape(
            B, kv.blocks_per_slot) % kv.pool.num_blocks)
    lengths = jnp.full((B,), min(page * kv.blocks_per_slot,
                                 engine.max_seq_len), jnp.int32)
    C = min(chunk, engine.max_seq_len)
    qc = jnp.asarray(rng.standard_normal((B, C, H, D)), jnp.float32)
    starts = jnp.zeros((B,), jnp.int32)
    qlens = jnp.full((B,), C, jnp.int32)
    return {
        "paged_attention": profile_kernel(
            ops.paged_decode_attention, q1, kp, vp, tables, lengths,
            name="paged_attention", reps=reps),
        "paged_prefill": profile_kernel(
            ops.paged_prefill_attention, qc, kp, vp, tables, starts, qlens,
            name="paged_prefill", reps=reps),
    }


class RecompilationTracker:
    """Shape-signature compilation counter for the engine's jitted
    programs.  ``observe`` is on the hot path — one tuple hash and one
    set lookup per call — and only does real work on a novel signature."""

    def __init__(self):
        self.signatures: Dict[str, set] = {}
        self.post_warm: Dict[str, int] = {}
        self.warm = False

    def mark_warm(self) -> None:
        """Declare warmup over: every later novel signature is a
        *post-warm recompile* — shape churn, the thing steady-state
        serving must never do."""
        self.warm = True

    def observe(self, program: str, signature: Tuple,
                tracer=None) -> bool:
        """Record one invocation of ``program`` with argument shape
        ``signature``.  Returns True when the signature is new (i.e. XLA
        compiled).  Beyond each program's first signature — or any novel
        signature after :meth:`mark_warm` — a ``recompile`` warning
        event goes through ``tracer``."""
        sigs = self.signatures.setdefault(program, set())
        if signature in sigs:
            return False
        sigs.add(signature)
        if self.warm:
            self.post_warm[program] = self.post_warm.get(program, 0) + 1
        if tracer is not None and (self.warm or len(sigs) > 1):
            tracer.recompile(program, repr(signature), len(sigs),
                             post_warm=self.warm)
        return True

    @property
    def post_warm_recompiles(self) -> int:
        return sum(self.post_warm.values())

    def compiles(self, program: Optional[str] = None) -> int:
        if program is not None:
            return len(self.signatures.get(program, ()))
        return sum(len(s) for s in self.signatures.values())

    def churning_programs(self, threshold: int = 3) -> List[str]:
        """Programs with suspiciously many signatures — the triage list:
        find which argument's shape wobbles and pad it."""
        return sorted(p for p, s in self.signatures.items()
                      if len(s) >= threshold or self.post_warm.get(p, 0))

    def summary(self) -> Dict[str, object]:
        return {
            "warm": self.warm,
            "compiles_total": self.compiles(),
            "post_warm_recompiles": self.post_warm_recompiles,
            "programs": {p: {"signatures": len(s),
                             "post_warm": self.post_warm.get(p, 0)}
                         for p, s in sorted(self.signatures.items())},
            "churning": self.churning_programs(),
        }
