"""The span readers on small synthetic traces: device idle inside the
program's own phase spans."""
import random
from types import SimpleNamespace

import pytest

from perfbench import harness, spans
from perfbench import trace as T

DEV = "/device:TPU:0"
STEP = T.NAMES["step_span"]


def _trace(program_spans=True):
    ops = [("%while.1 = (..) while(..)", 1.0, 1.2),
           ("%fusion.2 = f32[..] fusion(..)", 1.5, 1.9),
           ("%copy.3 = bf16[..] copy(..)", 3.0, 3.1)]
    host = [(STEP, 0.9, 2.0), (STEP, 2.9, 3.3),
            ("DevicePut", 1.21, 1.25)]
    if program_spans:
        host += [
            ("gateway.step", 0.92, 1.98),
            ("scheduler.step", 0.95, 1.95),
            ("engine.decode", 1.0, 1.3),        # idle 1.2-1.3
            ("kv.prefix_save", 1.3, 1.45),      # idle throughout
            ("engine.sample", 1.5, 1.9),        # busy throughout
            ("gateway.step", 2.92, 3.28),
            ("scheduler.step", 2.95, 3.25),
            ("kv.prefix_load", 3.0, 3.1),       # busy throughout
        ]
    return T.Trace(ops={DEV: ops}, host=sorted(host, key=lambda e: e[1]))


def test_shares_read_idle_inside_their_spans():
    tr = _trace()
    steps = 1.1 + 0.4
    # scheduler.step outside engine/kv spans: 0.95-1.0, 1.45-1.5,
    # 1.9-1.95 and 2.95-3.0, 3.1-3.25, all idle
    assert spans.scheduler_idle_share(tr) == pytest.approx(
        100 * (0.05 * 4 + 0.15) / steps)
    assert spans.prefix_save_idle_share(tr) == pytest.approx(
        100 * 0.15 / steps)
    total = spans.scheduler_idle_share(tr) + spans.prefix_save_idle_share(tr)
    assert total <= 100 * T.idle_share(tr)


def test_trace_without_program_spans_reads_none():
    tr = _trace(program_spans=False)
    assert T.idle_share(tr) is not None
    assert spans.scheduler_idle_share(tr) is None
    assert spans.prefix_save_idle_share(tr) is None
    # a program with spans that saved no prefix block reads no save share
    tr = _trace()
    tr.host = [e for e in tr.host if e[0] != "kv.prefix_save"]
    assert spans.prefix_save_idle_share(tr) is None
    assert spans.scheduler_idle_share(tr) > 0
    # no device events: nothing to read either
    assert spans.scheduler_idle_share(T.Trace(host=_trace().host)) is None


@pytest.mark.parametrize("seed", range(8))
def test_shares_never_exceed_the_idle_share(seed):
    """Random device ops and nested program spans: the two shares cover
    disjoint idle time inside the step spans."""
    rnd = random.Random(seed)
    ops, host, t = [], [], 0.0
    for _ in range(20):
        a = t + rnd.uniform(0.0, 0.2)
        b = a + rnd.uniform(0.3, 1.0)
        host.append((STEP, a, b))
        s0, s1 = a + rnd.uniform(-0.05, 0.05), b - rnd.uniform(0.0, 0.05)
        host.append(("scheduler.step", s0, s1))
        u = s0
        for name in ("engine.prefill_round", "kv.prefix_save",
                     "engine.decode", "engine.sample"):
            lo = u + rnd.uniform(0.0, 0.05)
            hi = min(lo + rnd.uniform(0.0, 0.2), s1)
            if lo < hi:
                host.append((name, lo, hi))
                u = hi
        for _ in range(5):
            lo = rnd.uniform(a - 0.1, b)
            ops.append(("%fusion = f32[] fusion()", lo,
                        lo + rnd.uniform(0.0, 0.15)))
        t = b
    tr = T.Trace(ops={DEV: ops}, host=host)
    sched = spans.scheduler_idle_share(tr)
    save = spans.prefix_save_idle_share(tr)
    assert sched >= 0 and save >= 0
    assert sched + save <= 100 * T.idle_share(tr) + 1e-9


def test_metric_readers_read_the_spans():
    ctx = SimpleNamespace(trace=_trace())
    for cell in ("tail", "batch"):
        for metric, fn in (("scheduler_idle_share", spans.scheduler_idle_share),
                           ("prefix_save_idle_share",
                            spans.prefix_save_idle_share)):
            read = harness.reader(f"{metric}.{cell}")
            assert read(ctx) == fn(ctx.trace)
            assert read(SimpleNamespace(trace=None)) is None
