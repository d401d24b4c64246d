"""Device idle time inside the program's own phase spans.

The program opens a profiler annotation around each phase of a step
(``scheduler.step``, ``scheduler.admit``, ``engine.decode``,
``kv.prefix_save``, ...).  They land on the host line that carries the
benchmark's ``pb.step`` spans, which is the line :func:`trace.load`
keeps, so only their names and times are read here.  Every share is
over the same denominator as ``trace.idle_share`` (the step spans), and
the shares below cover disjoint host time inside those spans, so their
sum never exceeds the device's idle share.

A trace of a program without spans reads ``None``, never 0: a reading
of 0 must mean the program ran its spans and the device never waited
in them.
"""
from __future__ import annotations

from typing import List, Optional

from perfbench import trace as T

STEP = "scheduler.step"
SAVE = "kv.prefix_save"
INNER = ("engine.", "kv.")      # layers the scheduler's step calls into


def _spans(tr: T.Trace, pred) -> List[T.Interval]:
    return T.union([(s, e) for n, s, e in tr.host if pred(n)])


def _idle(tr: T.Trace, iv: List[T.Interval]) -> float:
    """Seconds of ``iv`` with no operation on the device, averaged over
    the devices."""
    return sum(T.length(iv) - T.length(T.intersect(T.busy(tr, d), iv))
               for d in tr.devices) / len(tr.devices)


def _share(tr: T.Trace, idle_s: float) -> Optional[float]:
    total = T.length(T.steps(tr))
    if total <= 0 or not tr.ops:
        return None
    return 100.0 * idle_s / total


def scheduler_idle_share(tr: T.Trace) -> Optional[float]:
    """Device idle inside ``scheduler.step`` spans but outside every
    ``engine.*`` and ``kv.*`` span, over the step spans' time (%)."""
    sched = _spans(tr, lambda n: n == STEP)
    if not sched or not tr.ops:
        return None
    sched = T.intersect(sched, T.steps(tr))
    inner = T.intersect(sched, _spans(tr, lambda n: n.startswith(INNER)))
    return _share(tr, _idle(tr, sched) - _idle(tr, inner))


def prefix_save_idle_share(tr: T.Trace) -> Optional[float]:
    """Device idle inside ``kv.prefix_save`` spans, over the step spans'
    time (%)."""
    save = _spans(tr, lambda n: n == SAVE)
    if not save or not tr.ops or not _spans(tr, lambda n: n == STEP):
        return None
    return _share(tr, _idle(tr, T.intersect(save, T.steps(tr))))
