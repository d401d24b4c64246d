"""Reduction of a profiler trace to device busy time, kernel time and
idle gaps.

A trace is read into flat event lists: the device's operations and
programs (from the planes and lines ``programs.json`` names), and the
host thread that carries the benchmark's own ``TraceAnnotation`` spans.
Each kernel is declared in a file of its own, ``kernels/<kernel>.json``:
the list of HLO name patterns of its operations.  All times are seconds
on the profiler's clock.
"""
from __future__ import annotations

import bisect
import glob
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

HERE = Path(__file__).parent
NAMES = json.loads((HERE / "programs.json").read_text())
NAMES["kernels"] = {p.stem: json.loads(p.read_text())
                    for p in sorted((HERE / "kernels").glob("*.json"))}

Interval = Tuple[float, float]
Event = Tuple[str, float, float]            # (name, start_s, end_s)


class MissingKernel(RuntimeError):
    """A kernel the benchmark counted work for has no event in the trace."""


@dataclass
class Trace:
    ops: Dict[str, List[Event]] = field(default_factory=dict)      # device
    modules: Dict[str, List[Event]] = field(default_factory=dict)  # device
    host: List[Event] = field(default_factory=list)   # annotated thread

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)


def load(trace_dir: str) -> Trace:
    """Read the ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {trace_dir}, "
                           f"found {paths}")
    pd = ProfileData.from_file(paths[0])
    out = Trace()
    host_lines: List[List[Event]] = []
    for plane in pd.planes:
        dev = plane.name.startswith(NAMES["device_plane_prefix"])
        for line in plane.lines:
            evs = [(e.name, e.start_ns * 1e-9,
                    (e.start_ns + e.duration_ns) * 1e-9)
                   for e in line.events]
            if dev and line.name == NAMES["ops_line"]:
                out.ops[plane.name] = evs
            elif dev and line.name == NAMES["modules_line"]:
                out.modules[plane.name] = evs
            elif plane.name == NAMES["host_plane"]:
                host_lines.append(evs)
    for evs in host_lines:
        if any(n == NAMES["step_span"] for n, _, _ in evs):
            out.host = evs
            break
    return out


def union(iv: Sequence[Interval]) -> List[Interval]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(iv: Sequence[Interval]) -> float:
    return sum(b - a for a, b in iv)


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Intersection of two disjoint sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def steps(tr: Trace) -> List[Interval]:
    """Host intervals of the benchmark's gateway-step spans."""
    return union([(s, e) for n, s, e in tr.host if n == NAMES["step_span"]])


def busy(tr: Trace, device: str) -> List[Interval]:
    return union([(s, e) for _, s, e in tr.ops.get(device, [])])


def busy_seconds(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not tr.ops:
        return 0.0
    return sum(length(busy(tr, d)) for d in tr.devices) / len(tr.devices)


def idle_share(tr: Trace) -> Optional[float]:
    """1 - (device busy inside step spans) / (step spans), averaged over
    the devices: time waiting for arrivals is not counted."""
    st = steps(tr)
    total = length(st)
    if total <= 0 or not tr.ops:
        return None
    shares = [1.0 - length(intersect(busy(tr, d), st)) / total
              for d in tr.devices]
    return sum(shares) / len(shares)


def op_name(name: str) -> str:
    """An operation's own name: ``%fusion.12 = bf16[..] fusion(..)`` ->
    ``fusion.12`` (the trace names operations by their HLO line)."""
    return name.split(" = ", 1)[0].lstrip("%")


def matches(name: str, patterns: Sequence[str]) -> bool:
    """True when the operation or program is named by one of
    ``patterns``: its own name starts with one of them."""
    own = op_name(name)
    return any(own.startswith(p) for p in patterns)


def _timed(lines: Dict[str, List[Event]], patterns: Sequence[str]
           ) -> Tuple[float, int]:
    t, n = 0.0, 0
    for evs in lines.values():
        for name, s, e in evs:
            if matches(name, patterns):
                t += e - s
                n += 1
    return t, n


def op_time(tr: Trace, patterns: Sequence[str]) -> Tuple[float, int]:
    """Summed device seconds and count of operations named by
    ``patterns``, over all devices."""
    return _timed(tr.ops, patterns)


def module_time(tr: Trace, patterns: Sequence[str]) -> Tuple[float, int]:
    """Summed device seconds and count of program executions named by
    ``patterns``, over all devices."""
    return _timed(tr.modules, patterns)


def kernel_seconds(tr: Trace, kernel: str) -> float:
    """Device seconds of a kernel declared in ``kernels/``; raises
    :class:`MissingKernel` when the trace holds none of its events."""
    pats = NAMES["kernels"][kernel]
    t, n = op_time(tr, pats)
    if n == 0:
        raise MissingKernel(f"no event of kernel {kernel!r} (names {pats}) "
                            f"in the trace")
    return t


def top_ops(tr: Trace, k: int = 10) -> List[list]:
    """The ``k`` operation names (numbering dropped) that took most device
    time, in seconds averaged over devices.  A loop's operation contains
    the operations of its body, so both appear."""
    tot: Dict[str, float] = {}
    for evs in tr.ops.values():
        for name, s, e in evs:
            key = op_name(name).rstrip("0123456789").rstrip(".")
            tot[key] = tot.get(key, 0.0) + (e - s)
    nd = max(len(tr.ops), 1)
    return [[n, t / nd] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(tr: Trace, k: int = 10) -> List[list]:
    """Device idle time inside step spans, summed by what the host was
    doing when each gap began (the innermost host event covering it)."""
    if not tr.ops:
        return []
    st = steps(tr)
    dev = tr.devices[0]
    b = busy(tr, dev)
    free, t = [], float("-inf")
    for bl, bh in b:
        free.append((t, bl))
        t = bh
    free.append((t, float("inf")))
    gaps = intersect(free, st)
    host = sorted(tr.host, key=lambda e: (e[1], -e[2]))   # outer first
    starts = [e[1] for e in host]
    tot: Dict[str, float] = {}
    for lo, hi in gaps:
        label, best = "no host event", None
        i = bisect.bisect_right(starts, lo)
        for name, s, e in host[max(0, i - 512):i]:
            if e > lo and (best is None or e - s <= best):
                best, label = e - s, name
        tot[label] = tot.get(label, 0.0) + (hi - lo)
    return [[n, t] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]

