"""From a profiler trace to device busy time, kernel time and idle gaps.

The run wraps its measured window in a host annotation named ``WINDOW`` and
each call into the program in a ``bench.*`` annotation.  Device operations
are the events of each TPU plane's ``XLA Ops`` line, named by their HLO
instruction text (``%name = type opcode(operands), ...``); compiled programs
are the events of its ``XLA Modules`` line.  Busy time is the union, inside
the window, of the operations' and the programs' intervals: the profiler
may lose operation events in the middle of a program, and the program's
interval still shows the device busy.  An idle gap is a stretch of the
window in which nothing ran, named after what the host was doing at its
middle.  Operations nest (a ``while`` encloses its body), so per-operation
sums count only operations that enclose no other.  The profiler stops
recording device events (operations and programs alike) after some six
million operations, so a cell of many small operations traces a shorter
window (``trace_seconds``); ``coverage`` shows what the trace kept, and a
kernel's time per program run (``kernel_runs``) counts only the runs whose
operations the trace kept in full.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_OPCODE = re.compile(r" = .*? ([a-z][a-z0-9-]*)\(")

Event = Tuple[str, int, int]  # name, start ns, end ns


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]  # device plane -> operations, by start
    host: List[Event]  # host annotations and runtime events
    window: Tuple[int, int]
    modules: Dict[str, List[Event]] = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def find_xplane(directory) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load(path) -> Trace:
    """Read an ``.xplane.pb`` (or the newest under a directory)."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        path = find_xplane(path)
    data = ProfileData.from_file(str(path))
    ops, modules, host = {}, {}, []

    def events(line):
        return sorted(((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                       for e in line.events), key=lambda e: e[1])

    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = events(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(events(line))
    return from_events(ops, host, modules)


def from_events(ops: Dict[str, List[Event]], host: List[Event],
                modules: Dict[str, List[Event]] = None) -> Trace:
    windows = [e for e in host if e[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} annotation, found {len(windows)}")
    if not ops or not any(ops.values()):
        raise ValueError("no device operation in the trace")
    ops = {k: sorted(v, key=lambda e: e[1]) for k, v in ops.items()}
    return Trace(ops, sorted(host, key=lambda e: e[1]), windows[0][1:], modules or {})


def op_name(text: str) -> str:
    """``name opcode`` of an HLO instruction's text (the text itself if it is
    not one): ``%unpack_bits.1 = u32[..] custom-call(..)`` -> ``unpack_bits.1
    custom-call``."""
    head, sep, _ = text.partition(" = ")
    if not sep:
        return text
    m = _OPCODE.search(text)
    return f"{head.lstrip('%')} {m.group(1)}" if m else head.lstrip("%")


def _leaves(events: List[Event]) -> List[Event]:
    """Operations that enclose no other (events sorted by start)."""
    return [e for i, e in enumerate(events)
            if i + 1 == len(events) or events[i + 1][1] >= e[2]]


def _clip(events: List[Event], lo: int, hi: int) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]


def union(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    """Merged intervals of ``(start, end)`` pairs, clipped to ``[lo, hi]``."""
    merged: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy(trace: Trace, plane: str) -> List[Tuple[int, int]]:
    """Intervals of the window in which an operation or a program ran on
    ``plane``."""
    lo, hi = trace.window
    events = trace.ops[plane] + trace.modules.get(plane, [])
    return union(((s, e) for _, s, e in events), lo, hi)


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    per = [sum(e - s for s, e in busy(trace, plane)) for plane in trace.ops]
    return sum(per) / len(per) / 1e9


def coverage(trace: Trace) -> Dict[str, Dict[str, float]]:
    """Per device: operations and programs in the window, and the seconds
    from the last operation's end to the window's end.  Fewer operations
    than runs alike kept, or a long tail while the host kept calling the
    program, mean the profiler lost events."""
    lo, hi = trace.window
    return {plane: dict(ops=len(_clip(ops, lo, hi)),
                        modules=len(_clip(trace.modules.get(plane, []), lo, hi)),
                        tail_s=(hi - max([lo] + [e for _, _, e in ops if e <= hi])) / 1e9)
            for plane, ops in trace.ops.items()}


def kernel_s(trace: Trace, pattern: str) -> float:
    """Summed device seconds of the operations (enclosing no other) whose
    ``name opcode`` matches ``pattern``, averaged over the devices."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    per = [sum(e - s for n, s, e in _clip(_leaves(evs), lo, hi) if rx.search(op_name(n)))
           for evs in trace.ops.values()]
    return sum(per) / len(per) / 1e9


def module_s(trace: Trace, pattern: str) -> float:
    """Summed device seconds of the compiled programs whose name matches
    ``pattern``, averaged over the devices."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    per = [sum(e - s for n, s, e in _clip(evs, lo, hi) if rx.search(n))
           for evs in trace.modules.values()] or [0]
    return sum(per) / len(per) / 1e9


def kernel_runs(trace: Trace, pattern: str) -> Tuple[float, float]:
    """``(runs, seconds)`` of a kernel counted by the program runs that call
    it: over the program runs inside the window whose calls of the kernel
    (operations that enclose no other, ``name opcode`` matching ``pattern``)
    the trace kept in full, that is, as many as the most any run shows, the
    number of runs and the kernel's summed device seconds, each averaged over
    the devices.  Runs whose operations the profiler dropped are left out."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    runs = secs = 0.0
    for plane, evs in trace.ops.items():
        calls = [(s, e) for n, s, e in _leaves(evs) if rx.search(op_name(n))]
        starts = [s for s, _ in calls]
        per = []
        for _, ms, me in trace.modules.get(plane, []):
            if ms < lo or me > hi:
                continue
            inside = calls[bisect.bisect_left(starts, ms):bisect.bisect_left(starts, me)]
            inside = [(s, e) for s, e in inside if e <= me]
            if inside:
                per.append((len(inside), sum(e - s for s, e in inside)))
        full = max((n for n, _ in per), default=0)
        runs += sum(1 for n, _ in per if n == full)
        secs += sum(t for n, t in per if n == full) / 1e9
    return runs / len(trace.ops), secs / len(trace.ops)


def top_ops(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` operation names (``name opcode``) with the most device time
    in the window, over operations that enclose no other."""
    lo, hi = trace.window
    total: Dict[str, int] = defaultdict(int)
    for evs in trace.ops.values():
        for n, s, e in _clip(_leaves(evs), lo, hi):
            total[op_name(n)] += e - s
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / 1e9 / len(trace.ops)] for n, t in ranked]


class _HostIndex:
    """What the host was doing at a time: the innermost ``bench.*`` span and,
    inside it, the innermost other event."""

    def __init__(self, host: List[Event], lo: int, hi: int):
        import numpy as np

        evs = [e for e in host if e[2] > lo and e[1] < hi and e[0] != WINDOW]
        self.names = [e[0] for e in evs]
        self.start = np.array([e[1] for e in evs], np.int64)
        self.end = np.array([e[2] for e in evs], np.int64)
        self.bench = np.array([n.startswith("bench.") for n in self.names], bool)

    def label(self, t: int) -> str:
        import numpy as np

        cover = (self.start <= t) & (t < self.end)
        if not cover.any():
            return "no host span"
        length = self.end - self.start
        parts, lo, hi = [], None, None
        b = np.flatnonzero(cover & self.bench)
        if b.size:
            i = b[np.argmin(length[b])]
            parts.append(self.names[i])
            lo, hi = self.start[i], self.end[i]
        o = cover & ~self.bench
        if lo is not None:
            o &= (self.start >= lo) & (self.end <= hi)
        o = np.flatnonzero(o)
        if o.size:
            parts.append(self.names[o[np.argmin(length[o])]])
        return " > ".join(parts)[:200] or "no host span"


SHORT_GAP_NS = 10_000


def idle_gaps(trace: Trace, k: int = 10) -> List[List]:
    """Idle seconds of the window summed by what the host was doing, largest
    first (first device only).  Gaps under 10 us are summed apart."""
    lo, hi = trace.window
    gaps, t = [], lo
    for s, e in busy(trace, next(iter(trace.ops))):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    index = _HostIndex(trace.host, lo, hi)
    total: Dict[str, int] = defaultdict(int)
    for s, e in gaps:
        name = ("between operations (<10 us)" if e - s < SHORT_GAP_NS
                else index.label((s + e) // 2))
        total[name] += e - s
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / 1e9] for n, t in ranked]
