"""The named scope each device operation of a profiler trace ran under.

``ProfileData`` gives an operation's name (its HLO text) and its times, but
not the metadata the profiler keeps beside them: each device plane's event
metadata holds, per operation, a ``tf_op`` stat with the ``jax.named_scope``
path it was traced under (``jit(round_fn)/omc.client/.../dot_general:``; a
fusion carries its root operation's, a compiler-inserted copy none).  This
decodes that much of an ``.xplane.pb`` from the protobuf wire format: the
event and stat metadata maps of the device planes, and the host plane's
``bench.window`` event.  Every other event is skipped by its length, so a
trace of millions of operations reads in seconds.

A run's trace is the newest ``.xplane.pb`` under ``traces/<cell>-*/``;
``run.py`` deletes it after the readers ran.  A file whose window is not the
run's is another run's, and is refused.
"""

from __future__ import annotations

import dataclasses
import functools
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from . import trace
from .spec import BENCH

TRACES = BENCH / "traces"
TF_OP = "tf_op"
MARK = "omc."  # the program's scopes all start so
DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
# XSpace.planes; XPlane.name, .lines, .event_metadata, .stat_metadata;
# map entries' key and value; X*Metadata.id, .name; XEventMetadata.stats;
# XStat.metadata_id, .str_value, .ref_value; XLine.timestamp_ns, .events;
# XEvent.metadata_id, .offset_ps, .duration_ps.
_PLANES, _NAME, _LINES, _EVENT_MD, _STAT_MD = 1, 2, 3, 4, 5
_KEY, _VALUE = 1, 2
_MD_STATS = 5
_STAT_ID, _STR, _REF = 1, 5, 7
_LINE_TS, _LINE_EVENTS = 3, 4
_EV_MD, _EV_OFFSET, _EV_DURATION = 1, 2, 3


@dataclasses.dataclass(frozen=True)
class Scopes:
    paths: Dict[str, str]  # device operation name -> its tf_op path
    window: Optional[Tuple[int, int]]  # bench.window, ns as ProfileData has it


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = shift = 0
    while True:
        x = b[i]
        i += 1
        r |= (x & 0x7F) << shift
        if x < 0x80:
            return r, i
        shift += 7


def _fields(b: bytes, i: int, end: int):
    """``(field, value)`` of one message; a length-delimited value is its
    ``(start, end)`` in ``b``."""
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v = (i, i + n)
            i += n
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _map(b: bytes, entries: List[Tuple[int, int]]) -> Dict[int, Tuple[int, int]]:
    """A protobuf map of messages: key -> the value's ``(start, end)``."""
    out = {}
    for lo, hi in entries:
        key, value = 0, (lo, lo)
        for f, v in _fields(b, lo, hi):
            if f == _KEY:
                key = v
            elif f == _VALUE:
                value = v
        out[key] = value
    return out


def _plane(b: bytes, lo: int, hi: int):
    name, lines, events, stats = "", [], [], []
    for f, v in _fields(b, lo, hi):
        if f == _NAME:
            name = _text(b, v)
        elif f == _LINES:
            lines.append(v)
        elif f == _EVENT_MD:
            events.append(v)
        elif f == _STAT_MD:
            stats.append(v)
    return name, lines, _map(b, events), _map(b, stats)


def _named(b: bytes, md: Dict[int, Tuple[int, int]]) -> Dict[int, str]:
    out = {}
    for key, (lo, hi) in md.items():
        out[key] = next((_text(b, v) for f, v in _fields(b, lo, hi) if f == _NAME), "")
    return out


def _tf_ops(b: bytes, events, stats) -> Dict[str, str]:
    """Operation name -> ``tf_op`` path, over one device plane's metadata."""
    names = _named(b, stats)
    tf_op = next((k for k, n in names.items() if n == TF_OP), None)
    out = {}
    if tf_op is None:
        return out
    for lo, hi in events.values():
        name, path = None, None
        for f, v in _fields(b, lo, hi):
            if f == _NAME:
                name = _text(b, v)
            elif f == _MD_STATS:
                stat = dict(_fields(b, *v))
                if stat.get(_STAT_ID) == tf_op:
                    path = (_text(b, stat[_STR]) if _STR in stat
                            else names.get(stat.get(_REF), ""))
        if name is not None and path:
            out[name] = path
    return out


def _window(b: bytes, lines, events) -> Optional[Tuple[int, int]]:
    """``bench.window`` on the host plane, as ``ProfileData`` times it: the
    line's ``timestamp_ns`` plus the event's offset."""
    ids = [k for k, n in _named(b, events).items() if n == trace.WINDOW]
    if len(ids) != 1:
        return None
    key = bytes([_EV_MD << 3]) + _encode_varint(ids[0])
    found = []
    for lo, hi in lines:
        ts, i = 0, lo
        while i < hi:  # the line's fields, events skipped unless the window's
            k, i = _varint(b, i)
            if k & 7 != 2:  # XLine's other fields are varints
                v, i = _varint(b, i)
                if k >> 3 == _LINE_TS:
                    ts = v
                continue
            n, i = _varint(b, i)
            if k >> 3 == _LINE_EVENTS and b[i:i + len(key)] == key:
                ev = dict(_fields(b, i, i + n))
                start = ev.get(_EV_OFFSET, 0) / 1000
                found.append((int(ts + start),
                              int(ts + start + ev.get(_EV_DURATION, 0) / 1000)))
            i += n
    return found[0] if len(found) == 1 else None


def _encode_varint(v: int) -> bytes:
    out = bytearray()
    while v >= 0x80:
        out.append(v & 0x7F | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


@functools.lru_cache(maxsize=1)
def _read(path: str, mtime_ns: int, size: int) -> Scopes:
    b = Path(path).read_bytes()
    paths, window = {}, None
    for f, v in _fields(b, 0, len(b)):
        if f != _PLANES:
            continue
        name, lines, events, stats = _plane(b, *v)
        if name.startswith(DEVICE_PLANE):
            paths.update(_tf_ops(b, events, stats))
        elif name == HOST_PLANE:
            window = _window(b, lines, events)
    return Scopes(paths, window)


def read(path) -> Scopes:
    """The scope paths and the window of an ``.xplane.pb``."""
    st = Path(path).stat()
    return _read(str(path), st.st_mtime_ns, st.st_size)


def for_run(run) -> Optional[Scopes]:
    """The scopes of the run's own trace, or None: no trace file of the
    cell, or the newest is another run's (its window differs)."""
    found = list(TRACES.glob(f"{run.cell.name}-*/**/*.xplane.pb"))
    if not found:
        return None
    scopes = read(max(found, key=lambda p: p.stat().st_mtime_ns))
    if scopes.window is None or any(
            abs(a - b) > 1000 for a, b in zip(scopes.window, run.trace.window)):
        return None
    return scopes


_LEAF_NS: Dict[int, Tuple[trace.Trace, Dict[str, int]]] = {}


def _leaf_ns(tr: trace.Trace) -> Dict[str, int]:
    """Device ns inside the window by operation name, over operations that
    enclose no other, summed over the devices; kept for the last trace, as
    each of a run's readers asks for it."""
    hit = _LEAF_NS.get(id(tr))
    if hit is None or hit[0] is not tr:
        lo, hi = tr.window
        ns: Dict[str, int] = {}
        for evs in tr.ops.values():
            for n, s, e in trace._clip(trace._leaves(evs), lo, hi):
                ns[n] = ns.get(n, 0) + e - s
        _LEAF_NS.clear()
        hit = _LEAF_NS[id(tr)] = (tr, ns)
    return hit[1]


def share(run, scope: str) -> Optional[float]:
    """Percent of the window's device time, over operations that enclose no
    other, spent in operations whose ``tf_op`` path contains ``scope``.
    None when the run's trace cannot be read, or when no operation carries
    any ``omc.`` scope: a program from before the scopes.  A scope whose
    operations XLA fused into another scope's reads 0."""
    scopes = for_run(run)
    if scopes is None or not any(MARK in p for p in scopes.paths.values()):
        return None
    ns = _leaf_ns(run.trace)
    total = sum(ns.values())
    inside = sum(t for n, t in ns.items() if scope in scopes.paths.get(n, ""))
    return 100.0 * inside / total if total > 0 else None
