"""Span tracer: wall-clock and virtual-clock timing (DESIGN.md §15).

Two clocks, one span type:

  * **wall** spans time host-side phases — compile, dispatch, flush,
    hot-swap, payload encode/decode — on the profiler's clock
    (``time.time_ns``, what TraceMe stamps host events with on Linux), so
    a recorded span lines up with its ``omc.*`` event in a profiler trace
    (:func:`repro.obs.null_span` writes both).
  * **virtual** spans carry the async engine's simulated clock: a client
    round is a span at its check-in timestamp with the sampled latency as
    duration.  Virtual spans are *constructed*, never timed — the async
    event loop already knows both endpoints when the event fires.

The tracer is append-only and cheap (one list append per span); export
to Chrome-trace/Perfetto JSON lives in :mod:`repro.obs.export` so the
hot path never touches the filesystem.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional

#: span categories (the ``cat`` field) — keep in sync with DESIGN.md §15
WALL = "wall"
VIRTUAL = "virtual"


@dataclass(frozen=True)
class Span:
    """One closed interval on either clock.

    ``ts``/``dur`` are **seconds** on the span's own clock: wall spans are
    absolute (``time.time_ns() / 1e9``, the profiler's clock), virtual
    spans use the async engine's simulated time directly.  ``parent`` is
    the name of the wall span open when this one started (the span that
    caused it), ``None`` at top level and for virtual spans.
    """

    name: str
    ts: float
    dur: float
    cat: str = WALL
    args: Dict[str, Any] = field(default_factory=dict)
    parent: Optional[str] = None

    @property
    def end(self) -> float:
        return self.ts + self.dur


class Tracer:
    """Collects :class:`Span`\\ s for one run; thread-unsafe by design.

    All recording funnels through :meth:`add`; :meth:`span` is the
    wall-clock context manager and :meth:`vspan` the virtual-clock
    constructor.  Call sites go through :func:`repro.obs.null_span`.
    Open wall spans form a stack, whose top is the next span's parent.
    """

    def __init__(self) -> None:
        self._spans: List[Span] = []
        self._open: List[str] = []

    def __len__(self) -> int:
        return len(self._spans)

    def add(self, span: Span) -> Span:
        self._spans.append(span)
        return span

    @contextmanager
    def span(self, name: str, **args: Any) -> Iterator[Dict[str, Any]]:
        """Wall-clock span around a ``with`` body.

        Yields the mutable ``args`` dict so the body can attach results
        (e.g. byte counts) before the span closes.
        """
        parent = self._open[-1] if self._open else None
        self._open.append(name)
        t0 = time.time_ns()
        try:
            yield args
        finally:
            t1 = time.time_ns()
            self._open.pop()
            self.add(Span(name=name, ts=t0 / 1e9, dur=(t1 - t0) / 1e9,
                          args=args, parent=parent))

    def vspan(self, name: str, ts: float, dur: float, **args: Any) -> Span:
        """Record a virtual-clock span at simulated time ``ts``."""
        return self.add(
            Span(name=name, ts=float(ts), dur=float(dur), cat=VIRTUAL,
                 args=args)
        )

    def spans(self, cat: Optional[str] = None,
              name: Optional[str] = None) -> List[Span]:
        out = self._spans
        if cat is not None:
            out = [s for s in out if s.cat == cat]
        if name is not None:
            out = [s for s in out if s.name == name]
        return list(out)
