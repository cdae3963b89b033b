"""``repro.obs`` — telemetry for every training/serving path (DESIGN.md §15).

One handle, three layers:

  * **metrics** (:mod:`repro.obs.metrics`) — scalar bundles (loss,
    per-leaf quant-error norms, EF residual norms, alive counts)
    assembled host-side from program outputs and folded into a
    :class:`~repro.obs.metrics.MetricsSink`,
  * **tracing** (:mod:`repro.obs.trace`) — wall-clock spans (compile,
    dispatch, flush, hot-swap) plus virtual-clock spans for the async
    engine's simulated timeline; every span site also writes an
    ``omc.<name>`` host event into the JAX profiler's trace while one is
    recording (:func:`null_span`),
  * **export** (:mod:`repro.obs.export`) — JSONL event log +
    Chrome-trace/Perfetto JSON under ``experiments/obs/``, rendered by
    ``python -m repro.obs.report``.

The contract every instrumented call site honors: ``obs=None`` (the
default everywhere) must be a **true no-op** — no extra program outputs,
no ``Tracer`` spans, no files; only the profiler annotation, which
records nothing unless a profiler is running — so the tier-1
bit-identity gates between paths are untouched; and with ``obs``
*enabled*, compiled round programs only expose values they already
compute (the cohort mean) as extra outputs — all bundle math
(update/quant-error/EF norms) runs **eagerly on the host** after the
program returns, so the compiled round math is untouched and trained
trees and wire ledgers stay bit/byte-identical (gated in tier-1).

Typical use::

    obs = Obs(run_name="engine_c8")
    storage, hist = run_training_vectorized(..., obs=obs)
    paths = obs.flush()          # experiments/obs/engine_c8.{obs.jsonl,perfetto.json}
    # python -m repro.obs.report experiments/obs/engine_c8.obs.jsonl
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

import jax

from repro.obs.metrics import Bundle, MetricsSink
from repro.obs.trace import Span, Tracer

__all__ = ["Obs", "MetricsSink", "Tracer", "Span", "Bundle", "null_span"]

DEFAULT_OUT_DIR = os.path.join("experiments", "obs")


class Obs:
    """Per-run telemetry handle: a sink + a tracer + export plumbing.

    ``metrics=False`` keeps the compiled programs bundle-free (spans
    only); ``trace=False`` drops span recording.  Call sites must accept
    ``obs=None`` and treat it as fully disabled.
    """

    def __init__(self, run_name: str = "run", out_dir: Optional[str] = None,
                 *, metrics: bool = True, trace: bool = True) -> None:
        self.run_name = str(run_name)
        self.out_dir = out_dir if out_dir is not None else DEFAULT_OUT_DIR
        self.sink = MetricsSink()
        self.tracer: Optional[Tracer] = Tracer() if trace else None
        self._metrics = bool(metrics)

    @property
    def collect_metrics(self) -> bool:
        """Whether compiled programs should emit metric bundles."""
        return self._metrics

    def record(self, kind: str, bundle: Optional[Bundle] = None,
               **fields: Any) -> Dict[str, Any]:
        return self.sink.record(kind, bundle, **fields)

    def span(self, name: str, **args: Any):
        return null_span(self, name, **args)

    def vspan(self, name: str, ts: float, dur: float, **args: Any) -> None:
        if self.tracer is not None:
            self.tracer.vspan(name, ts, dur, **args)

    def flush(self) -> Dict[str, str]:
        """Write the JSONL (+ Perfetto when tracing) artifacts; return paths.

        Prepends a ``kind=meta`` record carrying the run name and the
        kernel dispatch counters accumulated so far (``kernels/ops.py``),
        so a single JSONL is a self-contained health record.
        """
        from repro.kernels import ops as kernel_ops
        from repro.obs.export import export_run

        meta = {
            "kind": "meta",
            "run": self.run_name,
            "dispatch_counts": kernel_ops.dispatch_counts(),
        }
        return export_run(
            self.out_dir, self.run_name,
            [meta] + self.sink.records(), self.tracer,
        )


class null_span:
    """The one span helper of every instrumented call site, used as
    ``with null_span(obs, name, **args) as args:``.

    Always enters a profiler annotation ``omc.<name>`` (a
    ``StepTraceAnnotation`` numbered ``step`` when given), which costs one
    check and records nothing unless a profiler is running; with an
    ``obs`` that traces, also records a wall span ``name`` on its
    :class:`Tracer`.  ``as`` binds the mutable ``args`` dict.  A class,
    not a generator: it sits on host loops of thousands of calls a second.
    """

    __slots__ = ("_note", "_span", "_args")

    def __init__(self, obs: Optional[Obs], name: str, *,
                 step: Optional[int] = None, **args: Any) -> None:
        if step is None:
            self._note = jax.profiler.TraceAnnotation("omc." + name)
        else:
            self._note = jax.profiler.StepTraceAnnotation("omc." + name,
                                                          step_num=step)
        self._span = (obs.tracer.span(name, **args)
                      if obs is not None and obs.tracer is not None else None)
        self._args = args

    def __enter__(self) -> Dict[str, Any]:
        self._note.__enter__()
        return self._args if self._span is None else self._span.__enter__()

    def __exit__(self, *exc) -> None:
        try:
            if self._span is not None:
                self._span.__exit__(*exc)
        finally:
            self._note.__exit__(*exc)
