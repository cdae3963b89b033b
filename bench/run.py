"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json`` and its files under
``bench/``.  Set-up (loading, weights from the seed, compiling, the first
steps that the reference checks) counts as ``setup_s``; the window then runs
for ``--seconds``; afterwards the peak device memory is read, the program's
state is freed and the reference decides ``correct``.  With ``--trace 1`` the
window runs under the profiler, for the traffic's ``trace_seconds`` where it
gives fewer (the profiler keeps only part of a long window of many small
operations, and reading such a trace takes minutes), and the line carries
the cell's per-layer metrics instead of its end-to-end ones.

Exits non-zero, with no result line, when JAX finds no TPU or fewer chips
than the cell asks for, or when a file of the benchmark or the program is
missing.  The last line of standard output is the result; the numbers
compared for ``correct`` are the last lines of standard error.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs to /tmp otherwise

from harness import device, spec, trace as trace_lib  # noqa: E402

TRACE_DIR = BENCH / "traces"


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader gets."""

    cell: spec.Cell
    peaks: dict
    chips: int
    counts: dict
    memory_peak_bytes: int
    trace: trace_lib.Trace


class CompileLog:
    """JAX's compile events: seconds by stage, and persistent-cache hits."""

    def __init__(self):
        import jax

        self.seconds, self.events = {}, {}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event.startswith("/jax/core/compile/") or "cache" in event:
            self.seconds[event] = self.seconds.get(event, 0.0) + secs

    def _event(self, event, **_):
        if "compilation_cache" in event:
            self.events[event] = self.events.get(event, 0) + 1


def run_cell(cell, seed: int, seconds: float, traced: bool, devices, peaks,
             t0: float, say=print, phases=None) -> dict:
    """Set up, measure and check one run; returns the result line as a dict.
    ``phases`` holds seconds of set-up already spent, by step.  A traced
    run's trace is read and then deleted: it is large."""
    import jax

    log = CompileLog()
    phases = dict(phases or {}, start=time.perf_counter() - t0)
    drv = spec.driver(cell).Driver(cell, seed)
    drv.setup()
    setup_s = time.perf_counter() - t0
    phases.update(getattr(drv, "phases", {}))
    setup_compile = dict(log.seconds)
    before = log.events.get("/jax/compilation_cache/compile_requests_use_cache", 0)
    trace_dir = TRACE_DIR / f"{cell.name}-{seed}"
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    if traced:
        seconds = min(seconds, cell.traffic.get("trace_seconds", seconds))
    with jax.profiler.TraceAnnotation(trace_lib.WINDOW):
        out = drv.window(seconds)
    if traced:
        jax.profiler.stop_trace()
    window_compiles = log.events.get(
        "/jax/compilation_cache/compile_requests_use_cache", 0) - before
    memory = device.memory_peak_bytes(devices)
    drv.release()
    t_check = time.perf_counter()
    numbers = drv.check()
    check_s = time.perf_counter() - t_check

    limits = cell.limits["limits"]
    checks = {k: dict(value=float(numbers[k]), limit=float(v))
              for k, v in limits.items() if k in numbers}
    correct = (set(checks) == set(limits) and
               all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                   for c in checks.values()))
    dev = dict(platform=devices[0].platform, kind=devices[0].device_kind,
               count=len(devices), memory_peak_bytes=memory)
    metrics, breakdown, kept = {}, None, None
    if traced:
        tr = trace_lib.load(trace_dir)
        run = Run(cell, peaks, len(devices), out["counts"], memory, tr)
        for m in cell.per_layer:
            value = spec.load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = dict(value=float(value), unit=m["unit"])
        dev.update(busy_s=trace_lib.busy_s(tr), window_s=tr.window_s)
        breakdown = dict(device_ops=trace_lib.top_ops(tr), idle_gaps=trace_lib.idle_gaps(tr))
        kept = trace_lib.coverage(tr)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = dict(out["metrics"], setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = dict(value=float(values[m["name"]]), unit=m["unit"])
    say(json.dumps(dict(setup_phases_s=phases, setup_compile_s=setup_compile,
                        cache_events=log.events, window_compiles=window_compiles,
                        counts=out["counts"], trace_kept=kept, check_s=check_s,
                        run_s=time.perf_counter() - t0, numbers=numbers,
                        detail=getattr(drv, "detail", None)),
                   default=str), file=sys.stderr)
    for k, c in checks.items():
        say(f"check {k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    line = dict(correct=bool(correct), attempted=int(out["attempted"]),
                failed=int(out["failed"]), metrics=metrics, device=dev)
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.resolve(args.workload)
        if importlib.util.find_spec("repro") is None:
            raise spec.SpecError("the program (src/repro) is not in this checkout")
        t = time.perf_counter()
        devices = device.require_tpu(cell.chips)
        phases = dict(before_jax=t - T0, jax_and_tpu_init=time.perf_counter() - t)
        peaks = device.peaks(devices[0].device_kind)
    except (spec.SpecError, device.NoChip) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    device.enable_compile_cache()
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices, peaks, T0,
                    phases=phases)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
