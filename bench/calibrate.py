"""Readings that set a cell's correctness limits, on the chip, in one process.

    python bench/calibrate.py --workload <name> --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds 30 --out readings.jsonl

For every seed: set up the program from the seed, run a window of
``--seconds``, free the program's state and compare with the reference (the
program's readings, whose largest sets the lower end of each limit).  For the
control seeds, also the control against the reference (the control's
readings, whose smallest sets the upper end) and, where the driver plants
faults in its reference, their readings.  Programs compile once and are
lent from one seed to the next.  Each seed prints one JSON line; the benchmark's
own runs never run the control.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from harness import device, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    device.require_tpu(cell.chips)
    device.enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    mod = spec.driver(cell)
    out = open(args.out, "a") if args.out else None
    prior = None
    for seed in sorted(set(seeds) | control, key=lambda s: (s not in control, s)):
        t = time.perf_counter()
        drv = mod.Driver(cell, seed)
        drv.setup(prior=prior)
        drv.window(args.seconds)
        drv.release()
        res = drv.check(control=seed in control)
        rec = dict(workload=cell.name, seed=seed, seconds=time.perf_counter() - t,
                   control_precision=cell.config.get("control"))
        if seed in control:
            rec["program"], rec["control"] = res
            if hasattr(drv, "faults"):
                detail = drv.detail
                rec["faults"] = drv.faults()
                drv.detail = dict(detail, half_batch=drv.fault_detail)
        else:
            rec["program"] = res
        rec["detail"] = getattr(drv, "detail", None)
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
        prior = drv
    return 0


if __name__ == "__main__":
    sys.exit(main())
