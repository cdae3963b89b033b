"""Resolve a workload of ``BENCHMARK.json`` to the files that define it.

A cell ``<config>.<traffic>`` is found by name: its configuration
(``configs/<config>.json``, as ``BENCHMARK.json`` names it), its traffic mix
(``traffic/<traffic>.json``, which names the driver that runs it), its
correctness limits (``limits/<workload>.json``) and the reader of each
per-layer metric it reports (``metrics/<metric>.py``, or for a metric split
by cell group, ``device_idle_share.train``, the quantity's
``metrics/device_idle_share.py``).  Adding a cell,
configuration or metric adds files and entries; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class SpecError(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _json(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise SpecError(f"missing file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return _json(root / "BENCHMARK.json")


def _applies(metric: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(workload: str, bench: Dict[str, Any] = None) -> Cell:
    bench = bench if bench is not None else load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"unknown workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(ROOT / configs[w["config"]]["file"])
    traffic = _json(BENCH / "traffic" / f"{w['traffic']}.json")
    limits = _json(BENCH / "limits" / f"{workload}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    for m in layer:
        metric_file(m["name"])
    return Cell(workload, int(w["chips"]), config, traffic, limits, e2e, layer)


def metric_file(name: str) -> Path:
    """``metrics/<name>.py``, else the reader of the quantity that the name
    splits by cell group: ``metrics/<name up to its first dot>.py``."""
    for stem in (name, name.split(".")[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.is_file():
            return path
    raise SpecError(f"no reader for metric {name!r} under {(BENCH / 'metrics').relative_to(ROOT)}")


def load_reader(name: str):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  metric_file(name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def driver(cell: Cell):
    """The driver module ``drivers/<traffic driver>.py``."""
    import importlib

    return importlib.import_module(f"drivers.{cell.traffic['driver']}")
