"""Every workload resolves to its files, ``BENCHMARK.json`` keeps to its
format, and the harness refuses to run without a TPU."""

import json
import re
import shutil
import subprocess
import sys

import pytest

from harness import spec

BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves(workload):
    cell = spec.resolve(workload)
    assert cell.chips in (1, 4)
    assert cell.config["name"] == workload.split(".")[0]
    mod = spec.driver(cell)
    assert hasattr(mod, "Driver")
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in names
        assert callable(spec.load_reader(m["name"]))
    assert set(cell.limits["limits"])


def test_split_metric_names_share_their_quantity_reader():
    assert spec.metric_file("device_idle_share.serve") == spec.metric_file(
        "device_idle_share.train") == spec.BENCH / "metrics" / "device_idle_share.py"
    assert spec.metric_file("mfu.wire").name == "mfu.wire.py"
    with pytest.raises(spec.SpecError):
        spec.metric_file("no_such_metric.train")


def test_benchmark_format():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (spec.ROOT / c["file"]).is_file()
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and len(w["why"]) <= 200
        names.append(w["name"])
    layers = {}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert m["better"] in ("lower", "higher")
        assert re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", m["unit"])
        names.append(m["name"])
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"], m["layer"])
        assert set(m["workloads"]) <= set(WORKLOADS)
    assert all(NAME.match(n) for n in names) and len(names) == len(set(names))
    assert len(json.dumps(BENCH)) < 64 * 1024


def _run(cwd, *extra, env=None):
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run([sys.executable, "bench/run.py", "--workload", WORKLOADS[0],
                           "--seed", "3000000001", "--seconds", "1", "--trace", "0", *extra],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    res = _run(spec.ROOT)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "no TPU" in res.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("traces", "__pycache__"))
    res = _run(tmp_path)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
