"""chip_smoke.py on the CPU: every phase at a tiny size through the jnp
oracles, the four-chip comparison on four virtual devices, and the refusal
to run at all without a TPU."""

import importlib.util
import os
import subprocess
import sys

import pytest

from repro.configs import qwen2_5_3b
from repro.models import conformer as cf

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

# Shapes no other test traces, so this file's kernel dispatches are fresh.
TINY = cf.ConformerConfig(n_layers=2, d_model=40, n_heads=4, d_ff=72,
                          n_classes=24, d_in=12)
SIZES = dict(batch=2, frames=16, local_steps=2)
ENV = {"PYTHONPATH": os.path.join(ROOT, "src"), "PATH": "/usr/bin:/bin",
       "JAX_PLATFORMS": "cpu"}


@pytest.fixture(scope="module")
def clock():
    return chip_smoke.CompileClock()


def test_train_and_wire_phases(clock, capsys):
    chip_smoke.ops.reset_dispatch_counts()
    unfused = chip_smoke.train_phase(TINY, fused=False, clock=clock, **SIZES)
    fused = chip_smoke.train_phase(TINY, fused=True, clock=clock, **SIZES)
    close = chip_smoke.compare_fused(unfused, fused)
    assert close["max_abs_diff"] <= chip_smoke.FUSED_MAX
    chip_smoke.check_dispatch("fused_aggregate", backend="ref")
    wire = chip_smoke.wire_phase(fused[0])
    assert wire["body_bytes"] < wire["payload_bytes"]
    chip_smoke.check_dispatch("unpack_bits", backend="ref")
    assert capsys.readouterr().out.count("[train] ") == 2


def test_check_dispatch_refuses_other_backends(monkeypatch):
    monkeypatch.setattr(chip_smoke.ops, "dispatch_counts",
                        lambda: {"pack_bits.pallas": 1, "unpack_bits.ref": 1})
    with pytest.raises(RuntimeError, match="dispatch wrong"):
        chip_smoke.check_dispatch("pack_bits")
    with pytest.raises(RuntimeError, match="dispatch wrong"):
        chip_smoke.check_dispatch("fused_aggregate", backend="ref")


def test_serve_phase(clock):
    out = chip_smoke.serve_phase(qwen2_5_3b, qwen2_5_3b.smoke_config(),
                                 clock=clock, batch=2, prompt_len=8, gen=3,
                                 calls=1)
    assert out["tokens_generated"] == 2 * 3 * 2
    assert out["swap_stall_s"] > 0


_FOUR = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import chip_smoke
from repro.models import conformer as cf
cfg = cf.ConformerConfig(n_layers=2, d_model=32, n_heads=4, d_ff=64,
                         n_classes=16, d_in=8)
out = chip_smoke.four_chip_phase(cfg, batch=2, frames=16)
assert sorted(out["four_devices"]["bytes_in_use"]) == [0, 1, 2, 3]
assert [c["shard"] for c in out["four_devices"]["chunks"]] == [0, 1, 2, 3]
assert out["ef_max_diff"] <= 1e-6, out
print("FOUR-OK")
"""


def test_four_chip_phase_on_virtual_devices():
    r = subprocess.run([sys.executable, "-c", _FOUR, ROOT], env=ENV,
                       capture_output=True, text=True, timeout=600)
    assert "FOUR-OK" in r.stdout, r.stdout[-4000:] + r.stderr[-4000:]


def test_refuses_without_tpu():
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=ENV, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr
