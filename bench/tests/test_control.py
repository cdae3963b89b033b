"""Each cell's control at smoke size: the program passes the cell's limits
and the control does not, so the comparison that decides ``correct`` can fail.

The control is the cell's reference put in the program's place, computed in
the precision below the one its configuration states (for the wire codec,
whose promise is a bit-exact round trip: the codes one bit narrower).  On
the chip the same readings, at the cells' own sizes, set the limits
(``bench/calibrate.py``; PERF.md section 4).  A served model's gaps grow
with depth and with the number of tokens compared, so at smoke size the
serving control is held to reading well above the program rather than to
the full-size limit."""

import pytest

from harness import spec
import tiny

SEEDS = [3_000_000_101, 3_000_000_102, 3_000_000_103]


def readings(workload, seed, size=None, traffic=None, seconds=0.3):
    cell = tiny.cell(workload)
    cell.config.update(size or {})
    cell.traffic.update(traffic or {})
    drv = spec.driver(cell).Driver(cell, seed)
    drv.setup()
    drv.window(seconds)
    drv.release()
    return cell.limits["limits"], drv.check(control=True)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("workload", ["conformer_s.train_local5",
                                      "conformer_s.wire_roundtrip"])
def test_control_fails_and_program_passes(workload, seed):
    limits, (program, control) = readings(workload, seed)
    assert all(program[k] <= limits[k] for k in limits), program
    assert any(control[k] > limits[k] for k in limits), control


SERVE_SIZE = dict(n_layers=4, d_model=2048, n_heads=16, n_kv_heads=2, head_dim=128,
                  d_ff=4096, vocab=4096)


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_serving_control_reads_far_above_the_program(seed):
    limits, (program, control) = readings("qwen2_5_3b.serve_stream", seed, SERVE_SIZE,
                                          dict(batch=4, new_tokens=16), seconds=5)
    assert program["served_gap"] <= limits["served_gap"]
    assert control["served_gap"] > max(0.1, 10 * program["served_gap"]), control
