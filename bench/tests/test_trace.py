"""The reduction from a profiler trace to busy time, kernel time, idle gaps,
and the roofline and utilization arithmetic of the metric readers."""

from pathlib import Path

import pytest

from harness import spec, trace

RECORDED = Path(__file__).parent / "data" / "small_trace.xplane.pb"
MS = 1_000_000


def synthetic():
    ops = {"/device:TPU:0": [
        ("%while.1 = (f32[8]) while((f32[8]) %t), body=%b", 0, 7 * MS // 2),
        ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 0, 2 * MS),
        ("%pack_bits.1 = u32[11]{0} custom-call(u32[32]{0} %c)", 2 * MS, 7 * MS // 2),
        ("%fusion.2 = f32[8]{0} fusion(u32[11]{0} %pack_bits.1)", 5 * MS, 6 * MS),
        ("%unpack_bits.1 = u32[32]{0} custom-call(u32[11]{0} %w)", 8 * MS, 12 * MS)]}
    modules = {"/device:TPU:0": [("jit_pack_bits(123)", 0, 7 * MS // 2),
                                 ("jit_other(5)", 5 * MS, 6 * MS),
                                 ("jit_unpack_bits(456)", 8 * MS, 12 * MS)]}
    host = [(trace.WINDOW, 0, 10 * MS), ("bench.encode", 2 * MS, 6 * MS),
            ("PjitFunction(pack)", 2 * MS, 5 * MS), ("bench.decode", 6 * MS, 10 * MS)]
    return trace.from_events(ops, host, modules)


def test_busy_is_the_union_inside_the_window():
    t = synthetic()
    # [0,3.5] + [5,6] + [8,10] clipped to the window
    assert trace.busy_s(t) == pytest.approx(6.5e-3)
    assert t.window_s == pytest.approx(10e-3)


def test_op_names():
    assert trace.op_name("%pack_bits.1 = u32[11,8192]{1,0:T(8,128)S(1)} custom-call("
                         "u32[32,8192]{1,0} %bitcast.2), custom_call_target=\"x\"") == \
        "pack_bits.1 custom-call"
    assert trace.op_name("%while.3 = (s32[]{:T(128)}, f32[8]{0}) while((s32[], f32[8]) "
                         "%tuple), condition=%c") == "while.3 while"
    assert trace.op_name("plain") == "plain"


def test_kernel_time_by_name():
    t = synthetic()
    # the enclosing while is not counted; operands that name a kernel are not it
    assert trace.kernel_s(t, r"pack_bits") == pytest.approx(1.5e-3 + 2e-3)
    assert trace.kernel_s(t, r"^unpack_bits[.\d]* custom-call$") == pytest.approx(2e-3)
    assert trace.kernel_s(t, r"while") == 0
    assert trace.module_s(t, r"^jit_(un)?pack_bits\(") == pytest.approx(3.5e-3 + 2e-3)
    assert trace.top_ops(t)[0] == ["fusion.1 fusion", pytest.approx(2e-3)]


def test_idle_gaps_name_the_host_span():
    gaps = dict(map(tuple, trace.idle_gaps(synthetic())))
    assert gaps == {"bench.encode > PjitFunction(pack)": pytest.approx(1.5e-3),
                    "bench.decode": pytest.approx(2e-3)}


def test_window_and_ops_are_required():
    with pytest.raises(ValueError):
        trace.from_events({"/device:TPU:0": [("x", 0, 1)]}, [])
    with pytest.raises(ValueError):
        trace.from_events({}, [(trace.WINDOW, 0, 1)])


class _Run:
    def __init__(self, tr, counts, peaks):
        self.trace, self.counts, self.peaks, self.chips = tr, counts, peaks, 1
        self.memory_peak_bytes = 2_000_000_000


PEAKS = {"bf16_flops": 1.97e14, "hbm_bytes_per_s": 8.19e11}


def test_roofline_and_mfu_arithmetic():
    t = synthetic()
    run = _Run(t, dict(trips=1, leaves=[(1000, 11)], seconds=0.5), PEAKS)
    least = 2 * (4 * 1000 + 4 * 344)  # pack and unpack of 1000 11-bit fields
    want = 100 * least / 8.19e11 / 5.5e-3  # pack and unpack programs in the window
    assert spec.load_reader("bitpack_roofline")(run) == pytest.approx(want)
    assert spec.load_reader("mfu.wire")(run) == pytest.approx(100 * least / 8.19e11 / 0.5)
    idle = spec.load_reader("device_idle_share.wire")(run)
    assert idle == pytest.approx(35.0)
    train = _Run(t, dict(params=1000, frames=10, samples=30, seconds=2.0), PEAKS)
    assert spec.load_reader("train_mfu")(train) == pytest.approx(
        100 * 6 * 1000 * 10 * 30 / 2.0 / 1.97e14)
    serve = _Run(t, dict(params=1000, tokens=50, seconds=2.0), PEAKS)
    assert spec.load_reader("serve_mfu")(serve) == pytest.approx(
        100 * 2 * 1000 * 50 / 2.0 / 1.97e14)
    assert spec.load_reader("peak_hbm_GB.train")(train) == pytest.approx(2.0)


def rounds_trace(dropped: bool):
    """Three 10 ms round programs, each with two fused-aggregation calls of
    1 ms; with ``dropped`` the profiler kept no operation of the last one."""
    ops, modules = [], []
    for r in range(3):
        t = r * 12 * MS
        modules.append((f"jit_round_fn({r})", t, t + 10 * MS))
        if dropped and r == 2:
            continue
        ops += [("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a)", t, t + 7 * MS),
                ("%fused_aggregate.1 = u16[8]{0} custom-call(u16[8]{0} %a)", t + 7 * MS, t + 8 * MS),
                ("%fused_aggregate.2 = u16[8]{0} custom-call(u16[8]{0} %b)", t + 8 * MS, t + 9 * MS)]
    host = [(trace.WINDOW, 0, 36 * MS)]
    return trace.from_events({"/device:TPU:0": ops}, host, {"/device:TPU:0": modules})


def test_lost_operations_leave_busy_time_and_rooflines_as_they_were():
    full, dropped = rounds_trace(False), rounds_trace(True)
    assert trace.coverage(full)["/device:TPU:0"] == dict(
        ops=9, modules=3, tail_s=pytest.approx(3e-3))
    assert trace.coverage(dropped)["/device:TPU:0"] == dict(
        ops=6, modules=3, tail_s=pytest.approx(15e-3))
    # the program whose operations were lost still counts as busy
    assert trace.busy_s(full) == trace.busy_s(dropped) == pytest.approx(30e-3)
    assert sum(t for _, t in trace.idle_gaps(dropped)) == pytest.approx(6e-3)
    assert trace.kernel_runs(full, r"^fused_aggregate") == (3, pytest.approx(6e-3))
    assert trace.kernel_runs(dropped, r"^fused_aggregate") == (2, pytest.approx(4e-3))
    counts = dict(cohort=8, selected_sizes=[1000, 24], container_bytes=2)
    read = spec.load_reader("fused_agg_roofline")
    want = 100 * 10 * 1024 * 2 / 8.19e11 / 2e-3  # one round's bytes over its 2 ms
    assert read(_Run(full, counts, PEAKS)) == pytest.approx(want)
    assert read(_Run(dropped, counts, PEAKS)) == pytest.approx(want)


def test_reader_finds_nothing_returns_none():
    t = trace.from_events({"/device:TPU:0": [("%fused_aggregate.1 = f32[] fusion(f32[] %a)", 0, MS)]},
                          [(trace.WINDOW, 0, 2 * MS)])
    counts = dict(rounds=1, cohort=8, selected_sizes=[10], container_bytes=2, trips=1,
                  leaves=[(10, 11)])
    assert spec.load_reader("fused_agg_roofline")(_Run(t, counts, PEAKS)) is None
    assert spec.load_reader("bitpack_roofline")(_Run(t, counts, PEAKS)) is None


def test_recorded_trace():
    """A real TPU trace (``record_trace.py``): three matmuls, three runs of a
    named Pallas kernel and three 5 ms host sleeps inside the window."""
    from jax.profiler import ProfileData

    t = trace.load(RECORDED)
    assert len(t.ops) == 1
    busy = trace.busy_s(t)
    assert 0 < busy < t.window_s
    # the union by brute force over the raw events
    data = ProfileData.from_file(str(RECORDED))
    lo, hi = t.window
    covered = set()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    for e in line.events:
                        s, end = max(int(e.start_ns), lo), min(int(e.start_ns + e.duration_ns), hi)
                        covered.update(range(s // 1000, end // 1000))  # microseconds
    assert busy == pytest.approx(len(covered) / 1e6, abs=2e-5 * 3 + 1e-4)
    assert trace.kernel_s(t, "double_kernel") > 0
    gaps = dict(map(tuple, trace.idle_gaps(t)))
    sleep = sum(v for k, v in gaps.items() if k.startswith("bench.host_work"))
    spans = sum(e - s for n, s, e in t.host if n == "bench.host_work") / 1e9
    # a gap is named after its middle, so the gaps around the sleeps carry
    # their whole length: at least the sleeps', at most the idle time
    assert spans >= 0.015 and spans <= sleep <= t.window_s - busy
