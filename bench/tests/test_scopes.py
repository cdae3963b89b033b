"""Named scopes from a trace's metadata (``harness/scopes.py``) and the
readers of the round-phase, materialization and codec-read metrics."""

import os
import types
from pathlib import Path

import pytest

from harness import scopes, spec, trace

RECORDED = Path(__file__).parent / "data" / "small_trace.xplane.pb"
MS = 1_000_000
CELL = "conformer_s.train_local1"


def _varint(v: int) -> bytes:
    out = bytearray()
    while v >= 0x80:
        out.append(v & 0x7F | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def _field(f: int, v) -> bytes:
    if isinstance(v, int):
        return _varint(f << 3) + _varint(v)
    v = v.encode() if isinstance(v, str) else v
    return _varint(f << 3 | 2) + _varint(len(v)) + v


def _entry(key: int, value: bytes) -> bytes:
    return _field(1, key) + _field(2, value)


def xspace(ops, window, by_ref=()):
    """An ``.xplane.pb``: one TPU plane whose event metadata names ``ops``
    (name -> ``tf_op`` path, or None for none; paths of names in ``by_ref``
    stored as a reference to a stat metadata name), and a host plane holding
    the ``bench.window`` event at ``window`` on a line that starts at 0."""
    stat_md = [_entry(1, _field(1, 1) + _field(2, scopes.TF_OP))]
    events = []
    for i, (name, path) in enumerate(ops.items(), start=1):
        md = _field(1, i) + _field(2, name)
        if path is not None and name in by_ref:
            ref = 100 + i
            stat_md.append(_entry(ref, _field(1, ref) + _field(2, path)))
            md += _field(5, _field(1, 1) + _field(7, ref))
        elif path is not None:
            md += _field(5, _field(1, 1) + _field(5, path))
        events.append(_entry(i, md))
    device = (_field(1, 1) + _field(2, "/device:TPU:0") + b"".join(_field(4, e) for e in events)
              + b"".join(_field(5, s) for s in stat_md))
    t0 = 0
    other = _field(1, 2) + _field(2, 0) + _field(3, 5_000)
    win = (_field(1, 1) + _field(2, (window[0] - t0) * 1000)
           + _field(3, (window[1] - window[0]) * 1000))
    line = (_field(1, 7) + _field(2, "python3") + _field(3, t0) + _field(4, other)
            + _field(4, win))
    host = (_field(1, 2) + _field(2, scopes.HOST_PLANE) + _field(3, line)
            + _field(4, _entry(1, _field(1, 1) + _field(2, trace.WINDOW)))
            + _field(4, _entry(2, _field(1, 2) + _field(2, "PjitFunction(f)"))))
    return _field(1, device) + _field(1, host)


def test_recorded_trace_names_the_kernel_scope():
    sc = scopes.read(RECORDED)
    t = trace.load(RECORDED)
    assert sc.window == t.window
    by_op = {trace.op_name(n): n for evs in t.ops.values() for n, _, _ in evs}
    assert sc.paths[by_op["double_kernel.1 custom-call"]] == \
        "jit(<lambda>)/double_kernel/pallas_call:"
    copies = [n for k, n in by_op.items() if k.startswith("copy-")]
    assert copies and not any(n in sc.paths for n in copies)


ROUND_OPS = {  # name -> (tf_op path, start ms, end ms)
    "%while.1 = (f32[8]) while(%t)": ("jit(round_fn)/omc.client/while:", 1, 7),
    "%fusion.1 = f32[8] fusion(%a)": ("jit(round_fn)/omc.decompress/mul:", 0, 1),
    "%fusion.2 = f32[8] fusion(%b)": ("jit(round_fn)/omc.client/vmap(one)/dot_general:", 1, 5),
    "%fusion.3 = f32[8] fusion(%c)": (
        "jit(round_fn)/omc.client/transpose(jvp(one))/omc.materialize/convert:", 5, 7),
    "%fusion.4 = u16[8] fusion(%d)": ("jit(round_fn)/omc.transport_encode/round:", 7, 8),
    "%fused_aggregate.1 = u16[8] custom-call(%e)": (
        "jit(round_fn)/omc.server_step/jit(fused_aggregate)/fused_aggregate/pallas_call:", 8, 10),
    "%copy-start = f32[8] copy-start(%f)": (None, 10, 11),
}
SHARES = dict(round_decompress_share=10.0, round_client_share=60.0,
              round_encode_share=10.0, round_server_share=20.0,
              serve_materialize_share=20.0)


def _run(ops, window=(0, 12 * MS), host=(), counts=None):
    evs = [(n, s * MS, e * MS) for n, (_, s, e) in ops.items()]
    tr = trace.from_events({"/device:TPU:0": evs}, [(trace.WINDOW, *window), *host])
    return types.SimpleNamespace(cell=types.SimpleNamespace(name=CELL), trace=tr,
                                 counts=counts or {}, peaks={}, chips=1)


def _write(root, seed, ops, window, by_ref=()):
    d = root / f"{CELL}-{seed}" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    path = d / "host.xplane.pb"
    path.write_bytes(xspace({n: p for n, (p, _, _) in ops.items()}, window, by_ref))
    return path


@pytest.fixture
def traces(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "TRACES", tmp_path)
    return tmp_path


@pytest.mark.parametrize("metric", sorted(SHARES))
def test_scope_share_of_leaf_device_time(traces, metric):
    """Shares over operations that enclose no other; a path held as a
    reference reads as one held as a string; a copy with no scope counts in
    the whole only."""
    _write(traces, 1, ROUND_OPS, (0, 12 * MS), by_ref={"%fusion.2 = f32[8] fusion(%b)"})
    run = _run(ROUND_OPS)
    # leaves: 1 + 4 + 2 + 1 + 2 + 1 = 11 ms; materialize nests in client
    want = SHARES[metric] * 10 / 11
    assert spec.load_reader(metric)(run) == pytest.approx(want)


@pytest.mark.parametrize("case", ["other_window", "no_trace", "no_scopes"])
def test_scope_share_refuses(traces, case):
    """None for another run's trace (the newest file's window is not the
    run's), for no trace, and for a program that carries no such scope."""
    read = spec.load_reader("round_client_share")
    if case == "other_window":
        older = _write(traces, 1, ROUND_OPS, (0, 12 * MS))
        newer = _write(traces, 2, ROUND_OPS, (0, 13 * MS))
        os.utime(older, ns=(0, newer.stat().st_mtime_ns - 1))
        assert scopes.read(newer).window == (0, 13 * MS)
        assert read(_run(ROUND_OPS)) is None
    elif case == "no_trace":
        assert read(_run(ROUND_OPS)) is None
    else:
        plain = {n: (p and p.replace("omc.", "x."), s, e)
                 for n, (p, s, e) in ROUND_OPS.items()}
        _write(traces, 1, plain, (0, 12 * MS))
        assert read(_run(plain)) is None
        assert spec.load_reader("round_decompress_share")(_run(plain)) is None


def test_scope_fused_into_another_reads_zero(traces):
    """A program with the scopes, none of whose operations' roots lies in
    ``omc.decompress`` (XLA fused it into its consumers), reads 0 there."""
    ops = {n: (p.replace("omc.decompress", "omc.client"), s, e) if p else (p, s, e)
           for n, (p, s, e) in ROUND_OPS.items()}
    _write(traces, 1, ops, (0, 12 * MS))
    assert spec.load_reader("round_decompress_share")(_run(ops)) == 0.0
    assert spec.load_reader("round_client_share")(_run(ops)) == pytest.approx(700 / 11)


D2H = "omc.codec.d2h"


def test_codec_read_counts_and_wait():
    """Reads that start in the window, per round trip; the union of their
    intervals inside it, over the window."""
    host = [(D2H, 1 * MS, 2 * MS), (D2H, 3 * MS / 2, 3 * MS), (D2H, 5 * MS, 6 * MS),
            (D2H, 9 * MS, 11 * MS), (D2H, 11 * MS, 12 * MS), ("omc.codec.h2d", 0, 9 * MS)]
    run = _run(ROUND_OPS, window=(0, 10 * MS), host=host, counts=dict(trips=2))
    assert spec.load_reader("wire_d2h_per_trip")(run) == pytest.approx(2.0)
    # [1, 3] + [5, 6] + [9, 10] of a 10 ms window
    assert spec.load_reader("wire_d2h_wait_share")(run) == pytest.approx(40.0)


def test_codec_readers_find_nothing_without_the_spans():
    run = _run(ROUND_OPS, host=[("bench.encode", 0, MS)], counts=dict(trips=3))
    assert spec.load_reader("wire_d2h_per_trip")(run) is None
    assert spec.load_reader("wire_d2h_wait_share")(run) is None
