"""Pallas kernel correctness: interpret-mode sweep vs pure-jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import FloatFormat
from repro.kernels import agg
from repro.kernels import dequant_matmul as dm
from repro.kernels import ops
from repro.kernels import quantize as qk
from repro.kernels import ref

FMTS = [FloatFormat(2, 3), FloatFormat(3, 7), FloatFormat(4, 14),
        FloatFormat(5, 10), FloatFormat(8, 23)]
SHAPES = [(8,), (129,), (37, 53), (2, 3, 65), (256, 128)]


@pytest.mark.parametrize("fmt", FMTS, ids=lambda f: f.name)
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_quantize_kernel_matches_ref(fmt, shape):
    x = jax.random.normal(jax.random.PRNGKey(hash(shape) % 2**31), shape)
    x = x * jnp.float32(3.0)
    got = qk.quantize(x, fmt, interpret=True)
    want = ref.ref_quantize(x, fmt)
    assert got.dtype == want.dtype == fmt.container_dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("fmt", FMTS, ids=lambda f: f.name)
@pytest.mark.parametrize("shape", [(64,), (33, 40)], ids=str)
def test_dequantize_kernel_matches_ref(fmt, shape):
    x = jax.random.normal(jax.random.PRNGKey(7), shape)
    codes = ref.ref_quantize(x, fmt)
    s, b = jnp.float32(1.05), jnp.float32(-0.01)
    got = qk.dequantize(codes, fmt, s, b, interpret=True)
    want = ref.ref_dequantize(codes, fmt, s, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("fmt", [FloatFormat(3, 7), FloatFormat(4, 14)],
                         ids=lambda f: f.name)
def test_quantize_stats_kernel(fmt):
    x = jax.random.normal(jax.random.PRNGKey(3), (1000,)) * 0.3
    codes, sums = qk.quantize_stats(x, fmt, interpret=True)
    rcodes, rsums = ref.ref_quantize_stats(x, fmt)
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(rcodes))
    np.testing.assert_allclose(np.asarray(sums), np.asarray(rsums),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("fmt", [FloatFormat(3, 7), FloatFormat(5, 10)],
                         ids=lambda f: f.name)
@pytest.mark.parametrize("mnk", [(48, 80, 96), (32, 32, 32), (100, 60, 70)],
                         ids=str)
def test_dequant_matmul_kernel(fmt, mnk):
    m, n, k = mnk
    a = jax.random.normal(jax.random.PRNGKey(1), (m, k))
    w = jax.random.normal(jax.random.PRNGKey(2), (k, n)) * 0.1
    codes = ref.ref_quantize(w, fmt)
    s, b = jnp.float32(0.98), jnp.float32(0.004)
    got = dm.dequant_matmul(a, codes, fmt, s, b, bm=32, bn=32, bk=32,
                            interpret=True)
    want = ref.ref_dequant_matmul(a, codes, fmt, s, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Fused compressed-domain aggregation (DESIGN.md §13)
# ---------------------------------------------------------------------------


def _fused_case(fmt, shape, batch_axes, cohort=5, seed=0, dead=(1,)):
    """Random server/client storage-form variables + a survival mask.

    Dead clients get garbage codes — including a genuine NaN code for
    formats with an exponent field — so the test proves the kernel's
    where-guard, not just numerical luck."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    srv_val = jax.random.normal(keys[0], shape)
    cl_val = jax.random.normal(keys[1], (cohort,) + shape) * 0.7
    srv_codes = ref.ref_quantize(srv_val, fmt)
    cl_codes = np.array(ref.ref_quantize(cl_val, fmt))
    w = np.ones((cohort,), np.float32)
    # all-ones exponent + nonzero mantissa: a genuine NaN code
    nan_code = (((1 << fmt.exp_bits) - 1) << fmt.mant_bits) | (
        1 << (fmt.mant_bits - 1))
    for c in dead:
        w[c] = 0.0
        cl_codes[c] = np.asarray(nan_code, cl_codes.dtype)
    sb = int(np.prod(shape[:batch_axes])) if batch_axes else 1
    rng = np.random.default_rng(seed + 1)
    srv_s = jnp.asarray(rng.normal(1.0, 0.05, sb).astype(np.float32))
    srv_b = jnp.asarray(rng.normal(0.0, 0.01, sb).astype(np.float32))
    cl_s = jnp.asarray(rng.normal(1.0, 0.05, (cohort, sb)).astype(np.float32))
    cl_b = jnp.asarray(rng.normal(0.0, 0.01, (cohort, sb)).astype(np.float32))
    if batch_axes:
        srv_s = srv_s.reshape(shape[:batch_axes])
        srv_b = srv_b.reshape(shape[:batch_axes])
        cl_s = cl_s.reshape((cohort,) + shape[:batch_axes])
        cl_b = cl_b.reshape((cohort,) + shape[:batch_axes])
    else:
        srv_s, srv_b = srv_s.reshape(()), srv_b.reshape(())
        cl_s, cl_b = cl_s.reshape(cohort), cl_b.reshape(cohort)
    return (srv_codes, srv_s, srv_b, jnp.asarray(cl_codes), cl_s, cl_b,
            jnp.asarray(w))


@pytest.mark.parametrize("fmt", [FloatFormat(3, 7), FloatFormat(4, 14)],
                         ids=lambda f: f.name)
@pytest.mark.parametrize("shape,batch_axes",
                         [((37, 19), 0), ((3, 40, 17), 1), ((5,), 0),
                          ((2, 3, 130), 2)],
                         ids=["flat2d", "stacked1", "tiny", "stacked2"])
def test_fused_aggregate_kernel_matches_ref(fmt, shape, batch_axes):
    """Interpret-mode Pallas vs the unfused oracle: server codes bit-equal,
    PVT affine equal up to f32 reduction-order noise, dead-client NaN rows
    discarded by the where-guard."""
    case = _fused_case(fmt, shape, batch_axes)
    got = agg.fused_aggregate(*case, 0.5, fmt, batch_axes=batch_axes,
                              interpret=True)
    want = ref.ref_fused_aggregate(*case, 0.5, fmt, batch_axes=batch_axes)
    g = np.asarray(got[0]).astype(np.int64)
    w = np.asarray(want[0]).astype(np.int64)
    # f32 reassociation between the tiled kernel and the oracle can flip a
    # round-to-nearest-even tie: allow adjacent codes on a <=0.5% fringe,
    # everything else bit-equal
    diff = g != w
    assert diff.mean() <= 5e-3, f"{diff.sum()}/{diff.size} codes differ"
    assert np.abs(g - w)[diff].max(initial=0) <= 1, "non-adjacent code drift"
    from repro.core.formats import decode
    np.testing.assert_allclose(
        np.asarray(decode(got[0], fmt)), np.asarray(decode(want[0], fmt)),
        rtol=2.0 ** -fmt.mant_bits, atol=fmt.subnormal_step)
    assert np.isfinite(np.asarray(got[1])).all()
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                               rtol=2e-5, atol=2e-6)


def test_fused_aggregate_all_dead_is_pure_server_decay():
    """Every client dead: the mean is 0 and the round is old + lr·(0 − old),
    still finite despite all-NaN client rows."""
    fmt = FloatFormat(3, 7)
    case = _fused_case(fmt, (64,), 0, cohort=4, dead=(0, 1, 2, 3))
    codes, s, b = agg.fused_aggregate(*case, 0.25, fmt, interpret=True)
    srv_codes, srv_s, srv_b = case[0], case[1], case[2]
    from repro.core.formats import decode
    old = np.asarray(decode(srv_codes, fmt)) * float(srv_s) + float(srv_b)
    got = np.asarray(decode(codes, fmt)) * np.asarray(s) + np.asarray(b)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, 0.75 * old, atol=6e-3)


def test_fused_aggregate_pvt_off_returns_identity_affine():
    fmt = FloatFormat(3, 7)
    case = _fused_case(fmt, (33,), 0)
    codes, s, b = ops.fused_aggregate(*case, 0.5, fmt, pvt=False)
    assert s.shape == () and b.shape == ()
    assert float(s) == 1.0 and float(b) == 0.0
    want = ref.ref_fused_aggregate(*case, 0.5, fmt)
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(want[0]))


# ---------------------------------------------------------------------------
# Dispatch policy (regression: per-call TPU probe swallowed exceptions and
# could flip between retraces — now a module constant, ref.py on CPU)
# ---------------------------------------------------------------------------


def test_cpu_dispatch_hits_ref(monkeypatch):
    assert ops._on_tpu() is ops._on_tpu()  # decided lazily once, then memoized
    if ops._ON_TPU:
        pytest.skip("host has a TPU: the compiled-Pallas branch is correct")
    calls = []
    real = ref.ref_pack
    monkeypatch.setattr(ref, "ref_pack",
                        lambda c, w: calls.append(w) or real(c, w))
    ops.reset_dispatch_counts()
    # fresh (shape, width) -> fresh trace of the jit'd wrapper -> the spy
    # fires iff the CPU branch routes through the ref oracle
    codes = jnp.arange(9973, dtype=jnp.uint32) & np.uint32(0x7FF)
    got = ops.pack_bits(codes, 11)
    assert calls == [11], "CPU dispatch did not route through kernels/ref.py"
    np.testing.assert_array_equal(np.asarray(got), np.asarray(real(codes, 11)))
    # the dispatch counter (DESIGN.md §15) agrees with the spy: the trace
    # was counted against the ref backend and never against pallas
    counts = ops.dispatch_counts()
    assert counts.get("pack_bits.ref") == 1, counts
    assert not any(k.endswith(".pallas") for k in counts), counts


def test_dispatch_counter_counts_traces_not_calls():
    """Counts are per compiled specialization: repeat calls with the same
    shape hit the jit cache and add nothing; a new shape retraces.  Prime
    sizes keep the specializations fresh regardless of test order."""
    if ops._ON_TPU:
        pytest.skip("backend split differs on TPU")
    ops.reset_dispatch_counts()
    codes = jnp.arange(1013, dtype=jnp.uint32) & np.uint32(0xF)
    ops.pack_bits(codes, 4)
    first = ops.dispatch_counts()
    assert first.get("pack_bits.ref") == 1, first
    ops.pack_bits(codes, 4)  # cache hit: no retrace, no count
    assert ops.dispatch_counts() == first
    ops.pack_bits(jnp.arange(1031, dtype=jnp.uint32) & np.uint32(0xF), 4)
    assert ops.dispatch_counts()["pack_bits.ref"] == 2
    # interpret mode is its own backend bucket, never 'ref'
    words = ops.pack_bits(jnp.arange(1013, dtype=jnp.uint32) & np.uint32(0x3F),
                          6)
    ops.unpack_bits(words, 6, 1013, force_interpret=True)
    counts = ops.dispatch_counts()
    assert counts.get("unpack_bits.interpret") == 1, counts
    assert "unpack_bits.ref" not in counts, counts


def test_interpret_dispatch_runs_kernel_body(monkeypatch):
    """force_interpret must execute the Pallas body, not the oracle."""
    if ops._ON_TPU:
        pytest.skip("on TPU the compiled branch wins by design")
    calls = []
    monkeypatch.setattr(
        ref, "ref_unpack",
        lambda *a, **k: calls.append(1) or (_ for _ in ()).throw(AssertionError))
    codes = jnp.arange(517, dtype=jnp.uint32) & np.uint32(0x3F)
    words = ops.pack_bits(codes, 6)
    back = ops.unpack_bits(words, 6, 517, force_interpret=True)
    assert not calls
    np.testing.assert_array_equal(np.asarray(back), np.asarray(codes))


def test_dequant_matmul_bias_rank1_correction():
    """The b-term folds as b * rowsum(A) — check against explicit compute."""
    fmt = FloatFormat(3, 7)
    a = jax.random.normal(jax.random.PRNGKey(4), (16, 24))
    w = jax.random.normal(jax.random.PRNGKey(5), (24, 8)) * 0.2
    codes = ref.ref_quantize(w, fmt)
    s, b = jnp.float32(1.1), jnp.float32(0.05)
    got = dm.dequant_matmul(a, codes, fmt, s, b, bm=8, bn=8, bk=8,
                            interpret=True)
    w_eff = s * ref.ref_dequantize(codes, fmt) + b
    want = a @ w_eff
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


_IMPORT_SCRIPT = r"""
import jax
from jax._src import xla_bridge

def _boom(*a, **k):
    raise AssertionError("device state touched while importing repro.kernels")

jax.devices = jax.local_devices = jax.default_backend = _boom
import repro.kernels
from repro.kernels import ops
assert not xla_bridge.backends_are_initialized()
print("IMPORT-OK")
"""


def test_import_touches_no_device_state():
    """Importing repro.kernels must not take the chip (one process per
    chip): the backend is decided at the first dispatch, not at import."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "src")
    r = subprocess.run([sys.executable, "-c", _IMPORT_SCRIPT],
                       capture_output=True, text=True, timeout=300,
                       env={"PYTHONPATH": src, "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert "IMPORT-OK" in r.stdout, r.stdout + r.stderr


def test_backend_probe_error_propagates(monkeypatch):
    """A backend that cannot be probed raises; it never silently selects
    the ref oracles."""
    def boom():
        raise RuntimeError("backend probe failed")

    monkeypatch.setattr(jax, "default_backend", boom)
    ops._on_tpu.cache_clear()
    try:
        with pytest.raises(RuntimeError, match="backend probe failed"):
            ops.pack_bits(jnp.arange(4127, dtype=jnp.uint32) & np.uint32(7), 3)
    finally:
        ops._on_tpu.cache_clear()


def test_tpu_dispatch_never_takes_ref(monkeypatch):
    """On a TPU process every op picks the compiled kernel; only an explicit
    force_interpret selects interpret mode."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert ops._dispatch("probe_op", False) == "pallas"
    assert ops._dispatch("probe_op", True) == "interpret"
