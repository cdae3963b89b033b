"""jit'd public wrappers for the Pallas kernels.

Dispatch policy (DESIGN.md §13):
  * on TPU: compiled Pallas kernels,
  * elsewhere: pure-jnp reference (``ref.py``) by default — fast on CPU —
    or interpret-mode Pallas when ``force_interpret=True`` (used by the
    correctness tests, which execute the actual kernel bodies).

The backend is decided ONCE per process, lazily at the first dispatch,
from ``jax.default_backend()`` and memoized by :func:`_on_tpu`.  Importing
this module touches no device state, so a process that only imports
``repro.kernels`` never takes the chip.  The probe catches nothing: a
backend that cannot be initialized raises instead of silently routing every
kernel to the jnp oracles, and the memo keeps dispatch fixed between
retraces (regression-tested in tests/test_kernels.py).  In a TPU process a
kernel never takes the ref or interpret branch unless the caller passes
``force_interpret=True``.

Every branch also bumps a **dispatch counter** keyed ``(op, backend)``
with backend ∈ {pallas, interpret, ref} (DESIGN.md §15).  The wrappers
are jitted, so the bump executes at *trace* time: counts are per compiled
specialization, not per call — exactly the right granularity for the
regression question "did a CPU run silently trace the compiled path?".
Read with :func:`dispatch_counts`; ``repro.obs`` embeds the counts in its
run meta record.
"""

from __future__ import annotations

import functools
from collections import Counter
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.formats import FloatFormat

from . import agg as _agg
from . import bitpack as _bp
from . import dequant_matmul as _dm
from . import quantize as _q
from . import ref


@functools.cache
def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def __getattr__(name: str):
    # ``ops._ON_TPU`` reads the memoized decision (deciding it if needed).
    if name == "_ON_TPU":
        return _on_tpu()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


_DISPATCHES: Counter = Counter()


def _record(op: str, backend: str) -> None:
    _DISPATCHES[f"{op}.{backend}"] += 1


def dispatch_counts() -> Dict[str, int]:
    """``{"<op>.<backend>": traces}`` accumulated since import/reset."""
    return dict(_DISPATCHES)


def reset_dispatch_counts() -> None:
    _DISPATCHES.clear()


def _dispatch(op: str, force_interpret: bool) -> str:
    """Pick + record the backend for one traced specialization."""
    backend = ("interpret" if force_interpret
               else "pallas" if _on_tpu() else "ref")
    _record(op, backend)
    return backend


@functools.partial(jax.jit, static_argnames=("fmt", "force_interpret"))
def quantize(x, fmt: FloatFormat, force_interpret: bool = False):
    backend = _dispatch("quantize", force_interpret)
    if backend == "pallas":
        return _q.quantize(x, fmt)
    if backend == "interpret":
        return _q.quantize(x, fmt, interpret=True)
    return ref.ref_quantize(x, fmt)


@functools.partial(jax.jit, static_argnames=("fmt", "force_interpret"))
def dequantize(codes, fmt: FloatFormat, s=None, b=None,
               force_interpret: bool = False):
    backend = _dispatch("dequantize", force_interpret)
    if backend == "pallas":
        return _q.dequantize(codes, fmt, s, b)
    if backend == "interpret":
        return _q.dequantize(codes, fmt, s, b, interpret=True)
    return ref.ref_dequantize(codes, fmt, s, b)


@functools.partial(jax.jit, static_argnames=("fmt", "force_interpret"))
def quantize_stats(x, fmt: FloatFormat, force_interpret: bool = False):
    backend = _dispatch("quantize_stats", force_interpret)
    if backend == "pallas":
        return _q.quantize_stats(x, fmt)
    if backend == "interpret":
        return _q.quantize_stats(x, fmt, interpret=True)
    return ref.ref_quantize_stats(x, fmt)


@functools.partial(jax.jit,
                   static_argnames=("fmt", "bm", "bn", "bk", "force_interpret"))
def dequant_matmul(a, w_codes, fmt: FloatFormat, s=None, b=None,
                   bm: int = 256, bn: int = 256, bk: int = 256,
                   force_interpret: bool = False):
    backend = _dispatch("dequant_matmul", force_interpret)
    if backend == "pallas":
        return _dm.dequant_matmul(a, w_codes, fmt, s, b, bm=bm, bn=bn, bk=bk)
    if backend == "interpret":
        return _dm.dequant_matmul(a, w_codes, fmt, s, b, bm=bm, bn=bn, bk=bk,
                                  interpret=True)
    return ref.ref_dequant_matmul(
        a, w_codes, fmt,
        jnp.float32(1.0) if s is None else s,
        jnp.float32(0.0) if b is None else b,
    )


@functools.partial(jax.jit, static_argnames=("width", "force_interpret"))
def pack_bits(codes, width: int, force_interpret: bool = False):
    """codes (values < 2**width) -> exact uint32 bitstream (wire form)."""
    backend = _dispatch("pack_bits", force_interpret)
    if backend == "pallas":
        return _bp.pack(codes, width)
    if backend == "interpret":
        return _bp.pack(codes, width, interpret=True)
    return ref.ref_pack(codes, width)


@functools.partial(jax.jit, static_argnames=("width", "n", "force_interpret"))
def unpack_bits(words, width: int, n: int, force_interpret: bool = False):
    """Inverse of :func:`pack_bits`: recover ``n`` codes (uint32)."""
    backend = _dispatch("unpack_bits", force_interpret)
    if backend == "pallas":
        return _bp.unpack(words, width, n)
    if backend == "interpret":
        return _bp.unpack(words, width, n, interpret=True)
    return ref.ref_unpack(words, width, n)


@functools.partial(
    jax.jit, static_argnames=("fmt", "batch_axes", "pvt", "force_interpret")
)
def fused_aggregate(srv_codes, srv_s, srv_b, cl_codes, cl_s, cl_b, weights,
                    lr, fmt: FloatFormat, batch_axes: int = 0,
                    pvt: bool = True, force_interpret: bool = False):
    """Compressed-domain server round for one variable (DESIGN.md §13).

    Returns (new_codes, s, b) — the aggregated server variable in storage
    form, without materializing f32 cohort state on the Pallas path.
    """
    backend = _dispatch("fused_aggregate", force_interpret)
    if backend == "pallas":
        out = _agg.fused_aggregate(srv_codes, srv_s, srv_b, cl_codes, cl_s,
                                   cl_b, weights, lr, fmt,
                                   batch_axes=batch_axes)
    elif backend == "interpret":
        out = _agg.fused_aggregate(srv_codes, srv_s, srv_b, cl_codes, cl_s,
                                   cl_b, weights, lr, fmt,
                                   batch_axes=batch_axes, interpret=True)
    else:
        out = ref.ref_fused_aggregate(srv_codes, srv_s, srv_b, cl_codes, cl_s,
                                      cl_b, weights, lr, fmt,
                                      batch_axes=batch_axes)
    if not pvt:
        codes, _, _ = out
        return codes, jnp.float32(1.0), jnp.float32(0.0)
    return out
