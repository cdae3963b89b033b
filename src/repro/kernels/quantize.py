"""Pallas TPU kernels: OMC quantize / dequantize (paper's hot elementwise op).

OMC pays an encode+decode per parameter per round ("lightweight operation",
paper §2.2/Tables 1-2) — on TPU this must stream HBM->VMEM->HBM at memory
bandwidth with the bit-twiddling fused, never materializing intermediate
f32 copies in HBM.  Three kernels:

  * ``quantize``        f32 tile -> minifloat bitfield codes (RNE,
                        subnormal-aware, saturating)
  * ``dequantize``      codes -> f32, fused with the PVT affine s·x + b
  * ``quantize_stats``  fused quantize + the four PVT sums (Σv, Σṽ, Σvṽ,
                        Σṽ²) accumulated across the grid — one pass instead
                        of quantize-then-resum (halves HBM traffic of the
                        round's re-compression step)

Tiling: inputs are flattened and tiled as (rows, 1024) VMEM blocks — the
lane dim is a multiple of 128 (VPU-aligned) and the block (8·1024 f32 =
32 KiB) keeps the working set far inside VMEM while saturating HBM.

Validation: interpret=True on CPU against ``ref.py`` (pure-jnp oracle) over
a shape x format sweep — see tests/test_kernels.py.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.formats import FloatFormat, decode as _jnp_decode, encode as _jnp_encode

LANES = 1024  # lane-dim tile (multiple of 128)
SUBLANES = 8  # row-dim tile


def _pad_flatten(x: jax.Array) -> Tuple[jax.Array, int]:
    """[-> (rows, LANES)] zero-padded view + original element count."""
    n = x.size
    rows = -(-n // LANES)
    rows = -(-rows // SUBLANES) * SUBLANES
    flat = jnp.ravel(x)
    flat = jnp.pad(flat, (0, rows * LANES - n))
    return flat.reshape(rows, LANES), n


def _quantize_kernel(x_ref, o_ref, *, fmt: FloatFormat):
    o_ref[...] = _jnp_encode(x_ref[...], fmt, quantize=True)


def _dequantize_kernel(c_ref, s_ref, b_ref, o_ref, *, fmt: FloatFormat):
    s = s_ref[0, 0]
    b = b_ref[0, 0]
    o_ref[...] = _jnp_decode(c_ref[...], fmt) * s + b


def _quantize_stats_kernel(x_ref, o_ref, sums_ref, *, fmt: FloatFormat):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    x = x_ref[...]
    codes = _jnp_encode(x, fmt, quantize=True)
    o_ref[...] = codes
    q = _jnp_decode(codes, fmt)
    # Per-lane partial sums (Mosaic cannot store scalars to VMEM); the
    # lanes are summed outside the kernel.
    sums_ref[...] += jnp.concatenate(
        [jnp.sum(t, axis=0, keepdims=True) for t in (x, q, x * q, q * q)],
        axis=0)


def quantize(x: jax.Array, fmt: FloatFormat, *, interpret: bool = False) -> jax.Array:
    """f32 array -> bitfield codes (same shape, container dtype)."""
    x2, n = _pad_flatten(jnp.asarray(x, jnp.float32))
    rows = x2.shape[0]
    grid = (rows // SUBLANES,)
    out = pl.pallas_call(
        functools.partial(_quantize_kernel, fmt=fmt),
        grid=grid,
        in_specs=[pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), fmt.container_dtype),
        interpret=interpret,
        name="quantize",
    )(x2)
    return out.reshape(-1)[:n].reshape(x.shape)


def dequantize(codes: jax.Array, fmt: FloatFormat, s=None, b=None,
               *, interpret: bool = False) -> jax.Array:
    """codes -> f32 with the PVT affine fused (s, b scalars)."""
    c2, n = _pad_flatten(codes.astype(fmt.container_dtype))
    rows = c2.shape[0]
    s_arr = jnp.full((1, 1), 1.0 if s is None else s, jnp.float32)
    b_arr = jnp.full((1, 1), 0.0 if b is None else b, jnp.float32)
    grid = (rows // SUBLANES,)
    out = pl.pallas_call(
        functools.partial(_dequantize_kernel, fmt=fmt),
        grid=grid,
        in_specs=[
            pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
        name="dequantize",
    )(c2, s_arr, b_arr)
    return out.reshape(-1)[:n].reshape(codes.shape)


def quantize_stats(x: jax.Array, fmt: FloatFormat, *, interpret: bool = False):
    """(codes, sums[4]) — fused quantize + PVT statistics.

    Padding contributes zeros to every sum, which biases only the count n —
    callers use the true element count (ref.py semantics match exactly).
    """
    x2, n = _pad_flatten(jnp.asarray(x, jnp.float32))
    rows = x2.shape[0]
    grid = (rows // SUBLANES,)
    codes, sums = pl.pallas_call(
        functools.partial(_quantize_stats_kernel, fmt=fmt),
        grid=grid,
        in_specs=[pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0)),
            pl.BlockSpec((4, LANES), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), fmt.container_dtype),
            jax.ShapeDtypeStruct((4, LANES), jnp.float32),
        ],
        interpret=interpret,
        name="quantize_stats",
    )(x2)
    return codes.reshape(-1)[:n].reshape(x.shape), jnp.sum(sums, axis=1)
