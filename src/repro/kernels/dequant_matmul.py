"""Pallas TPU kernel: A[M,K] @ dequant(Wq[K,N]) — serving's hot matmul.

OMC keeps weights compressed in HBM.  At decode time the matmul is
HBM-bandwidth-bound on the weight stream, so the win is *reading the codes*
(u8/u16/u32) out of HBM and decompressing per-VMEM-tile right before the
MXU — the f32 weights never exist in HBM (paper Fig. 1, TPU-native form;
DESIGN.md §2).

Grid (nm, nn, nk) with k innermost; BlockSpecs stream
    A   (bm, bk) tiles   [M-major]
    Wq  (bk, bn) tiles   (codes, in their uint container)
    out (bm, bn) tiles, f32 accumulation in a VMEM scratch.
Tile defaults (bm=bn=bk=256 for f32/u16) keep the working set
(bm·bk·4 + bk·bn·(2+4) + 2·bm·bn·4 ≈ 2.8 MiB) well inside the ~16 MiB VMEM
with MXU-aligned (128-multiple) dims.

The PVT affine (s, b) is fused into the tile decode.  ``bias=b`` requires
care: W = s·dec(C) + b makes A @ W = s·(A @ dec(C)) + (A·1)·b — the kernel
computes the row-sums of A on the fly for the rank-1 correction.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import FloatFormat, decode as _jnp_decode


def _dequant_matmul_kernel(a_ref, w_ref, s_ref, b_ref, o_ref, acc_ref,
                           rowsum_ref, *, fmt: FloatFormat, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        rowsum_ref[...] = jnp.zeros_like(rowsum_ref)

    a = a_ref[...].astype(jnp.float32)
    w = _jnp_decode(w_ref[...], fmt)  # codes tile -> f32 in VMEM
    acc_ref[...] += jax.lax.dot(a, w, preferred_element_type=jnp.float32)
    rowsum_ref[...] += jnp.sum(a, axis=1, keepdims=True)

    @pl.when(k == nk - 1)
    def _done():
        s = s_ref[0, 0]
        b = b_ref[0, 0]
        # A @ (s·W + b·1) = s·(A @ W) + b·rowsum(A)·1^T
        o_ref[...] = s * acc_ref[...] + b * rowsum_ref[...]


def dequant_matmul(
    a: jax.Array,  # [M, K] f32/bf16
    w_codes: jax.Array,  # [K, N] uint container
    fmt: FloatFormat,
    s=None,
    b=None,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """A @ (s·decode(w_codes) + b), f32 accumulation, tiled for VMEM/MXU."""
    m, k = a.shape
    k2, n = w_codes.shape
    assert k == k2, (a.shape, w_codes.shape)
    bm_, bn_, bk_ = min(bm, m), min(bn, n), min(bk, k)
    # shrink to divisors (kernel assumes exact tiling; pad if needed)
    pad_m, pad_n, pad_k = (-m) % bm_, (-n) % bn_, (-k) % bk_
    if pad_m or pad_k:
        a = jnp.pad(a, ((0, pad_m), (0, pad_k)))
    if pad_k or pad_n:
        w_codes = jnp.pad(w_codes, ((0, pad_k), (0, pad_n)))
    mp, kp = a.shape
    np_ = w_codes.shape[1]
    nm, nn, nk = mp // bm_, np_ // bn_, kp // bk_
    s_arr = jnp.full((1, 1), 1.0 if s is None else s, jnp.float32)
    b_arr = jnp.full((1, 1), 0.0 if b is None else b, jnp.float32)

    out = pl.pallas_call(
        functools.partial(_dequant_matmul_kernel, fmt=fmt, nk=nk),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bm_, bn_), jnp.float32),
            pltpu.VMEM((bm_, 1), jnp.float32),
        ],
        interpret=interpret,
        name="dequant_matmul",
    )(a, w_codes, s_arr, b_arr)
    return out[:m, :n]
