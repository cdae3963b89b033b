"""Pallas TPU kernel: fused compressed-domain cohort aggregation.

The unfused server round materializes f32 cohort state three times per
selected variable: decode every client upload, weighted-average, interpolate
into the decoded server value, then re-quantize + re-solve PVT.  For a
cohort of C clients that is (C + 1) f32 HBM round trips of the full variable.
This kernel fuses the whole chain —

    dequant(client codes) -> mask dead rows -> weighted mean
        -> server interpolation -> value_quantize -> encode + PVT sums

— into one pass: codes stream HBM->VMEM, every f32 intermediate lives only
in the (C, TILE) VMEM working set, and the outputs are the new server codes
plus the four PVT sums (Σv, Σṽ, Σvṽ, Σṽ²) per stacked entry.  The (s, b)
affine is solved from those sums outside the kernel with the exact
``pvt_solve_fast`` closed form.

Semantics (the contract the engine equivalence gate enforces — DESIGN.md §13):
  * client row c is reconstructed as ``s_c · decode(codes_c) + b_c``;
  * dead clients (weight <= 0) are zeroed *before* the weighted mean — the
    same ``where(alive, x, 0)`` the unfused engine applies, so NaN/garbage
    in failed-client rows never propagates;
  * weighted mean divides by ``max(Σw, 1e-9)`` (``cohort.aggregate_weighted``);
  * the new server value is ``old + lr·(mean − old)`` and is re-quantized
    with round-to-nearest-even via ``value_quantize`` — identical rounding to
    the unfused ``compress_variable`` path;
  * PVT sums are masked to the true element count (tail padding decodes to
    the padded-code value and would otherwise bias the solve).

Validated in interpret mode against ``ref.ref_fused_aggregate`` (and, at the
engine level, against the unfused round) in tests/test_kernels.py.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.formats import (
    FloatFormat,
    decode as _jnp_decode,
    encode as _jnp_encode,
    value_quantize as _jnp_value_quantize,
)

LANES = 128  # lane width of one vreg
TILE_ROWS = 32  # sublane rows per block: a multiple of every container's
                # native tiling (u32: 8, u16: 16, u8: 32 rows)
TILE = TILE_ROWS * LANES  # elements per block


def _fused_kernel(srv_ref, ss_ref, sb_ref, cl_ref, cs_ref, cb_ref, w_ref,
                  k_ref, o_ref, sums_ref, *, fmt: FloatFormat, m: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    w = w_ref[...]  # (C, 1, 1)
    lr, wsum = k_ref[0, 0], k_ref[0, 1]
    old = _jnp_decode(srv_ref[...], fmt) * ss_ref[...] + sb_ref[...]  # (R, L)
    x = _jnp_decode(cl_ref[...], fmt)  # (C, R, L)
    x = x * cs_ref[...] + cb_ref[...]
    # Zero dead rows BEFORE the mean — mirrors engine's where(alive, x, 0);
    # where (not multiply) so NaN in failed-client rows cannot propagate.
    x = jnp.where(w > 0, x, 0.0)
    acc = jnp.sum(x * w, axis=0) / wsum
    new = old + lr * (acc - old)
    vq = _jnp_value_quantize(new, fmt)
    o_ref[...] = _jnp_encode(vq, fmt, quantize=False)
    # PVT sums over true elements only: the padded tail decodes to the
    # padded-code value, not 0, and would bias the affine solve.  Partial
    # sums stay per lane; the lanes are summed outside the kernel.
    row = jax.lax.broadcasted_iota(jnp.int32, new.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, new.shape, 1)
    valid = (j * TILE_ROWS + row) * LANES + col < m
    nv = jnp.where(valid, new, 0.0)
    qv = jnp.where(valid, vq, 0.0)
    sums_ref[...] += jnp.concatenate(
        [jnp.sum(t, axis=0, keepdims=True) for t in (nv, qv, nv * qv, qv * qv)],
        axis=0)


def _solve_from_sums(sums: jax.Array, n: int) -> Tuple[jax.Array, jax.Array]:
    """(s, b) per stacked entry from [SB, 4] sums — pvt_solve_fast closed form."""
    s_v, s_q, s_vq, s_qq = sums[:, 0], sums[:, 1], sums[:, 2], sums[:, 3]
    nf = jnp.float32(n)
    den = nf * s_qq - s_q * s_q
    num = nf * s_vq - s_v * s_q
    degenerate = den <= 0
    s = jnp.where(degenerate, 1.0, num / jnp.where(degenerate, 1.0, den))
    b = (s_v - s * s_q) / nf
    return s.astype(jnp.float32), b.astype(jnp.float32)


def _col(x, sb: int) -> jax.Array:
    """PVT scalar (scalar or per-stacked-entry) -> (SB, 1, 1) f32."""
    x = jnp.asarray(x, jnp.float32)
    if x.size == sb:
        return x.reshape(sb, 1, 1)
    return jnp.full((sb, 1, 1), x.reshape(()))


def _ccol(x, c: int, sb: int) -> jax.Array:
    """Per-client PVT scalar (per-client or per-(client, entry)) -> (SB, C, 1, 1)."""
    x = jnp.asarray(x, jnp.float32)
    if x.size == c * sb:
        return x.reshape(c, sb).T.reshape(sb, c, 1, 1)
    return jnp.broadcast_to(x.reshape(1, c, 1, 1), (sb, c, 1, 1))


def fused_aggregate(
    srv_codes: jax.Array,
    srv_s: jax.Array,
    srv_b: jax.Array,
    cl_codes: jax.Array,
    cl_s: jax.Array,
    cl_b: jax.Array,
    weights: jax.Array,
    lr,
    fmt: FloatFormat,
    *,
    batch_axes: int = 0,
    interpret: bool = False,
):
    """One variable's server round, entirely in the compressed domain.

    srv_codes: leaf-shaped container codes; cl_codes: (C,) + leaf shape;
    (srv_s, srv_b) / (cl_s, cl_b): the matching PVT scalars (scalar or
    per-stacked-entry with ``batch_axes`` leading stacked axes); weights: (C,)
    f32 aggregation weights (0 == dead client).  Returns (new_codes, s, b)
    shaped exactly like the unfused ``compress_variable(..., fast=True)``
    output on the aggregated tree.
    """
    shape = srv_codes.shape
    sb = int(np.prod(shape[:batch_axes])) if batch_axes else 1
    m = int(srv_codes.size) // sb
    c = int(cl_codes.shape[0])
    m_pad = -(-m // TILE) * TILE
    rows = m_pad // LANES

    # Every block's last two dims are (TILE_ROWS, LANES) or the whole array
    # dims; stacked entries and clients ride on squeezed leading axes.
    srv2 = srv_codes.reshape(sb, m).astype(fmt.container_dtype)
    cl2 = cl_codes.reshape(c, sb, m).astype(fmt.container_dtype)
    srv2 = jnp.pad(srv2, ((0, 0), (0, m_pad - m))).reshape(sb, rows, LANES)
    cl2 = jnp.pad(cl2, ((0, 0), (0, 0), (0, m_pad - m)))
    cl2 = cl2.reshape(c, sb, rows, LANES)
    ss, sbias = _col(srv_s, sb), _col(srv_b, sb)
    cs, cb = _ccol(cl_s, c, sb), _ccol(cl_b, c, sb)
    w = jnp.asarray(weights, jnp.float32)
    w3 = w.reshape(c, 1, 1)
    k = jnp.stack([jnp.asarray(lr, jnp.float32),
                   jnp.maximum(jnp.sum(w), 1e-9)]).reshape(1, 2)

    sq = pl.Squeezed()
    grid = (sb, rows // TILE_ROWS)
    new_codes, sums = pl.pallas_call(
        functools.partial(_fused_kernel, fmt=fmt, m=m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((sq, TILE_ROWS, LANES), lambda i, j: (i, j, 0)),
            pl.BlockSpec((sq, 1, 1), lambda i, j: (i, 0, 0)),      # server s
            pl.BlockSpec((sq, 1, 1), lambda i, j: (i, 0, 0)),      # server b
            pl.BlockSpec((c, sq, TILE_ROWS, LANES),
                         lambda i, j: (0, i, j, 0)),               # client codes
            pl.BlockSpec((sq, c, 1, 1), lambda i, j: (i, 0, 0, 0)),  # client s
            pl.BlockSpec((sq, c, 1, 1), lambda i, j: (i, 0, 0, 0)),  # client b
            pl.BlockSpec((c, 1, 1), lambda i, j: (0, 0, 0)),       # weights
            pl.BlockSpec((1, 2), lambda i, j: (0, 0)),             # lr, Σw
        ],
        out_specs=[
            pl.BlockSpec((sq, TILE_ROWS, LANES), lambda i, j: (i, j, 0)),
            pl.BlockSpec((sq, 4, LANES), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((sb, rows, LANES), fmt.container_dtype),
            jax.ShapeDtypeStruct((sb, 4, LANES), jnp.float32),
        ],
        interpret=interpret,
        name="fused_aggregate",
    )(srv2, ss, sbias, cl2, cs, cb, w3, k)

    codes = new_codes.reshape(sb, m_pad)[:, :m].reshape(shape)
    s, b = _solve_from_sums(jnp.sum(sums, axis=2), m)
    if batch_axes:
        bshape = shape[:batch_axes] + (1,) * (len(shape) - batch_axes)
        return codes, s.reshape(bshape), b.reshape(bshape)
    return codes, s.reshape(()), b.reshape(())


def fused_aggregate_moved_bytes(
    cohort: int, n: int, fmt: FloatFormat, *, stack_entries: int = 1
) -> int:
    """HBM bytes the fused pass actually moves: its operand + result buffers.

    A fused kernel reads each operand and writes each result exactly once;
    every f32 intermediate is tile-local VMEM, so the HBM traffic is the sum
    of the (padded) buffer sizes: (C+1) code planes in + 1 out, the per-entry
    PVT scalars, the weights, and the per-lane [SB, 4, LANES] sums.
    """
    sb = stack_entries
    m = n // sb
    m_pad = -(-m // TILE) * TILE
    cb = fmt.container_bytes_per_value
    codes = (cohort + 1 + 1) * sb * m_pad * cb  # C client + 1 server in, 1 out
    scalars = 4 * (2 * sb + 2 * cohort * sb + cohort + 2)  # s/b, w, lr, Σw
    sums = 4 * sb * 4 * LANES
    return codes + scalars + sums
