"""Pallas TPU kernels: exact-width bitstream pack/unpack on-device.

``core.packing`` implements the wire bitstream with a scatter-add (pack) and
a dynamic gather (unpack) — fine as a jnp oracle, but scatters serialize on
TPU and the gather defeats fusion.  These kernels reformulate both directions
as fully *static* dataflow so the whole pack/unpack runs as vectorized VPU
work at HBM bandwidth:

  Superblock layout.  For a w-bit field width let L = lcm(32, w).  A block of
  ``P_f = L // w`` consecutive fields occupies exactly ``P_w = L // 32``
  consecutive uint32 words, and *no field crosses a block boundary*.  Within
  a block the field -> (word, shift) mapping is a compile-time constant, so
  both directions unroll into static row loads/stores + scalar shifts over
  a fields-major layout (one superblock per lane):

  * pack:   word j ORs together the in-word contributions of the (statically
    known) fields that land in it — the same ``(f << sh)`` / ``(f >> (31-sh))
    >> 1`` low/high split as ``core.packing.pack``.  Contributed bits are
    disjoint, so the combine is a plain OR — no scatter.
  * unpack: field i reads its containing word and that word's successor
    (clamped to the block edge; the clamp is harmless because a non-crossing
    field's high part is zeroed by the final ``& (2**w - 1)`` mask, mirroring
    the oracle's appended zero word).

Bit-identity with ``core.packing`` is exact by construction: the packed
stream is *canonical* — unique given the field values and zero tail padding —
and both implementations emit it.  Property-tested over every format in the
zoo (and 2-bit ternary) in tests/test_bitpack.py, interpret mode on CPU.

Contract details (bit layout, tail semantics): DESIGN.md §13.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.packing import packed_words

_M32 = np.uint32(0xFFFFFFFF)
# Superblocks per grid step: the lane extent of one block.
_STEP = 2048


@functools.lru_cache(maxsize=None)
def _geometry(width: int) -> Tuple[int, int]:
    """(fields_per_block, words_per_block) of the w-bit superblock."""
    lcm = (32 * width) // math.gcd(32, width)
    return lcm // width, lcm // 32


def _blocks(n: int, p_f: int) -> Tuple[int, int]:
    """(padded superblock count, superblocks per grid step) for n fields.

    The count is padded to whole grid steps of a multiple of 128 lanes."""
    nblocks = -(-max(n, 1) // p_f)
    lanes = min(_STEP, -(-nblocks // 128) * 128)
    return -(-nblocks // lanes) * lanes, lanes


# Both kernels work fields-major: a block is (P_f, lanes) fields or
# (P_w, lanes) words, one superblock per lane, so HBM arrays are lane-dense
# and every access is a static single-row ref load or store.  (A layout
# with superblocks on rows, (rows, P_f) blocks sliced by lane, miscompiled
# on TPU v5e: some packed words lost bits 16-23.)


def _pack_kernel(f_ref, o_ref, *, width: int):
    p_f, p_w = _geometry(width)
    for j in range(p_w):
        acc = None
        for i in range(p_f):
            word, sh = (i * width) // 32, (i * width) % 32
            if word == j:
                term = f_ref[i : i + 1, :] << np.uint32(sh)
            elif word + 1 == j and sh + width > 32:  # field crosses into j
                # field >> (32-sh) is UB at sh == 0; the two-step shift is safe
                term = (f_ref[i : i + 1, :] >> np.uint32(31 - sh)) >> np.uint32(1)
            else:
                continue
            acc = term if acc is None else (acc | term)
        o_ref[j : j + 1, :] = acc


def _unpack_kernel(w_ref, o_ref, *, width: int):
    p_f, p_w = _geometry(width)
    mask = np.uint32((1 << width) - 1) if width < 32 else _M32
    for i in range(p_f):
        word, sh = (i * width) // 32, (i * width) % 32
        lo = w_ref[word : word + 1, :] >> np.uint32(sh)
        nxt = min(word + 1, p_w - 1)  # edge clamp; high bits masked off below
        hi = (w_ref[nxt : nxt + 1, :] << np.uint32(31 - sh)) << np.uint32(1)
        o_ref[i : i + 1, :] = (lo | hi) & mask


def _call(kernel, x, rows_in: int, rows_out: int, lanes: int, width: int,
          interpret: bool, name: str) -> jax.Array:
    nblocks = x.shape[1]
    return pl.pallas_call(
        functools.partial(kernel, width=width),
        grid=(nblocks // lanes,),
        in_specs=[pl.BlockSpec((rows_in, lanes), lambda i: (0, i))],
        out_specs=pl.BlockSpec((rows_out, lanes), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((rows_out, nblocks), jnp.uint32),
        interpret=interpret,
        name=name,
    )(x)


def pack(codes: jax.Array, width: int, *, interpret: bool = False) -> jax.Array:
    """Pack ``codes`` (values < 2**width) into the exact uint32 bitstream.

    Bit-identical to ``core.packing.pack`` (the canonical layout).
    """
    if not (1 <= width <= 32):
        raise ValueError(f"width must be in [1, 32], got {width}")
    p_f, p_w = _geometry(width)
    flat = codes.reshape(-1).astype(jnp.uint32)
    n = flat.shape[0]
    nblocks, lanes = _blocks(n, p_f)
    flat = jnp.pad(flat, (0, nblocks * p_f - n))
    out = _call(_pack_kernel, flat.reshape(nblocks, p_f).T, p_f, p_w, lanes,
                width, interpret, "pack_bits")
    return out.T.reshape(-1)[: packed_words(n, width)]


def unpack(words: jax.Array, width: int, n: int, *, interpret: bool = False) -> jax.Array:
    """Inverse of :func:`pack`: recover ``n`` codes of ``width`` bits (uint32)."""
    if not (1 <= width <= 32):
        raise ValueError(f"width must be in [1, 32], got {width}")
    p_f, p_w = _geometry(width)
    flat = words.reshape(-1).astype(jnp.uint32)
    nblocks, lanes = _blocks(n, p_f)
    # Zero tail padding == the oracle's appended zero word.
    flat = jnp.pad(flat, (0, nblocks * p_w - flat.shape[0]))
    out = _call(_unpack_kernel, flat.reshape(nblocks, p_w).T, p_w, p_f, lanes,
                width, interpret, "unpack_bits")
    return out.T.reshape(-1)[:n]


def pack_moved_bytes(n: int, width: int) -> int:
    """HBM bytes the pack kernel actually moves (padded operands + result)."""
    p_f, p_w = _geometry(width)
    nblocks, _ = _blocks(n, p_f)
    return 4 * nblocks * p_f + 4 * nblocks * p_w


def unpack_moved_bytes(n: int, width: int) -> int:
    """HBM bytes the unpack kernel actually moves (padded operands + result)."""
    return pack_moved_bytes(n, width)
