"""SxEyMz minifloat formats and the bitfield codec used by OMC.

The paper stores parameters as reduced-bitwidth floating point (sign /
exponent / mantissa), e.g. S1E3M7 (11 bits) or S1E4M14 (19 bits).  This module
implements:

  * ``FloatFormat`` — the format descriptor (parse/format "S1E3M7" strings).
  * ``value_quantize`` — round a float32 array to the nearest representable
    value of the format (round-to-nearest-even, flush-to-zero below the
    format's min normal, *saturating* at max normal).
  * ``encode`` / ``decode`` — exact conversion between representable float32
    values and the packed integer bitfield (stored in the smallest uint
    container; see ``packing.py`` for the exact-width bitstream).

Semantics notes (see DESIGN.md §2):
  * Subnormals of the *target* format are fully supported.  This matters for
    real weight tensors: S1E4 formats have min-normal 2**-6 ≈ 0.016, and a
    flush-to-zero quantizer would zero out a large share of typically
    initialized weights (std ~0.02) — training would collapse.  The paper's
    formats therefore must (and here do) extend down to the subnormal step
    2**(1 - bias - M).
  * ``jax.lax.reduce_precision(x, E, M)`` is the oracle for RNE on *normal*
    values, but it flushes target subnormals to zero and overflows to inf.
    ``value_quantize`` rounds the normal range on the raw bits (equal to
    ``reduce_precision`` there, and lowerable inside Pallas TPU kernels),
    uses a scaled round-half-even for the subnormal range, and clamps to
    ±max_normal (OMC storage must never hold inf).  For (5, 10) this
    reproduces the float16 cast bit-for-bit, subnormals included (tested).
  * The bitfield layout is IEEE-like: exponent bias ``2**(E-1)-1``, top
    exponent field reserved for inf/NaN (NaN is propagated so that a poisoned
    training state stays visible; inf is saturated away).
"""

from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np

_FMT_RE = re.compile(r"^S1E(\d+)M(\d+)$")


def uint_container(bits: int):
    """The narrowest uint dtype that holds a ``bits``-wide code."""
    if bits <= 8:
        return jnp.uint8
    if bits <= 16:
        return jnp.uint16
    return jnp.uint32


@dataclasses.dataclass(frozen=True)
class FloatFormat:
    """A 1-sign / `exp_bits`-exponent / `mant_bits`-mantissa float format."""

    exp_bits: int
    mant_bits: int

    def __post_init__(self):
        if not (2 <= self.exp_bits <= 8):
            raise ValueError(f"exp_bits must be in [2, 8], got {self.exp_bits}")
        if not (1 <= self.mant_bits <= 23):
            raise ValueError(f"mant_bits must be in [1, 23], got {self.mant_bits}")

    # -- identity ----------------------------------------------------------
    @property
    def bits(self) -> int:
        return 1 + self.exp_bits + self.mant_bits

    @property
    def name(self) -> str:
        return f"S1E{self.exp_bits}M{self.mant_bits}"

    @classmethod
    def parse(cls, s: str) -> "FloatFormat":
        m = _FMT_RE.match(s.strip().upper())
        if not m:
            raise ValueError(f"bad float format {s!r}; expected e.g. 'S1E3M7'")
        return cls(int(m.group(1)), int(m.group(2)))

    # -- numeric range ------------------------------------------------------
    @property
    def bias(self) -> int:
        return (1 << (self.exp_bits - 1)) - 1

    @property
    def max_exp_field(self) -> int:
        """Largest exponent field for a *normal* value (top field = inf/NaN)."""
        return (1 << self.exp_bits) - 2

    @property
    def max_normal(self) -> float:
        return float(
            (2.0 - 2.0 ** (-self.mant_bits)) * 2.0 ** (self.max_exp_field - self.bias)
        )

    @property
    def min_normal(self) -> float:
        return float(2.0 ** (1 - self.bias))

    @property
    def subnormal_step(self) -> float:
        """Spacing of subnormals — the smallest positive representable value."""
        return float(2.0 ** (1 - self.bias - self.mant_bits))

    @property
    def container_dtype(self):
        return uint_container(self.bits)

    @property
    def container_bytes_per_value(self) -> int:
        return jnp.dtype(self.container_dtype).itemsize

    @property
    def is_identity(self) -> bool:
        return self.exp_bits == 8 and self.mant_bits == 23


FP32 = FloatFormat(8, 23)


def _round_mantissa(x: jax.Array, mant_bits: int) -> jax.Array:
    """Round-to-nearest-even of f32 ``x`` to ``mant_bits`` mantissa bits.

    The add-half-and-truncate trick on the raw bits: exact for f32 normals
    and f32 subnormals alike (XLA CPU flushes subnormals in float
    arithmetic, so E8 formats, whose subnormals ARE f32 subnormals, need
    it), and built from integer ops that both XLA and the Pallas TPU
    lowering accept (Mosaic has no ``reduce_precision``).  The caller
    clamps first: a magnitude ``<= max_normal`` never carries past it,
    because max_normal has zero low bits.
    """
    sh = 23 - mant_bits
    if sh == 0:
        return x
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    lsb = (b >> sh) & np.uint32(1)
    rb = b + (np.uint32((1 << (sh - 1)) - 1) + lsb)
    rb = rb & np.uint32(~((1 << sh) - 1) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(rb, jnp.float32)


def value_quantize(x: jax.Array, fmt: FloatFormat) -> jax.Array:
    """Nearest representable value: RNE, subnormal-aware, saturating. f32->f32.

    One formulation for XLA and the Pallas kernels (``kernels/agg.py``,
    ``kernels/quantize.py``): equal bit for bit to
    ``reduce_precision(x, E, M)`` on the format's normal range.
    """
    x = jnp.asarray(x, jnp.float32)
    if fmt.is_identity:
        return x
    xc = jnp.clip(x, -fmt.max_normal, fmt.max_normal)  # NaN propagates
    out = _round_mantissa(xc, fmt.mant_bits)
    if fmt.exp_bits < 8:
        # Subnormal range: |x| < min_normal rounds (half-to-even) to a
        # multiple of the subnormal step.  For exp_bits <= 7 the step is a
        # normal f32 (>= 2**-85), so the division/round/multiply chain is
        # exact.
        step = np.float32(fmt.subnormal_step)
        sub = jnp.round(xc / step) * step
        out = jnp.where(jnp.abs(xc) < fmt.min_normal, sub, out)
    return jnp.where(jnp.isnan(x), x, out)


def encode(x: jax.Array, fmt: FloatFormat, *, quantize: bool = True) -> jax.Array:
    """float32 -> bitfield in the format's container dtype.

    With ``quantize=True`` (default) the input is first rounded with
    ``value_quantize``; with ``quantize=False`` the caller asserts the values
    are already exactly representable (the repack is then exact).
    """
    if quantize:
        x = value_quantize(x, fmt)
    x = jnp.asarray(x, jnp.float32)
    y, z = fmt.exp_bits, fmt.mant_bits
    b32 = jax.lax.bitcast_convert_type(x, jnp.uint32)
    sign = b32 >> 31
    mag = b32 & np.uint32(0x7FFFFFFF)

    is_zero = mag == 0
    is_nan = mag > np.uint32(0x7F800000)

    e32 = (mag >> 23).astype(jnp.int32)
    ef = e32 - 127 + fmt.bias  # target exponent field (normals: 1..max_exp_field)
    m = (mag & np.uint32(0x7FFFFF)) >> (23 - z)

    sign_sh = sign << (y + z)
    normal = sign_sh | (ef.astype(jnp.uint32) << z) | m
    # Subnormal range (ef <= 0): mantissa field = |v| / subnormal_step, an
    # exact integer in [0, 2**z) for representable inputs.  Two sub-cases:
    #   * normal f32 input (e32 > 0): safe float division — for exp_bits <= 7
    #     the step is a normal f32, and E8 formats never hit this case (their
    #     exponent range equals f32's, so normal inputs map to normal codes).
    #   * f32-subnormal input (e32 == 0): float arithmetic is flushed on XLA
    #     CPU; the field is m32 >> (150 - bias - mant_bits) exactly (low bits
    #     are zero for representable inputs).
    absx = jax.lax.bitcast_convert_type(mag, jnp.float32)
    # Clamped before the cast, which goes through int32: Mosaic has no
    # float -> uint32 conversion.
    m_sub = jnp.round(jnp.minimum(absx / np.float32(fmt.subnormal_step),
                                  np.float32((1 << z) - 1)))
    m_sub = m_sub.astype(jnp.int32).astype(jnp.uint32)
    sub_shift = 150 - fmt.bias - z  # >= 0 for every supported format
    m_sub_tiny = (mag >> min(sub_shift, 31)) if sub_shift < 32 else jnp.zeros_like(mag)
    m_sub = jnp.where(e32 == 0, m_sub_tiny, m_sub)
    subnormal = sign_sh | m_sub
    # Above max_normal: saturate (defensive; value_quantize already clamps).
    too_big = ef > fmt.max_exp_field
    max_code = sign_sh | np.uint32((fmt.max_exp_field << z) | ((1 << z) - 1))
    nan_code = sign_sh | np.uint32((((1 << y) - 1) << z) | (1 << max(z - 1, 0)))

    out = jnp.where(ef <= 0, subnormal, normal)
    out = jnp.where(too_big, max_code, out)
    out = jnp.where(is_zero, sign_sh, out)
    out = jnp.where(is_nan, nan_code, out)
    return out.astype(fmt.container_dtype)


def decode(code: jax.Array, fmt: FloatFormat) -> jax.Array:
    """bitfield -> float32 (exact for every code the format can hold)."""
    y, z = fmt.exp_bits, fmt.mant_bits
    c = jnp.asarray(code).astype(jnp.uint32)
    sign = (c >> (y + z)) & np.uint32(1)
    ef = (c >> z) & np.uint32((1 << y) - 1)
    m = c & np.uint32((1 << z) - 1)

    sign31 = sign << 31
    # Normal path: rebias exponent, shift mantissa up — exact bit assembly.
    nrm_bits = sign31 | ((ef + np.uint32(127 - fmt.bias)) << 23) | (m << (23 - z))
    nrm = jax.lax.bitcast_convert_type(nrm_bits, jnp.float32)
    # Target-format subnormals: m * 2**(1 - bias - mant_bits).
    if fmt.exp_bits == 8:
        # E8 subnormals ARE f32 subnormals — assemble the bits directly
        # (float arithmetic would be flushed to zero on XLA CPU).
        sub = jax.lax.bitcast_convert_type(sign31 | (m << (23 - z)), jnp.float32)
    else:
        # exp_bits <= 7: the step 2**(1-bias-z) >= 2**-85 is a normal f32, so
        # integer-times-power-of-two is exact.  m < 2**23, so the cast goes
        # through int32 (Mosaic has no uint32 -> float conversion).
        sub = m.astype(jnp.int32).astype(jnp.float32) * np.float32(
            2.0 ** (1 - fmt.bias - z))
        sub = jnp.where(sign == 1, -sub, sub)
    # Specials.
    inf_bits = sign31 | np.uint32(0x7F800000)
    nan_bits = sign31 | np.uint32(0x7FC00000)
    special = jax.lax.bitcast_convert_type(
        jnp.where(m == 0, inf_bits, nan_bits), jnp.float32
    )
    signed_zero = jax.lax.bitcast_convert_type(sign31, jnp.float32)

    out = jnp.where(ef == 0, jnp.where(m == 0, signed_zero, sub), nrm)
    out = jnp.where(ef == ((1 << y) - 1), special, out)
    return out


def qdq(x: jax.Array, fmt: FloatFormat) -> jax.Array:
    """Quantize-dequantize simulation (equals value_quantize; kept for API)."""
    return value_quantize(x, fmt)


def qdq_ste(x: jax.Array, fmt: FloatFormat) -> jax.Array:
    """Quantize-dequantize with a straight-through gradient (QAT baseline)."""
    return x + jax.lax.stop_gradient(value_quantize(x, fmt) - x)
