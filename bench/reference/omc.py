"""Plain reference of OMC storage: minifloat rounding, codes and the PVT affine.

Written from the format's definition (sign / exponent / mantissa with an
IEEE-style bias, subnormals, saturation at the largest normal, round half to
even) and the least-squares affine of the paper's section 2.3; it imports
nothing of the program under test.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np


class Fmt:
    """``S1E<e>M<m>``: bias ``2**(e-1)-1``, top exponent field reserved."""

    def __init__(self, name: str):
        m = re.fullmatch(r"S1E(\d+)M(\d+)", name)
        if not m:
            raise ValueError(f"bad format {name!r}")
        self.name = name
        self.e, self.m = int(m.group(1)), int(m.group(2))
        self.bits = 1 + self.e + self.m
        self.bias = 2 ** (self.e - 1) - 1
        self.max_normal = (2.0 - 2.0 ** -self.m) * 2.0 ** (2 ** self.e - 2 - self.bias)
        self.min_normal = 2.0 ** (1 - self.bias)
        self.sub_step = 2.0 ** (1 - self.bias - self.m)


def quantize(x, fmt: Fmt):
    """Nearest value of ``fmt`` (half to even), saturating; f32 -> f32."""
    x = jnp.clip(jnp.asarray(x, jnp.float32), -fmt.max_normal, fmt.max_normal)
    _, ex = jnp.frexp(x)  # |x| = frac * 2**ex, frac in [0.5, 1)
    step_exp = jnp.maximum(ex - 1 - fmt.m, int(np.log2(fmt.sub_step)))
    # scaling by powers of two is exact, so only the rounding rounds
    return (jnp.round(x * jnp.ldexp(jnp.float32(1.0), -step_exp))
            * jnp.ldexp(jnp.float32(1.0), step_exp))


def decode_codes(codes, fmt: Fmt):
    """Bitfield codes (sign | exponent | mantissa) -> f32 values."""
    c = jnp.asarray(codes).astype(jnp.int32)
    sign = (c >> (fmt.e + fmt.m)) & 1
    ef = (c >> fmt.m) & (2 ** fmt.e - 1)
    man = (c & (2 ** fmt.m - 1)).astype(jnp.float32)
    normal = (1.0 + man / 2.0 ** fmt.m) * jnp.ldexp(jnp.float32(1.0), ef - fmt.bias)
    sub = man * jnp.float32(fmt.sub_step)
    mag = jnp.where(ef == 0, sub, normal)
    mag = jnp.where(ef == 2 ** fmt.e - 1, jnp.nan, mag)
    return jnp.where(sign == 1, -mag, mag)


def pvt(v, vq, batch_axes: int = 0):
    """Least-squares ``(s, b)`` with ``s*vq + b ~ v`` over the trailing axes."""
    v = v.astype(jnp.float32)
    vq = vq.astype(jnp.float32)
    axes = tuple(range(batch_axes, v.ndim))
    n = float(np.prod([v.shape[a] for a in axes]))
    sv, sq = v.sum(axes, keepdims=True), vq.sum(axes, keepdims=True)
    svq, sqq = (v * vq).sum(axes, keepdims=True), (vq * vq).sum(axes, keepdims=True)
    den = n * sqq - sq * sq
    s = jnp.where(den > 0, (n * svq - sv * sq) / jnp.where(den > 0, den, 1.0), 1.0)
    b = (sv - s * sq) / n
    return s, b


def qdq(v, fmt: Fmt, batch_axes: int = 0):
    """Quantize then dequantize with the PVT affine solved per stacked entry."""
    vq = quantize(v, fmt)
    s, b = pvt(v, vq, batch_axes)
    return vq * s + b


def selected(path: str, shape, stack_axes: int, fraction_min_size: int = 256) -> bool:
    """The weights-only rule: matrices (rank >= 2 past the stacked axes)."""
    return len(shape) - stack_axes >= 2 and int(np.prod(shape)) >= fraction_min_size


def ppq_mask(seed: int, round_index, client_id, num_vars: int, fraction: float):
    """Exact-fraction pseudo-random choice of the variables a client quantizes:
    the ``round(num_vars * fraction)`` lowest-ranked uniform scores of a key
    folded with the round and the client."""
    if fraction >= 1.0:
        return jnp.ones((num_vars,), bool)
    k = int(round(num_vars * fraction))
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), round_index),
                             client_id)
    scores = jax.random.uniform(key, (num_vars,))
    return jnp.argsort(jnp.argsort(scores)) < k
