"""Operations and bytes a piece of work needs, computed from its shapes.

The yardstick for the rooflines and utilizations: the least HBM traffic of
the bitpack and fused-aggregation kernels, and the model FLOPs of a training
sample (6·N per frame) and of a served token (2·N).
"""

from __future__ import annotations


def packed_words(n: int, width: int) -> int:
    """32-bit words of an exact ``width``-bit stream of ``n`` fields."""
    return (n * width + 31) // 32


def packbits_bound_bytes(n: int, width: int) -> int:
    """Least HBM bytes to pack (or unpack) ``n`` codes of ``width`` bits: one
    read of the code plane as 32-bit lanes and one write of the bitstream (or
    the reverse)."""
    return 4 * n + 4 * packed_words(n, width)


def container_bytes(bits: int) -> int:
    """Bytes of the smallest unsigned container of a ``bits``-bit code."""
    return 1 if bits <= 8 else 2 if bits <= 16 else 4


def fused_aggregate_bound_bytes(cohort: int, n: int, container: int) -> int:
    """Least HBM bytes of one fused server round over a variable of ``n``
    elements: read the server plane and ``cohort`` client planes, write the
    new server plane."""
    return (cohort + 2) * n * container


def train_flops_per_sample(params: int, frames: int) -> float:
    """Forward and backward model FLOPs of one sample: 6·N per frame."""
    return 6.0 * params * frames


def decode_flops_per_token(params: int) -> float:
    """Model FLOPs of one served token: 2·N."""
    return 2.0 * params
