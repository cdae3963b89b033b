"""Seeded weights: the inputs the benchmark gives the program and its reference.

Every leaf is drawn from its own key, ``fold_in(key, crc32(path))``, and
every layer of a stacked leaf from ``fold_in(leaf_key, layer)``, so the
reference can draw one layer at a time and get the values the program was
given.  Kinds: ``matrix`` (normal, std ``1/sqrt(fan_in)``), ``embed``
(normal, std 0.02), ``conv`` (normal, std 0.1), ``bias`` (normal, std 0.02)
and ``scale`` (ones).
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

STD = {"embed": 0.02, "conv": 0.1, "bias": 0.02}


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number below 2**62 (the driver's seeds pass 2**31)."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 62:
        raise ValueError(f"seed {seed} out of range")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def draw(key, kind: str, shape):
    if kind == "scale":
        return jnp.ones(shape, jnp.float32)
    std = STD.get(kind)
    if std is None:  # matrix
        std = shape[-2] ** -0.5
    return jax.random.normal(key, shape, jnp.float32) * std


def layer(key, path: str, kind: str, shape, index: int):
    """One layer ``index`` of a stacked leaf of per-layer ``shape``."""
    return draw(jax.random.fold_in(leaf_key(key, path), index), kind, shape)


def init(key, layout):
    """Full tree from ``layout``: ``{path: (kind, shape, stack)}`` with
    ``stack`` the number of layers on a leading axis (0 = not stacked)."""
    out = {}
    for path, (kind, shape, stack) in layout.items():
        k = leaf_key(key, path)
        if stack:
            v = jax.vmap(lambda i: draw(jax.random.fold_in(k, i), kind, shape))(
                jnp.arange(stack))
        else:
            v = draw(k, kind, shape)
        node = out
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = v
    return out
