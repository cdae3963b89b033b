"""Plain decoder-only transformer (the Qwen2 layer) over OMC-rounded weights.

Pre-norm blocks: RMSNorm, grouped-query attention with biases on q/k/v and
rotary positions (theta from the config, the two halves of each head
rotated), causal softmax, output projection; RMSNorm, SwiGLU MLP; a final
RMSNorm and the tied embedding as the head.  The whole sequence runs at once
with the whole score matrix, layer by layer: each layer's weights are drawn
from the seed and rounded to the storage format (one affine per layer) just
before use, so no more than one layer is held.  Imports nothing of the
program under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import omc, weights


def layout(cfg):
    d, f, n = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    hd = cfg["head_dim"] or d // cfg["n_heads"]
    q, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    blk = {"attn_norm": ("scale", (d,)), "mlp_norm": ("scale", (d,)),
           "wq": ("matrix", (d, q)), "wk": ("matrix", (d, kv)), "wv": ("matrix", (d, kv)),
           "wo": ("matrix", (q, d)), "w1": ("matrix", (d, f)), "w3": ("matrix", (d, f)),
           "w2": ("matrix", (f, d))}
    if cfg["qkv_bias"]:
        blk.update(bq=("bias", (q,)), bk=("bias", (kv,)), bv=("bias", (kv,)))
    out = {"embed": ("embed", (cfg["vocab"], d), 0), "final_norm": ("scale", (d,), 0)}
    if not cfg["tie_embeddings"]:
        out["lm_head"] = ("matrix", (d, cfg["vocab"]), 0)
    out.update({f"blocks/{k}": (kind, shape, n) for k, (kind, shape) in blk.items()})
    return out


def param_count(cfg) -> int:
    return sum(int(np.prod(shape)) * max(stack, 1)
               for _, shape, stack in layout(cfg).values())


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(ang)[None, :, None].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None].astype(x.dtype)
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _matmul(a, b):
    return a @ b


def scaled_cast(dtype):
    """A matmul whose operands are rounded to ``dtype`` with one scale per
    tensor (amax to the dtype's largest finite value), accumulated in f32."""
    top = float(jnp.finfo(dtype).max)

    def cast(a):
        scale = jnp.maximum(jnp.abs(a).max(), 1e-30) / top
        return (a / scale).astype(dtype).astype(jnp.float32) * scale

    return lambda a, b: cast(a) @ cast(b)


def block(cfg, w, x, mm=_matmul):
    b, s, d = x.shape
    h_q, h_kv = cfg["n_heads"], cfg["n_kv_heads"]
    hd = cfg["head_dim"] or d // h_q
    eps = cfg["norm_eps"]
    h = _rms(x, w["attn_norm"], eps)
    q = mm(h, w["wq"]) + w.get("bq", 0.0)
    k = mm(h, w["wk"]) + w.get("bk", 0.0)
    v = mm(h, w["wv"]) + w.get("bv", 0.0)
    q = _rope(q.reshape(b, s, h_q, hd), cfg["rope_theta"])
    k = _rope(k.reshape(b, s, h_kv, hd), cfg["rope_theta"])
    v = v.reshape(b, s, h_kv, hd)
    k = jnp.repeat(k, h_q // h_kv, axis=2)  # query head i reads key head i // group
    v = jnp.repeat(v, h_q // h_kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / hd ** 0.5
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, h_q * hd)
    x = x + mm(o, w["wo"])
    h = _rms(x, w["mlp_norm"], eps)
    return x + mm(jax.nn.silu(mm(h, w["w1"])) * mm(h, w["w3"]), w["w2"])


class Forward:
    """Logits at chosen positions of token sequences, computed layer by layer.

    ``control=None``: float32 at the highest matmul precision.  A control
    computes lower: ``"bfloat16"`` holds every array in bfloat16; a float8
    name rounds every matmul operand to it (one scale per tensor) and keeps
    the rest in float32."""

    def __init__(self, cfg, key, fmt: omc.Fmt, control=None):
        self.cfg, self.key, self.fmt = cfg, key, fmt
        dtype = jnp.bfloat16 if control == "bfloat16" else jnp.float32
        mm = (scaled_cast(jnp.dtype(control)) if control not in (None, "bfloat16")
              else _matmul)
        self.dtype = dtype
        lay = layout(cfg)
        blocks = {p.split("/", 1)[1]: v for p, v in lay.items() if p.startswith("blocks/")}
        precision = "highest" if control is None else "default"

        def stored(path, kind, shape, value):
            return omc.qdq(value, fmt) if omc.selected(path, shape, 0) else value

        def layer(x, key, index):
            with jax.default_matmul_precision(precision):
                w = {n: stored(n, kind, shape,
                               weights.layer(key, f"blocks/{n}", kind, shape, index)
                               ).astype(dtype)
                     for n, (kind, shape, _) in blocks.items()}
                return block(cfg, w, x, mm)

        def table(key):
            kind, shape, _ = lay["embed"]
            return omc.qdq(weights.draw(weights.leaf_key(key, "embed"), kind, shape), fmt)

        def head(x, emb, key):
            with jax.default_matmul_precision(precision):
                kind, shape, _ = lay["final_norm"]
                norm = weights.draw(weights.leaf_key(key, "final_norm"), kind, shape)
                h = _rms(x, norm.astype(dtype), cfg["norm_eps"])
                return mm(h, emb.astype(dtype).T).astype(jnp.float32)

        self._layer = jax.jit(layer)
        self._table = jax.jit(table)
        self._head = jax.jit(head)
        if not cfg["tie_embeddings"]:
            raise NotImplementedError("untied heads")

    def __call__(self, tokens, start: int, count: int):
        """Logits ``[B, count, vocab]`` at positions ``start .. start+count-1``."""
        emb = self._table(self.key)
        x = emb[jnp.asarray(tokens)].astype(self.dtype)
        for i in range(self.cfg["n_layers"]):
            x = self._layer(x, self.key, jnp.int32(i))
        return self._head(x[:, start:start + count], emb, self.key)
