"""Plain float32 Conformer encoder with a framewise cross-entropy loss.

The block as the paper's streaming Conformer has it: half feed-forward,
self-attention with rotary positions (causal, over a window of past frames),
the convolution module (pointwise with a GLU, causal depthwise convolution,
GroupNorm in place of BatchNorm, swish, pointwise), a second half
feed-forward and a closing LayerNorm.  Written in straightforward
``jax.numpy`` with the whole score matrix and no remat; it imports nothing of
the program under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def layout(cfg):
    """``{path: (kind, per-layer shape, layers)}`` of the parameter tree."""
    d, f, n = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    out = {"in_proj": ("matrix", (cfg["d_in"], d), 0),
           "in_bias": ("bias", (d,), 0),
           "out_proj": ("matrix", (d, cfg["n_classes"]), 0),
           "out_bias": ("bias", (cfg["n_classes"],), 0)}
    blk = {}
    for ffn in ("ffn1", "ffn2"):
        blk.update({f"{ffn}/scale": ("scale", (d,)), f"{ffn}/bias": ("bias", (d,)),
                    f"{ffn}/w1": ("matrix", (d, f)), f"{ffn}/b1": ("bias", (f,)),
                    f"{ffn}/w2": ("matrix", (f, d)), f"{ffn}/b2": ("bias", (d,))})
    for name in ("attn", "conv", "out"):
        blk[f"{name}_scale"] = ("scale", (d,))
        blk[f"{name}_bias"] = ("bias", (d,))
    for name in ("wq", "wk", "wv", "wo", "conv_pw2"):
        blk[name] = ("matrix", (d, d))
    blk["conv_pw1"] = ("matrix", (d, 2 * d))
    blk["conv_dw"] = ("conv", (cfg["conv_kernel"], d))
    blk["conv_gn_scale"] = ("scale", (d,))
    blk["conv_gn_bias"] = ("bias", (d,))
    out.update({f"blocks/{k}": (kind, shape, n) for k, (kind, shape) in blk.items()})
    return out


def param_count(cfg) -> int:
    total = 0
    for _, shape, stack in layout(cfg).values():
        size = 1
        for s in shape:
            size *= s
        total += size * max(stack, 1)
    return total


def _ln(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _group_norm(x, scale, bias, groups, eps):
    *lead, c = x.shape
    g = x.reshape(*lead, groups, c // groups)
    mu = g.mean(-1, keepdims=True)
    var = ((g - mu) ** 2).mean(-1, keepdims=True)
    return ((g - mu) / jnp.sqrt(var + eps)).reshape(*lead, c) * scale + bias


def _rope(x, theta):
    """x: [B, S, H, hd]; rotates the two halves of each head."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(ang)[None, :, None].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None].astype(x.dtype)
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _half_ffn(x, p, eps):
    h = _ln(x, p["scale"], p["bias"], eps)
    return x + 0.5 * (jax.nn.silu(h @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"])


def block(cfg, w, x):
    eps, heads = cfg["norm_eps"], cfg["n_heads"]
    b, s, d = x.shape
    hd = d // heads
    x = _half_ffn(x, w["ffn1"], eps)
    h = _ln(x, w["attn_scale"], w["attn_bias"], eps)
    q, k, v = ((h @ w[n]).reshape(b, s, heads, hd) for n in ("wq", "wk", "wv"))
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / hd ** 0.5
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    ok = j <= i
    if cfg.get("window") is not None:
        ok = ok & (j > i - cfg["window"])
    probs = jax.nn.softmax(jnp.where(ok, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, d)
    x = x + o @ w["wo"]
    h = _ln(x, w["conv_scale"], w["conv_bias"], eps) @ w["conv_pw1"]
    h = h[..., :d] * jax.nn.sigmoid(h[..., d:])
    kw = cfg["conv_kernel"]
    hp = jnp.pad(h, ((0, 0), (kw - 1, 0), (0, 0)))
    h = sum(hp[:, t: t + s] * w["conv_dw"][t] for t in range(kw))
    h = _group_norm(h, w["conv_gn_scale"], w["conv_gn_bias"], cfg["gn_groups"], eps)
    x = x + jax.nn.silu(h) @ w["conv_pw2"]
    x = _half_ffn(x, w["ffn2"], eps)
    return _ln(x, w["out_scale"], w["out_bias"], eps)


def loss(cfg, params, batch):
    """Mean framewise cross-entropy of ``batch = {frames, labels}``."""
    dt = params["in_proj"].dtype
    x = batch["frames"].astype(dt) @ params["in_proj"] + params["in_bias"]
    x, _ = jax.lax.scan(lambda h, w: (block(cfg, w, h), None), x, params["blocks"])
    logits = (x @ params["out_proj"] + params["out_bias"]).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)
    return -picked.mean()
