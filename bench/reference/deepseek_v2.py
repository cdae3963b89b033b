"""Plain DeepSeek-V2 decoder (arXiv 2405.04434, the published
``modeling_deepseek.py``) over OMC-rounded weights, for a device that holds a
share of each layer's routed experts.

Pre-norm blocks: RMSNorm; multi-head latent attention: ``q = h·Wq`` split
per head into a 128-wide part without position and a 64-wide rotary part,
``[c, k_pe] = h·Wkv_a``, ``c`` RMS-normalised, ``[k_nope, v] = c·Wkv_b``,
one rotary key head ``k_pe`` shared by all heads, YaRN rotary frequencies on
the de-interleaved pairs (``view(d/2, 2).transpose``, then the two halves
rotated, as the published ``apply_rotary_pos_emb``), causal softmax of
scores scaled by ``q_head_dim^-0.5 · mscale²``, output projection; RMSNorm
and a SwiGLU in the first ``first_k_dense_replace`` layers, else the expert
layer: softmax over all ``router_experts`` router logits, greedy top-k
weights as they are (renormalised only with ``norm_topk_prob``) times
``routed_scaling_factor``, the held experts' SwiGLUs weighted by them, plus
the shared experts' SwiGLU; a final RMSNorm and the untied head.

Departures from the published model, none of which changes a result:
only the expanded attention path (no absorbed decode and no cache: the whole
sequence runs at once); attention per sequence and block of 1024 queries,
each block against every key under the causal mask; each held expert as a
dense pass over every token whose output is weighted by the token's gate for
it (zero where the expert was not chosen), in place of a dispatch.  What the experts held
elsewhere would add is left out, as in the program.  Each layer's weights
are drawn from the seed and rounded to the storage format (one affine per
layer, and per expert for the experts' matrices) just before use.  Imports
nothing of the program under test.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import omc, weights
from .transformer import _matmul, scaled_cast

Q_BLOCK = 1024
EXPERT_LEAVES = ("w1", "w3", "w2")  # in the expert layers: [held, in, out]


def layout(cfg):
    d, h, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    f, fe, n_held = cfg["intermediate_size"], cfg["moe_intermediate_size"], len(cfg["held_experts"])
    fs = cfg["n_shared_experts"] * fe
    attn = {"attn_norm": ("scale", (d,)), "wq": ("matrix", (d, h * (dn + dr))),
            "wkv_a": ("matrix", (d, r + dr)), "kv_norm": ("scale", (r,)),
            "wkv_b": ("matrix", (r, h * (dn + dv))), "wo": ("matrix", (h * dv, d)),
            "mlp_norm": ("scale", (d,))}
    dense = dict(attn, w1=("matrix", (d, f)), w3=("matrix", (d, f)), w2=("matrix", (f, d)))
    moe = dict(attn, router=("matrix", (d, cfg["router_experts"])),
               w1=("matrix", (n_held, d, fe)), w3=("matrix", (n_held, d, fe)),
               w2=("matrix", (n_held, fe, d)), shared_w1=("matrix", (d, fs)),
               shared_w3=("matrix", (d, fs)), shared_w2=("matrix", (fs, d)))
    n_dense = cfg["first_k_dense_replace"]
    out = {"embed": ("embed", (cfg["vocab_size"], d), 0), "final_norm": ("scale", (d,), 0),
           "lm_head": ("matrix", (d, cfg["vocab_size"]), 0)}
    out.update({f"dense/{k}": (kind, shape, n_dense) for k, (kind, shape) in dense.items()})
    out.update({f"blocks/{k}": (kind, shape, cfg["num_hidden_layers"] - n_dense)
                for k, (kind, shape) in moe.items()})
    return out


def param_count(cfg) -> int:
    return sum(int(np.prod(shape)) * max(stack, 1)
               for _, shape, stack in layout(cfg).values())


def yarn(cfg):
    """``(inv_freq [dr/2], softmax scale)`` from the published YaRN formulas."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    y = cfg.get("rope_scaling")
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5
    if not y:
        return freq_extra.astype(np.float32), scale

    def mscale(s, m):
        return 1.0 if s <= 1 else 0.1 * m * math.log(s) + 1.0

    def correction_dim(rotations):
        return (dim * math.log(y["original_max_position_embeddings"] / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    freq_inter = 1.0 / (y["factor"] * base ** (np.arange(0, dim, 2) / dim))
    inv_freq = freq_inter * ramp + freq_extra * (1 - ramp)
    # the cos/sin factor mscale(factor, mscale) / mscale(factor, mscale_all_dim)
    assert mscale(y["factor"], y["mscale"]) == mscale(y["factor"], y["mscale_all_dim"])
    m = mscale(y["factor"], y["mscale_all_dim"])
    return inv_freq.astype(np.float32), scale * m * m


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, positions, inv_freq):
    """x [S, H, d] at ``positions`` [S]: pairs de-interleaved, halves rotated."""
    s, h, d = x.shape
    x = x.reshape(s, h, d // 2, 2).swapaxes(-1, -2).reshape(s, h, d)
    ang = positions[:, None].astype(jnp.float32) * inv_freq
    emb = jnp.concatenate([ang, ang], -1)[:, None]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


def _swiglu(h, w1, w3, w2, mm):
    return mm(jax.nn.silu(mm(h, w1)) * mm(h, w3), w2)


def attention(cfg, w, h, mm, inv_freq, scale):
    """Latent attention of one sequence ``h`` [S, D] (normed) -> [S, D]."""
    s = h.shape[0]
    nh, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    pos = jnp.arange(s)
    q = mm(h, w["wq"]).reshape(s, nh, dn + dr)
    ckv = mm(h, w["wkv_a"])
    c = _rms(ckv[:, :r], w["kv_norm"], cfg["rms_norm_eps"])
    k_pe = _rope(ckv[:, None, r:], pos, inv_freq)
    kv = mm(c, w["wkv_b"]).reshape(s, nh, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (s, nh, dr))], -1)
    v = kv[..., dn:]
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, inv_freq)], -1)
    n = -(-s // Q_BLOCK)
    blocks = jnp.pad(q, ((0, n * Q_BLOCK - s), (0, 0), (0, 0))).reshape(n, Q_BLOCK, nh, dn + dr)

    def block(args):  # one block of queries against every key, causally masked
        qb, lo = args
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        causal = pos[None, :] <= (lo + jnp.arange(Q_BLOCK))[:, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(block, (blocks, jnp.arange(n) * Q_BLOCK)).reshape(n * Q_BLOCK, nh * dv)
    return mm(out[:s], w["wo"])


def experts(cfg, w, h, mm):
    """Held routed experts plus the shared experts of tokens ``h`` [T, D]."""
    probs = jax.nn.softmax(mm(h, w["router"]), axis=-1)
    gate, ids = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        gate = gate / gate.sum(-1, keepdims=True)
    gate = gate * cfg["routed_scaling_factor"]
    y = _swiglu(h, w["shared_w1"], w["shared_w3"], w["shared_w2"], mm)
    for j, e in enumerate(cfg["held_experts"]):
        weight = jnp.where(ids == e, gate, 0.0).sum(-1)  # zero where e was not chosen
        y = y + weight[:, None] * _swiglu(h, w["w1"][j], w["w3"][j], w["w2"][j], mm)
    return y


def block(cfg, w, x, dense: bool, mm, inv_freq, scale):
    """One layer over one sequence ``x`` [S, D]."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(cfg, w, _rms(x, w["attn_norm"], eps), mm, inv_freq, scale)
    h = _rms(x, w["mlp_norm"], eps)
    return x + (_swiglu(h, w["w1"], w["w3"], w["w2"], mm) if dense else experts(cfg, w, h, mm))


class Forward:
    """Logits at chosen positions of token sequences, computed layer by layer.

    ``control=None``: float32 at the highest matmul precision.  A control
    computes lower: ``"bfloat16"`` holds every array in bfloat16; a float8
    name rounds every matmul operand to it (one scale per tensor) and keeps
    the rest in float32."""

    def __init__(self, cfg, key, fmt: omc.Fmt, control=None):
        self.cfg, self.key = cfg, key
        dtype = jnp.bfloat16 if control == "bfloat16" else jnp.float32
        mm = (scaled_cast(jnp.dtype(control)) if control not in (None, "bfloat16")
              else _matmul)
        self.dtype = dtype
        lay = layout(cfg)
        precision = "highest" if control is None else "default"
        inv_freq, scale = yarn(cfg)

        def stored(group, name, shape, value):
            if not omc.selected(name, shape, 0):
                return value
            return omc.qdq(value, fmt, 1 if group == "blocks" and name in EXPERT_LEAVES else 0)

        def layer(group, x, key, index):
            with jax.default_matmul_precision(precision):
                w = {p.split("/", 1)[1]: stored(group, p.split("/", 1)[1], shape,
                                                weights.layer(key, p, kind, shape, index)
                                                ).astype(dtype)
                     for p, (kind, shape, _) in lay.items() if p.startswith(group + "/")}
                return jax.lax.map(lambda xb: block(cfg, w, xb, group == "dense", mm,
                                                    inv_freq, scale), x)

        def table(key):
            kind, shape, _ = lay["embed"]
            return omc.qdq(weights.draw(weights.leaf_key(key, "embed"), kind, shape), fmt)

        def head(x, key):
            with jax.default_matmul_precision(precision):
                norm = weights.draw(weights.leaf_key(key, "final_norm"), *lay["final_norm"][:2])
                kind, shape, _ = lay["lm_head"]
                w = omc.qdq(weights.draw(weights.leaf_key(key, "lm_head"), kind, shape), fmt)
                h = _rms(x, norm.astype(dtype), cfg["rms_norm_eps"])
                return mm(h, w.astype(dtype)).astype(jnp.float32)

        self._dense = jax.jit(lambda x, key, i: layer("dense", x, key, i))
        self._moe = jax.jit(lambda x, key, i: layer("blocks", x, key, i))
        self._table = jax.jit(table)
        self._head = jax.jit(head)

    def __call__(self, tokens, start: int, count: int):
        """Logits ``[B, count, vocab]`` at positions ``start .. start+count-1``."""
        x = self._table(self.key)[jnp.asarray(tokens)].astype(self.dtype)
        n_dense = self.cfg["first_k_dense_replace"]
        for i in range(n_dense):
            x = self._dense(x, self.key, jnp.int32(i))
        for i in range(self.cfg["num_hidden_layers"] - n_dense):
            x = self._moe(x, self.key, jnp.int32(i))
        return self._head(x[:, start:start + count], self.key)
