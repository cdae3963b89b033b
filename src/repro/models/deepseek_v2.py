"""DeepSeek-V2 decoder LM: multi-head latent attention and a held share of
routed experts beside shared experts (arXiv 2405.04434; the published
``modeling_deepseek.py``).

Block (pre-norm, residual around each half):

* Multi-head latent attention (MLA), ``omc.mla``.  ``q = h·Wq`` splits per
  head into ``q_nope`` and ``q_pe``; ``[c, k_pe] = h·Wkv_a``, ``c =
  RMSNorm(c)``.  ``k_pe`` is one head, read by all.  Rotary (YaRN
  frequencies, DeepSeek's interleaved pair layout) goes on ``q_pe`` and
  ``k_pe`` only; scores are scaled by ``q_head_dim^-0.5 · m²`` with ``m`` the
  YaRN temperature of ``mscale_all_dim``.  Two paths with one result:
  expanded (training and prefill: ``[k_nope, v] = c·Wkv_b``, then the
  chunked ``attention.attend``) and absorbed (decode: ``W_UK`` folded into
  the query and ``W_UV`` into the output, so a step reads only the latent
  cache, ``attention.LatentCache``).
* Feed-forward: the first ``first_k_dense_replace`` layers a SwiGLU of
  ``intermediate_size``; every later layer the expert layer ``omc.moe``:
  the router (``omc.moe.route``) scores all ``router_experts`` in float32
  and keeps the greedy top ``num_experts_per_tok`` weights as they are
  (times ``routed_scaling_factor``; renormalised only with
  ``norm_topk_prob``); ``omc.moe.experts`` computes the part that the
  ``held_experts`` give, dropping no token (``moe.held_expert_ffn``); the
  shared experts (``omc.moe.shared``) form one SwiGLU of
  ``n_shared_experts · moe_intermediate_size`` that every token passes.

A device that holds a share of each layer's experts (expert parallelism)
computes that share's part of the routed sum; the rest would come from the
devices that hold the other experts.  Dense layers and expert layers each
run as one ``lax.scan`` over their stacked parameters, with the
materializer's per-layer hook.  Field names follow the published
``config.json`` where they mean the same thing.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import attention as attn
from . import moe
from .common import (
    Materializer,
    ParamSpec,
    RSPEC,
    apply_rope_interleaved,
    dense_init,
    embed_init,
    rms_norm,
    scan_blocks,
    softmax_xent_chunked,
    stack_layer_params,
    swiglu,
    wspec,
    yarn_inv_freq,
    yarn_mscale,
)
from .transformer import _embed_lookup

MLA = "omc.mla"
MOE = "omc.moe"


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    vocab_size: int
    intermediate_size: int  # dense layers' SwiGLU
    moe_intermediate_size: int  # one routed expert's SwiGLU
    router_experts: int  # experts the router scores (published n_routed_experts)
    num_experts_per_tok: int
    n_shared_experts: int
    held_experts: Tuple[int, ...]  # global ids of the routed experts held here
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    rope_theta: float = 10_000.0
    rope_scaling: Any = None  # the published YaRN group, or None
    rms_norm_eps: float = 1e-6

    def __post_init__(self):
        held = tuple(int(e) for e in self.held_experts)
        if not held or len(set(held)) != len(held) or not all(
                0 <= e < self.router_experts for e in held):
            raise ValueError(f"held_experts {held} must be distinct ids below "
                             f"router_experts={self.router_experts}")
        object.__setattr__(self, "held_experts", held)
        if isinstance(self.rope_scaling, dict):  # hashable, like every field
            object.__setattr__(self, "rope_scaling", tuple(sorted(self.rope_scaling.items())))

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def shared_width(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    @property
    def yarn(self) -> Dict[str, Any]:
        return dict(self.rope_scaling or ())

    @property
    def inv_freq(self) -> np.ndarray:
        """Rotary inverse frequencies [qk_rope_head_dim / 2]."""
        y, d = self.yarn, self.qk_rope_head_dim
        if not y:
            return (self.rope_theta ** (-np.arange(0, d, 2) / d)).astype(np.float32)
        if y.get("type", "yarn") != "yarn":
            raise NotImplementedError(f"rope scaling {y['type']!r}")
        # cos/sin carry mscale(factor, mscale) / mscale(factor, mscale_all_dim);
        # the published values make it 1
        if yarn_mscale(y["factor"], y["mscale"]) != yarn_mscale(y["factor"], y["mscale_all_dim"]):
            raise NotImplementedError("YaRN cos/sin factor other than 1")
        return yarn_inv_freq(d, self.rope_theta, y["factor"],
                             y["original_max_position_embeddings"],
                             y["beta_fast"], y["beta_slow"])

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.yarn["factor"], self.yarn["mscale_all_dim"]) if self.yarn else 1.0
        return self.q_head_dim ** -0.5 * m * m


# ---------------------------------------------------------------------------
# init / specs
# ---------------------------------------------------------------------------


def _attn_init(ks, cfg: DeepseekV2Config) -> Dict[str, Any]:
    d, h, r = cfg.hidden_size, cfg.num_attention_heads, cfg.kv_lora_rank
    return dict(
        attn_norm=jnp.ones((d,), jnp.float32),
        wq=dense_init(ks[0], d, h * cfg.q_head_dim),
        wkv_a=dense_init(ks[1], d, r + cfg.qk_rope_head_dim),
        kv_norm=jnp.ones((r,), jnp.float32),
        wkv_b=dense_init(ks[2], r, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        wo=dense_init(ks[3], h * cfg.v_head_dim, d),
        mlp_norm=jnp.ones((d,), jnp.float32),
    )


def _dense_init(key, cfg: DeepseekV2Config) -> Dict[str, Any]:
    ks = jax.random.split(key, 7)
    d, f = cfg.hidden_size, cfg.intermediate_size
    return dict(_attn_init(ks, cfg), w1=dense_init(ks[4], d, f),
                w3=dense_init(ks[5], d, f), w2=dense_init(ks[6], f, d))


def _moe_init(key, cfg: DeepseekV2Config) -> Dict[str, Any]:
    ks = jax.random.split(key, 11)
    d, f, fs = cfg.hidden_size, cfg.moe_intermediate_size, cfg.shared_width
    n = len(cfg.held_experts)

    def experts(k, d_in, d_out):
        return jnp.stack([dense_init(kk, d_in, d_out) for kk in jax.random.split(k, n)])

    return dict(
        _attn_init(ks, cfg),
        router=dense_init(ks[4], d, cfg.router_experts),
        w1=experts(ks[5], d, f), w3=experts(ks[6], d, f), w2=experts(ks[7], f, d),
        shared_w1=dense_init(ks[8], d, fs), shared_w3=dense_init(ks[9], d, fs),
        shared_w2=dense_init(ks[10], fs, d),
    )


_ATTN_SPECS = dict(
    attn_norm=RSPEC,
    wq=wspec("fsdp", "tensor"),
    wkv_a=wspec("fsdp", None),
    kv_norm=RSPEC,
    wkv_b=wspec("fsdp", "tensor"),
    wo=wspec("tensor", "fsdp"),
    mlp_norm=RSPEC,
)
DENSE_SPECS = dict(_ATTN_SPECS, w1=wspec("fsdp", "tensor"), w3=wspec("fsdp", "tensor"),
                   w2=wspec("tensor", "fsdp"))
# An expert's matrices are specified without the expert axis, so each
# (layer, expert) gets its own OMC affine: what an expert stores does not
# depend on which experts share its device.
MOE_SPECS = dict(
    _ATTN_SPECS,
    router=wspec("fsdp", None),
    w1=wspec("fsdp", None), w3=wspec("fsdp", None), w2=wspec(None, "fsdp"),
    shared_w1=wspec("fsdp", "tensor"), shared_w3=wspec("fsdp", "tensor"),
    shared_w2=wspec("tensor", "fsdp"),
)


def init(key, cfg: DeepseekV2Config) -> Dict[str, Any]:
    kd, km, ke, kh = jax.random.split(key, 4)
    return dict(
        embed=embed_init(ke, cfg.vocab_size, cfg.hidden_size),
        dense=stack_layer_params([_dense_init(k, cfg) for k in
                                  jax.random.split(kd, cfg.first_k_dense_replace)]),
        blocks=stack_layer_params([_moe_init(k, cfg) for k in
                                   jax.random.split(km, cfg.n_moe_layers)]),
        final_norm=jnp.ones((cfg.hidden_size,), jnp.float32),
        lm_head=dense_init(kh, cfg.hidden_size, cfg.vocab_size),
    )


def param_specs(cfg: DeepseekV2Config) -> Dict[str, Any]:
    return dict(
        embed=ParamSpec(storage=("fsdp", "tensor"), gathered=(None, "tensor")),
        dense=DENSE_SPECS,
        blocks=MOE_SPECS,
        final_norm=RSPEC,
        lm_head=wspec("fsdp", "tensor"),
    )


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------


def mla_inputs(cfg: DeepseekV2Config, w, h, positions):
    """Queries and each position's latent: ``q_nope`` [B,S,H,dn], rotated
    ``q_pe`` [B,S,H,dr], normalised ``c`` [B,S,r], rotated ``k_pe`` [B,S,dr]."""
    b, s, _ = h.shape
    dn, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = (h @ w["wq"]).reshape(b, s, cfg.num_attention_heads, cfg.q_head_dim)
    kv = h @ w["wkv_a"]
    c = rms_norm(kv[..., :r], w["kv_norm"], cfg.rms_norm_eps)
    q_pe = apply_rope_interleaved(q[..., dn:], positions, cfg.inv_freq)
    k_pe = apply_rope_interleaved(kv[..., None, r:], positions, cfg.inv_freq)[..., 0, :]
    return q[..., :dn], q_pe, c, k_pe


def mla_expanded(cfg: DeepseekV2Config, w, q_nope, q_pe, c, k_pe, positions):
    """Causal attention of every position, keys and values rebuilt from the
    latent through ``Wkv_b`` -> the block's output [B, S, D]."""
    b, s, _ = c.shape
    h, dn, dv = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    kv = (c @ w["wkv_b"]).reshape(b, s, h, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe[:, :, None], (b, s, h, cfg.qk_rope_head_dim))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    pos = jnp.broadcast_to(positions, (b, s))
    o = attn.attend(q, k, kv[..., dn:], pos, pos, causal=True, scale=cfg.softmax_scale)
    return o.reshape(b, s, h * dv) @ w["wo"]


def mla_absorbed(cfg: DeepseekV2Config, w, q_nope, q_pe, c, k_pe, c_cache, pe_cache, length):
    """One new position per sequence against the latent cache, whose first
    ``length`` slots hold the earlier positions: ``score = (q_nope·W_UKᵀ)·c
    + q_pe·k_pe``, ``o = (Σ p·c)·W_UV`` -> the block's output [B, 1, D].
    The new position's own latent (``c``, ``k_pe`` [B,1,·]) is attended
    beside the cache, not read back from it."""
    b = c.shape[0]
    h, dn, dv, r = (cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim,
                    cfg.kv_lora_rank)
    wkv_b = w["wkv_b"].reshape(r, h, dn + dv)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], wkv_b[..., :dn])  # W_UK absorbed
    old = (jnp.einsum("bhr,brs->bhs", q_lat, c_cache)
           + jnp.einsum("bhd,bds->bhs", q_pe[:, 0], pe_cache)) * cfg.softmax_scale
    new = (jnp.einsum("bhr,br->bh", q_lat, c[:, 0])
           + jnp.einsum("bhd,bd->bh", q_pe[:, 0], k_pe[:, 0])) * cfg.softmax_scale
    old = jnp.where(jnp.arange(c_cache.shape[-1]) < length, old, attn.NEG_INF)
    p = jax.nn.softmax(jnp.concatenate([old, new[..., None]], -1), axis=-1)
    ctx = jnp.einsum("bhs,brs->bhr", p[..., :-1], c_cache) + p[..., -1:] * c[:, :1]
    o = jnp.einsum("bhr,rhd->bhd", ctx, wkv_b[..., dn:])  # W_UV absorbed
    return o.reshape(b, 1, h * dv) @ w["wo"]


# ---------------------------------------------------------------------------
# feed-forward
# ---------------------------------------------------------------------------


def moe_layer(cfg: DeepseekV2Config, w, h):
    """Held routed experts plus the shared experts: [B, S, D] -> [B, S, D]."""
    b, s, d = h.shape
    x = h.reshape(b * s, d)
    with jax.named_scope(MOE):
        with jax.named_scope("omc.moe.route"):
            gate, ids = moe.route_topk(x, w["router"], cfg.num_experts_per_tok,
                                       normalize=cfg.norm_topk_prob,
                                       scaling=cfg.routed_scaling_factor)
        with jax.named_scope("omc.moe.experts"):
            y = moe.held_expert_ffn(x, gate, ids, w["w1"], w["w3"], w["w2"],
                                    cfg.held_experts, cfg.router_experts)
        with jax.named_scope("omc.moe.shared"):
            y = y + swiglu(x, w["shared_w1"], w["shared_w3"], w["shared_w2"])
    return y.reshape(b, s, d).astype(h.dtype)


def _ffn(cfg: DeepseekV2Config, w, x, dense: bool):
    h = rms_norm(x, w["mlp_norm"], cfg.rms_norm_eps)
    return x + (swiglu(h, w["w1"], w["w3"], w["w2"]) if dense else moe_layer(cfg, w, h))


def _layer(cfg: DeepseekV2Config, w, x, positions, dense: bool):
    """One block over whole sequences -> (x', c, k_pe) of every position."""
    h = rms_norm(x, w["attn_norm"], cfg.rms_norm_eps)
    with jax.named_scope(MLA):
        q_nope, q_pe, c, k_pe = mla_inputs(cfg, w, h, positions)
        x = x + mla_expanded(cfg, w, q_nope, q_pe, c, k_pe, positions)
    return _ffn(cfg, w, x, dense), c, k_pe


def _groups(cfg: DeepseekV2Config, params):
    """(stacked params, specs, dense?, index of the group's first layer)."""
    return ((params["dense"], DENSE_SPECS, True, 0),
            (params["blocks"], MOE_SPECS, False, cfg.first_k_dense_replace))


def _embed(cfg: DeepseekV2Config, params, tokens, mat: Materializer):
    table = mat({"embed": params["embed"]}, {"embed": param_specs(cfg)["embed"]})["embed"]
    return _embed_lookup(table, tokens)


def _logits(cfg: DeepseekV2Config, params, x, mat: Materializer):
    x = rms_norm(x, mat.leaf(params["final_norm"]), cfg.rms_norm_eps)
    return x @ mat({"h": params["lm_head"]}, {"h": wspec("fsdp", "tensor")})["h"]


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------


def forward(cfg: DeepseekV2Config, params, batch, mat: Materializer):
    """Token stream -> final hidden states [B, S, D] (pre-norm, pre-head)."""
    tokens = batch["tokens"]
    x = _embed(cfg, params, tokens, mat)
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
    for stacked, specs, dense, _ in _groups(cfg, params):
        x = scan_blocks(lambda x_, w, _, d=dense: _layer(cfg, w, x_, positions, d)[0],
                        stacked, x, mat, specs)
    return x


def loss(cfg: DeepseekV2Config, params, batch, mat: Materializer) -> jax.Array:
    hidden = rms_norm(forward(cfg, params, batch, mat), mat.leaf(params["final_norm"]),
                      cfg.rms_norm_eps)
    head = mat({"h": params["lm_head"]}, {"h": wspec("fsdp", "tensor")})["h"]
    return softmax_xent_chunked(hidden, head, batch["labels"], batch.get("mask"))


# ---------------------------------------------------------------------------
# serving: prefill + single-token decode against the latent cache
# ---------------------------------------------------------------------------


def init_decode_state(cfg: DeepseekV2Config, batch: int, max_len: int,
                      dtype=jnp.bfloat16) -> attn.LatentCache:
    return attn.init_latent_cache(cfg.num_hidden_layers, batch, max_len, cfg.kv_lora_rank,
                                  cfg.qk_rope_head_dim, dtype)


def prefill(cfg: DeepseekV2Config, params, batch, mat: Materializer,
            cache: attn.LatentCache) -> Tuple[attn.LatentCache, jax.Array]:
    """Run the prompts (one length), write each layer's latents into the
    cache in place, return the logits of the last position [B, 1, V].

    Each layer runs one sequence at a time (``lax.map``): an 8k prompt's
    attention tiles and expert pairs then stay a sequence's worth."""
    tokens = batch["tokens"]
    s = tokens.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)[None]
    carry = (_embed(cfg, params, tokens, mat), cache.c, cache.k_pe)
    for stacked, specs, dense, first in _groups(cfg, params):

        def body(carry, xs, specs=specs, dense=dense, first=first):
            x, c_all, pe_all = carry
            w_layer, i = xs
            w = mat(w_layer, specs)

            def one(xb):
                xb, c, pe = _layer(cfg, w, xb[None], positions, dense)
                return xb[0], c[0], pe[0]

            x, c, pe = jax.lax.map(one, x)
            at = (first + i, 0, 0, 0)
            c_all = jax.lax.dynamic_update_slice(
                c_all, jnp.swapaxes(c, 1, 2)[None].astype(c_all.dtype), at)
            pe_all = jax.lax.dynamic_update_slice(
                pe_all, jnp.swapaxes(pe, 1, 2)[None].astype(pe_all.dtype), at)
            return (x, c_all, pe_all), None

        n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        carry, _ = jax.lax.scan(body, carry, (stacked, jnp.arange(n, dtype=jnp.int32)))
    x, c_all, pe_all = carry
    new = attn.LatentCache(c=c_all, k_pe=pe_all, length=jnp.asarray(s, jnp.int32))
    return new, _logits(cfg, params, x[:, -1:], mat)


def decode_step(cfg: DeepseekV2Config, params, cache: attn.LatentCache,
                tokens: jax.Array, mat: Materializer):
    """One new token [B, 1] at position ``cache.length`` -> (cache', logits
    [B, 1, V]).  The layers only read the cache; the new position's latents
    are written once, after the last layer."""
    b = tokens.shape[0]
    pos = cache.length
    positions = jnp.full((b, 1), pos, jnp.int32)
    x = _embed(cfg, params, tokens, mat)
    new_c, new_pe = [], []
    for stacked, specs, dense, first in _groups(cfg, params):

        def body(x, xs, specs=specs, dense=dense, first=first):
            w_layer, i = xs
            w = mat(w_layer, specs)
            h = rms_norm(x, w["attn_norm"], cfg.rms_norm_eps)
            with jax.named_scope(MLA):
                q_nope, q_pe, c, k_pe = mla_inputs(cfg, w, h, positions)
                layer = first + i
                x = x + mla_absorbed(cfg, w, q_nope, q_pe, c, k_pe, cache.c[layer],
                                     cache.k_pe[layer], pos)
            return _ffn(cfg, w, x, dense), (c, k_pe)

        n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        x, (c, pe) = jax.lax.scan(body, x, (stacked, jnp.arange(n, dtype=jnp.int32)))
        new_c.append(c)
        new_pe.append(pe)
    at = (0, 0, 0, pos)
    c_all = jax.lax.dynamic_update_slice(
        cache.c, jnp.swapaxes(jnp.concatenate(new_c), 2, 3).astype(cache.c.dtype), at)
    pe_all = jax.lax.dynamic_update_slice(
        cache.k_pe, jnp.swapaxes(jnp.concatenate(new_pe), 2, 3).astype(cache.k_pe.dtype), at)
    new = attn.LatentCache(c=c_all, k_pe=pe_all, length=pos + 1)
    return new, _logits(cfg, params, x, mat)
