"""Dense decoder-only transformer LM with GQA (llama/qwen/mistral family).

Covers the assigned dense archs (qwen2.5-3b, qwen1.5-110b, mistral-nemo-12b,
h2o-danube-3-4b) and serves as the LM backbone for the VLM (internvl2-1b).

Sharding (DESIGN.md §4):
  * QKV / MLP-in projections: column-parallel (out dim -> `tensor`).
  * Attention-out / MLP-out: row-parallel (in dim -> `tensor`, psum on out).
  * Attention core: q-block dim -> `model` (Ulysses-style; see attention.py).
  * Weight storage: every matrix additionally sharded over `fsdp`; the
    materializer's gather hint removes the fsdp axis per layer under remat.
  * Embedding: vocab -> `tensor`, d -> `fsdp`; lookup on the vocab-sharded
    table (SPMD lowers to masked local gathers + all-reduce).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import attention as attn
from .common import (
    Materializer,
    ParamSpec,
    RSPEC,
    apply_rope,
    dense_init,
    embed_init,
    rms_norm,
    scan_blocks,
    shard_hint,
    softmax_xent_chunked,
    stack_layer_params,
    swiglu,
    wspec,
)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None  # defaults to d_model // n_heads
    qkv_bias: bool = False  # qwen family
    window: Optional[int] = None  # sliding-window attention (mistral family)
    swa_every: int = 1  # 1 = every layer windowed; n>1: 1 in n full attention
    rope_theta: float = 10_000.0
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    # Frontend stubs (vlm/audio): number of pre-embedded positions prepended
    # to the token stream; their embeddings arrive via batch["patches"].
    prefix_embeds: int = 0
    # §Perf: store residual-stream activations sequence-sharded over `model`
    # (Megatron-SP).  Remat-boundary activations shrink by the TP degree and
    # the per-layer TP all-reduces become reduce-scatter + all-gather pairs
    # (half the wire bytes).  Off by default (paper-faithful baseline).
    sp_residuals: bool = False

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.hd

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.hd

    def layer_window(self, layer_idx: int) -> Optional[int]:
        if self.window is None:
            return None
        if self.swa_every <= 1:
            return self.window
        return None if (layer_idx % self.swa_every == self.swa_every - 1) else self.window

    @property
    def uniform_window(self) -> Optional[int]:
        """Window if identical across layers (lets blocks share one scan)."""
        ws = {self.layer_window(i) for i in range(self.n_layers)}
        return None if len(ws) > 1 else next(iter(ws))

    def param_count(self) -> int:
        d, f, v = self.d_model, self.d_ff, self.vocab
        per_layer = d * (self.q_dim + 2 * self.kv_dim) + self.q_dim * d + 3 * d * f + 2 * d
        if self.qkv_bias:
            per_layer += self.q_dim + 2 * self.kv_dim
        emb = v * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb + d


# ---------------------------------------------------------------------------
# init / specs
# ---------------------------------------------------------------------------


def _block_init(key, cfg: TransformerConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, 6)
    d, f = cfg.d_model, cfg.d_ff
    p = dict(
        attn_norm=jnp.ones((d,), jnp.float32),
        wq=dense_init(ks[0], d, cfg.q_dim),
        wk=dense_init(ks[1], d, cfg.kv_dim),
        wv=dense_init(ks[2], d, cfg.kv_dim),
        wo=dense_init(ks[3], cfg.q_dim, d),
        mlp_norm=jnp.ones((d,), jnp.float32),
        w1=dense_init(ks[4], d, f),
        w3=dense_init(ks[5], d, f),
        w2=dense_init(ks[4], f, d),
    )
    if cfg.qkv_bias:
        p.update(
            bq=jnp.zeros((cfg.q_dim,), jnp.float32),
            bk=jnp.zeros((cfg.kv_dim,), jnp.float32),
            bv=jnp.zeros((cfg.kv_dim,), jnp.float32),
        )
    return p


def block_specs(cfg: TransformerConfig) -> Dict[str, ParamSpec]:
    s = dict(
        attn_norm=RSPEC,
        wq=wspec("fsdp", "tensor"),
        wk=wspec("fsdp", "tensor"),
        wv=wspec("fsdp", "tensor"),
        wo=wspec("tensor", "fsdp"),
        mlp_norm=RSPEC,
        w1=wspec("fsdp", "tensor"),
        w3=wspec("fsdp", "tensor"),
        w2=wspec("tensor", "fsdp"),
    )
    if cfg.qkv_bias:
        s.update(bq=wspec("tensor"), bk=wspec("tensor"), bv=wspec("tensor"))
    return s


def init(key, cfg: TransformerConfig) -> Dict[str, Any]:
    kb, ke, kh = jax.random.split(key, 3)
    blocks = stack_layer_params(
        [_block_init(k, cfg) for k in jax.random.split(kb, cfg.n_layers)]
    )
    params = dict(
        embed=embed_init(ke, cfg.vocab, cfg.d_model),
        blocks=blocks,
        final_norm=jnp.ones((cfg.d_model,), jnp.float32),
    )
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(kh, cfg.d_model, cfg.vocab)
    return params


def param_specs(cfg: TransformerConfig) -> Dict[str, Any]:
    specs = dict(
        embed=ParamSpec(storage=("fsdp", "tensor"), gathered=(None, "tensor")),
        blocks=block_specs(cfg),
        final_norm=RSPEC,
    )
    if not cfg.tie_embeddings:
        specs["lm_head"] = wspec("fsdp", "tensor")
    return specs


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _embed_lookup(table: jax.Array, tokens: jax.Array) -> jax.Array:
    """Row lookup on a (possibly vocab-sharded) table."""
    return jnp.take(table, tokens, axis=0)


def _qkv(w, x, cfg: TransformerConfig):
    b, s, _ = x.shape
    q = x @ w["wq"] + (w["bq"] if "bq" in w else 0.0)
    k = x @ w["wk"] + (w["bk"] if "bk" in w else 0.0)
    v = x @ w["wv"] + (w["bv"] if "bv" in w else 0.0)
    q = shard_hint(q, "batch", None, "tensor").reshape(b, s, cfg.n_heads, cfg.hd)
    k = shard_hint(k, "batch", None, "tensor").reshape(b, s, cfg.n_kv_heads, cfg.hd)
    v = shard_hint(v, "batch", None, "tensor").reshape(b, s, cfg.n_kv_heads, cfg.hd)
    return q, k, v


def _res_hint(x, cfg):
    seq = "seq" if (cfg.sp_residuals and x.shape[1] > 1) else None
    return shard_hint(x, "batch", seq, None)


def _block_apply(cfg: TransformerConfig, w, x, positions, window):
    """One decoder block (pre-norm GQA attention + SwiGLU MLP)."""
    b, s, d = x.shape
    h = rms_norm(x, w["attn_norm"], cfg.norm_eps)
    q, k, v = _qkv(w, h, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    o = attn.attend(q, k, v, positions, positions, causal=True, window=window)
    o = o.reshape(b, s, cfg.q_dim)
    x = _res_hint(x + o @ w["wo"], cfg)
    h = rms_norm(x, w["mlp_norm"], cfg.norm_eps)
    x = _res_hint(x + swiglu(h, w["w1"], w["w3"], w["w2"]), cfg)
    return x


def _input_embeds(cfg: TransformerConfig, params, batch, mat: Materializer):
    """Token (+ optional modality-prefix) embeddings -> (x [B,S,D], positions)."""
    tokens = batch["tokens"]
    b = tokens.shape[0]
    emb_w = mat({"embed": params["embed"]}, {"embed": param_specs(cfg)["embed"]})
    x = _embed_lookup(emb_w["embed"], tokens)
    if cfg.prefix_embeds:
        # Modality frontend stub: precomputed patch/frame embeddings.
        x = jnp.concatenate([batch["patches"].astype(x.dtype), x], axis=1)
    x = _res_hint(x, cfg)
    s = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return x, positions


def forward(cfg: TransformerConfig, params, batch, mat: Materializer):
    """Token stream -> final hidden states [B, S, D] (pre-head)."""
    x, positions = _input_embeds(cfg, params, batch, mat)
    window = cfg.uniform_window
    specs = block_specs(cfg)
    if window is not None or cfg.window is None:
        # Homogeneous layers: one scan over the stacked block params.
        def body(carry, w, _):
            return _block_apply(cfg, w, carry, positions, window)

        x = scan_blocks(body, params["blocks"], x, mat, specs)
    else:
        # Mixed SWA/full layers: per-layer window, unrolled (rare path; the
        # assigned SWA archs use a uniform window so the scan path is taken).
        for i in range(cfg.n_layers):
            w_i = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])

            def body1(x_, w=w_i, win=cfg.layer_window(i)):
                return _block_apply(cfg, mat(w, specs), x_, positions, win)

            x = jax.checkpoint(body1)(x)
    return rms_norm(x, mat.leaf(params["final_norm"]), cfg.norm_eps)


def _head_weight(cfg: TransformerConfig, params, mat):
    if cfg.tie_embeddings:
        emb = mat({"e": params["embed"]}, {"e": ParamSpec(("fsdp", "tensor"), ("tensor", None))})["e"]
        return emb.T
    return mat({"h": params["lm_head"]}, {"h": wspec("fsdp", "tensor")})["h"]


def loss(cfg: TransformerConfig, params, batch, mat: Materializer) -> jax.Array:
    hidden = forward(cfg, params, batch, mat)
    labels = batch["labels"]
    if cfg.prefix_embeds:
        # Prefix positions carry no next-token target.
        pad = jnp.zeros((labels.shape[0], cfg.prefix_embeds), labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
        mask = jnp.concatenate(
            [jnp.zeros((labels.shape[0], cfg.prefix_embeds), jnp.float32),
             batch.get("mask", jnp.ones_like(batch["labels"], jnp.float32))],
            axis=1,
        )
    else:
        mask = batch.get("mask")
    return softmax_xent_chunked(hidden, _head_weight(cfg, params, mat), labels, mask)


# ---------------------------------------------------------------------------
# serving: prefill + single-token decode against a KV cache
# ---------------------------------------------------------------------------


def init_decode_state(cfg: TransformerConfig, batch: int, max_len: int,
                      dtype=jnp.bfloat16) -> attn.KVCache:
    buf = max_len if cfg.window is None else min(max_len, cfg.window)
    return attn.init_cache(cfg.n_layers, batch, buf, cfg.n_kv_heads, cfg.hd, dtype)


def prefill(cfg: TransformerConfig, params, batch, mat: Materializer,
            cache: attn.KVCache) -> Tuple[attn.KVCache, jax.Array]:
    """Run the prompt, fill the cache, return logits of the last position."""
    x, positions = _input_embeds(cfg, params, batch, mat)
    b, s = positions.shape
    specs = block_specs(cfg)
    window = cfg.uniform_window
    buf = cache.buf_len

    def body(carry, w, _):
        x_ = carry
        h = rms_norm(x_, w["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(w, h, cfg)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        o = attn.attend(q, k, v, positions, positions, causal=True, window=window)
        o = o.reshape(b, s, cfg.q_dim)
        x_ = x_ + shard_hint(o @ w["wo"], "batch", None, None)
        h = rms_norm(x_, w["mlp_norm"], cfg.norm_eps)
        x_ = x_ + swiglu(h, w["w1"], w["w3"], w["w2"])
        # cache tail: last `buf` positions of k/v (ring layout: slot = pos % buf)
        t = min(buf, s)
        kc, vc, pc = k[:, -t:], v[:, -t:], positions[:, -t:]
        if t < buf:  # prompt shorter than the buffer: left-pad empty slots
            pad = buf - t
            kc = jnp.pad(kc, ((0, 0), (0, pad), (0, 0), (0, 0)))
            vc = jnp.pad(vc, ((0, 0), (0, pad), (0, 0), (0, 0)))
            pc = jnp.pad(pc, ((0, 0), (0, pad)), constant_values=-1)
        return x_, (kc.astype(cache.k.dtype), vc.astype(cache.v.dtype), pc)

    def body_fn(carry, xs):
        w_layer, _ = xs
        w = mat(w_layer, specs)
        return body(carry, w, None)

    body_fn = jax.checkpoint(body_fn, prevent_cse=False)
    x, (ks, vs, ps) = jax.lax.scan(body_fn, x, (params["blocks"], None))
    if cfg.window is not None and s >= buf:
        # ring layout: rotate so that slot index == pos % buf
        roll = s % buf
        ks = jnp.roll(ks, roll, axis=2)
        vs = jnp.roll(vs, roll, axis=2)
        ps = jnp.roll(ps, roll, axis=2)
    new_cache = attn.KVCache(
        k=ks, v=vs, pos=ps, length=jnp.asarray(s, jnp.int32)
    )
    new_cache = attn.cache_shard_hint(new_cache)
    x = rms_norm(x, mat.leaf(params["final_norm"]), cfg.norm_eps)
    logits = x[:, -1:] @ _head_weight(cfg, params, mat)
    return new_cache, shard_hint(logits, "batch", None, "tensor")


def decode_step(cfg: TransformerConfig, params, cache: attn.KVCache,
                tokens: jax.Array, mat: Materializer):
    """One new token [B, 1] against the cache -> (cache', logits [B,1,V])."""
    b = tokens.shape[0]
    emb_w = mat({"embed": params["embed"]}, {"embed": param_specs(cfg)["embed"]})
    x = _embed_lookup(emb_w["embed"], tokens)
    x = shard_hint(x, "batch", None, None)
    position = cache.length  # scalar int32
    positions = jnp.full((b, 1), position, jnp.int32)
    specs = block_specs(cfg)
    ring = cfg.window is not None

    # the layers only read the cache; each attends its new token beside it,
    # and the tokens are written once after the last layer, so a donated
    # cache changes in place and no layer's slice is copied
    def body(x_, xs):
        w_layer, (kc, vc, pc) = xs
        w = mat(w_layer, specs)
        h = rms_norm(x_, w["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(w, h, cfg)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        o = attn.decode_attend(q, kc, vc, pc, position, window=cfg.window, k_new=k, v_new=v)
        o = o.reshape(b, 1, cfg.q_dim)
        x_ = x_ + shard_hint(o @ w["wo"], "batch", None, None)
        h = rms_norm(x_, w["mlp_norm"], cfg.norm_eps)
        x_ = x_ + swiglu(h, w["w1"], w["w3"], w["w2"])
        return x_, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], (cache.k, cache.v, cache.pos)))
    c = attn.cache_write(cache, ks, vs, position, ring)
    new_cache = attn.cache_shard_hint(c._replace(length=cache.length + 1))
    x = rms_norm(x, mat.leaf(params["final_norm"]), cfg.norm_eps)
    logits = x @ _head_weight(cfg, params, mat)
    return new_cache, shard_hint(logits, "batch", None, "tensor")
