"""Mixture-of-Experts decoder LM (mixtral-8x7b, dbrx-132b).

Expert parallelism (DESIGN.md §4): activations are replicated over the
`model` axis after attention (standard 2-D TP+DP layout), so MoE dispatch
needs *no* all-to-all — each model shard locally gathers the tokens routed to
the experts it owns (capacity-bounded, gate-priority), runs the expert FFN,
and scatter-adds its contribution; a single psum over `model` combines, which
is the same collective a dense row-parallel FFN already pays.

Expert-to-mesh mapping:
  * E >= model-axis (dbrx 16e on 16): each shard owns E/M experts.
  * E <  model-axis (mixtral 8e on 16): each expert is co-owned by M/E
    shards which split the FFN hidden dim (`ep_partitions`); both owners
    process the same tokens and their partial outputs merge in the psum.
    Expert weights are *stored* in the flattened [E*parts, D, F/parts]
    layout so they are expert-sharded at rest (checkpoints keep the
    canonical [E, D, F] layout — see repro.checkpoint).

When no mesh is active (CPU smoke tests) the dispatch runs as a pure-jnp
single-device reference with identical semantics; a property test asserts the
shard_map path matches it on a multi-device host mesh.
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
    current_mesh,
    dense_init,
    embed_init,
    rms_norm,
    scan_blocks,
    shard_hint,
    softmax_xent_chunked,
    stack_layer_params,
    wspec,
)
from .transformer import (TransformerConfig, _embed_lookup, _head_weight, _qkv,
                          param_specs as _dense_param_specs)


@dataclasses.dataclass(frozen=True)
class MoEConfig(TransformerConfig):
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    ep_partitions: int = 1  # FFN-dim split when E < model axis (set by launch)
    router_aux_weight: float = 0.01
    router_z_weight: float = 1e-3

    @property
    def stored_experts(self) -> int:
        return self.n_experts * self.ep_partitions

    @property
    def f_local(self) -> int:
        return self.d_ff // self.ep_partitions

    def param_count(self) -> int:
        d, f, v = self.d_model, self.d_ff, self.vocab
        per_layer = (
            d * (self.q_dim + 2 * self.kv_dim) + self.q_dim * d
            + 3 * d * f * self.n_experts + d * self.n_experts + 2 * d
        )
        emb = v * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb + d

    def active_param_count(self) -> int:
        d, f, v = self.d_model, self.d_ff, self.vocab
        per_layer = (
            d * (self.q_dim + 2 * self.kv_dim) + self.q_dim * d
            + 3 * d * f * self.top_k + d * self.n_experts + 2 * d
        )
        emb = v * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb + d


# ---------------------------------------------------------------------------
# init / specs
# ---------------------------------------------------------------------------


def _block_init(key, cfg: MoEConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    d, fl, we = cfg.d_model, cfg.f_local, cfg.stored_experts

    def expert_stack(k, d_in, d_out):
        return jnp.stack(
            [dense_init(kk, d_in, d_out) for kk in jax.random.split(k, we)], 0
        )

    return dict(
        attn_norm=jnp.ones((d,), jnp.float32),
        wq=dense_init(ks[0], d, cfg.q_dim),
        wk=dense_init(ks[1], d, cfg.kv_dim),
        wv=dense_init(ks[2], d, cfg.kv_dim),
        wo=dense_init(ks[3], cfg.q_dim, d),
        mlp_norm=jnp.ones((d,), jnp.float32),
        router=dense_init(ks[4], d, cfg.n_experts),
        w1=expert_stack(ks[5], d, fl),
        w3=expert_stack(ks[6], d, fl),
        w2=expert_stack(ks[7], fl, d),
    )


def block_specs(cfg: MoEConfig) -> Dict[str, ParamSpec]:
    return dict(
        attn_norm=RSPEC,
        wq=wspec("fsdp", "tensor"),
        wk=wspec("fsdp", "tensor"),
        wv=wspec("fsdp", "tensor"),
        wo=wspec("tensor", "fsdp"),
        mlp_norm=RSPEC,
        router=wspec("fsdp", None),
        w1=wspec("expert", "fsdp", None),
        w3=wspec("expert", "fsdp", None),
        w2=wspec("expert", "fsdp", None),
    )


def init(key, cfg: MoEConfig) -> Dict[str, Any]:
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


def param_specs(cfg: MoEConfig) -> Dict[str, Any]:
    specs = _dense_param_specs(cfg)
    specs["blocks"] = block_specs(cfg)
    return specs


# ---------------------------------------------------------------------------
# MoE FFN — routing + capacity dispatch
# ---------------------------------------------------------------------------


def _route(x2d: jax.Array, router_w: jax.Array, cfg: MoEConfig):
    """[T, D] -> (gate values [T,k], expert ids [T,k], aux losses)."""
    logits = (x2d @ router_w).astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gval, gidx = jax.lax.top_k(probs, cfg.top_k)
    gval = gval / jnp.maximum(gval.sum(-1, keepdims=True), 1e-9)
    # load-balance aux (Switch-style): E * sum_e fraction_e * prob_e
    dispatch_frac = jnp.mean(
        jax.nn.one_hot(gidx[:, 0], cfg.n_experts, dtype=jnp.float32), axis=0
    )
    prob_frac = jnp.mean(probs, axis=0)
    aux = cfg.n_experts * jnp.sum(dispatch_frac * prob_frac)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return gval, gidx, cfg.router_aux_weight * aux + cfg.router_z_weight * z


def _expert_ffn(xe: jax.Array, w1e, w3e, w2e) -> jax.Array:
    """[C, D] @ expert weights -> [C, D] (SwiGLU)."""
    return (jax.nn.silu(xe @ w1e) * (xe @ w3e)) @ w2e


def _dispatch_compute(x2d, gval, gidx, w1, w3, w2, cfg: MoEConfig,
                      local_experts, capacity: int):
    """Gather-compute-scatter for a set of locally-owned experts.

    x2d [T, D]; w1/w3/w2 [n_local, D, F_l] / [n_local, F_l, D];
    local_experts: int32 [n_local] global expert ids.  Returns partial y [T, D]
    (contributions of the local experts only).
    """
    t = x2d.shape[0]
    flat_gv = gval.reshape(-1)  # [T*k]
    flat_eid = gidx.reshape(-1)  # [T*k]
    token_of_pair = jnp.arange(flat_eid.shape[0], dtype=jnp.int32) // cfg.top_k

    def one_expert(y, inputs):
        e, w1e, w3e, w2e = inputs
        score = jnp.where(flat_eid == e, flat_gv, -1.0)
        top_v, top_i = jax.lax.top_k(score, capacity)
        valid = (top_v > 0.0).astype(jnp.float32)  # dropped / unrouted slots
        tok = token_of_pair[top_i]
        xe = x2d[tok] * valid[:, None]
        he = _expert_ffn(xe, w1e, w3e, w2e)
        contrib = he * (top_v * valid)[:, None]
        return y.at[tok].add(contrib, mode="drop"), None

    y0 = jnp.zeros((t, x2d.shape[1]), jnp.float32)
    y, _ = jax.lax.scan(one_expert, y0, (local_experts, w1, w3, w2))
    return y


def moe_ffn(x: jax.Array, w: Dict[str, jax.Array], cfg: MoEConfig):
    """[B, S, D] -> ([B, S, D], aux_loss).  w holds router/w1/w3/w2 (f32)."""
    b, s, d = x.shape
    mesh = current_mesh()
    t = b * s

    if mesh is None or "model" not in mesh.axis_names or cfg.ep_partitions == 0:
        # Single-device reference path.
        x2d = x.reshape(t, d).astype(jnp.float32)
        gval, gidx, aux = _route(x2d, w["router"], cfg)
        cap = _capacity(t, cfg)
        y = _dispatch_compute(
            x2d, gval, gidx, w["w1"], w["w3"], w["w2"], cfg,
            jnp.repeat(jnp.arange(cfg.n_experts, dtype=jnp.int32), cfg.ep_partitions)
            if cfg.ep_partitions > 1 else jnp.arange(cfg.n_experts, dtype=jnp.int32),
            cap,
        )
        return y.reshape(b, s, d).astype(x.dtype), aux

    from jax.sharding import PartitionSpec as P
    from .common import resolve_spec

    m = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    batch_spec = resolve_spec(["batch"], [b], mesh)[0]  # axes or None
    b_shards = int(np.prod([dict(zip(mesh.axis_names, mesh.devices.shape))[a]
                            for a in (batch_spec if isinstance(batch_spec, tuple)
                                      else ((batch_spec,) if batch_spec else ()))]))
    t_local = (b // max(b_shards, 1)) * s
    cap = _capacity(t_local, cfg)
    we = cfg.stored_experts
    if we % m == 0:
        n_local = we // m
    else:
        raise ValueError(
            f"stored_experts={we} not divisible by model axis {m}; "
            f"set ep_partitions so that n_experts*ep_partitions % model == 0"
        )

    def shard_fn(x_l, router_w, w1_l, w3_l, w2_l):
        bl, sl, dl = x_l.shape
        x2d = x_l.reshape(bl * sl, dl).astype(jnp.float32)
        gval, gidx, aux = _route(x2d, router_w, cfg)
        midx = jax.lax.axis_index("model")
        # stored-expert rows owned by this shard -> global expert ids
        rows = midx * n_local + jnp.arange(n_local, dtype=jnp.int32)
        local_eids = rows // cfg.ep_partitions
        y = _dispatch_compute(x2d, gval, gidx, w1_l, w3_l, w2_l, cfg,
                              local_eids, cap)
        y = jax.lax.psum(y, "model")
        aux = jax.lax.pmean(aux, "model")
        return y.reshape(bl, sl, dl), aux

    xspec = P(batch_spec, None, None)
    wspec_ = P("model", None, None)
    y, aux = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(xspec, P(None, None), wspec_, wspec_, wspec_),
        out_specs=(xspec, P()),
        check_vma=False,
    )(x, w["router"], w["w1"], w["w3"], w["w2"])
    return y.astype(x.dtype), aux


def _capacity(tokens: int, cfg: MoEConfig) -> int:
    c = int(np.ceil(tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    c = max(8, -(-c // 8) * 8)  # pad to multiple of 8, floor 8
    return min(c, tokens * cfg.top_k)  # can't exceed the pair count


# ---------------------------------------------------------------------------
# Held-expert layer: route over every expert, compute the ones held here,
# drop no token (DeepSeek-style serving MoE; DESIGN.md §16)
# ---------------------------------------------------------------------------


def route_topk(x2d: jax.Array, router_w: jax.Array, top_k: int, *,
               normalize: bool, scaling: float = 1.0):
    """[T, D] -> (gate weights [T, k], expert ids [T, k]).

    Softmax over all the router's experts in float32 (the router matmul at
    full precision), greedy top-k; the k weights are renormalised to sum to 1
    only with ``normalize``, then multiplied by ``scaling``."""
    logits = jnp.dot(x2d.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    gate, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if normalize:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-20)
    return gate * scaling, ids


def held_expert_ffn(x2d: jax.Array, gate: jax.Array, ids: jax.Array,
                    w1: jax.Array, w3: jax.Array, w2: jax.Array,
                    held, n_experts: int) -> jax.Array:
    """This device's part of the routed experts' output: for every token,
    ``Σ gate_e · SwiGLU_e(x)`` over its routed experts ``e`` that are held
    here; experts held elsewhere add nothing.

    x2d [T, D]; gate/ids [T, k] over all ``n_experts``; w1/w3 [H, D, F],
    w2 [H, F, D] for the ``held`` global expert ids, in that order.  No
    capacity and no drop: the T·k (token, expert) pairs are sorted by held
    expert (pairs of experts held elsewhere last) and run as one grouped
    matmul per weight (``lax.ragged_dot``), whose groups are the held
    experts' pair counts."""
    t, d = x2d.shape
    k = ids.shape[1]
    n_held = len(held)
    local = np.full(n_experts, n_held, np.int32)  # global id -> slot; n_held: not here
    local[np.asarray(held)] = np.arange(n_held, dtype=np.int32)
    slot = jnp.asarray(local)[ids].reshape(-1)  # [T*k]
    order = jnp.argsort(slot, stable=True)
    sizes = jnp.zeros((n_held + 1,), jnp.int32).at[slot].add(1)[:n_held]
    xs = x2d[order // k]
    h = jax.nn.silu(jax.lax.ragged_dot(xs, w1, sizes, preferred_element_type=jnp.float32))
    h = h * jax.lax.ragged_dot(xs, w3, sizes, preferred_element_type=jnp.float32)
    out = jax.lax.ragged_dot(h, w2, sizes, preferred_element_type=jnp.float32)
    # rows past the held experts' groups belong to no group: zero them
    out = jnp.where((slot[order] < n_held)[:, None], out, 0.0)
    out = out[jnp.argsort(order)].reshape(t, k, d)  # back to (token, choice)
    weight = jnp.where(slot.reshape(t, k) < n_held, gate, 0.0)
    return (weight[..., None] * out).sum(1)


# ---------------------------------------------------------------------------
# forward / loss / serve
# ---------------------------------------------------------------------------


def _block_apply(cfg: MoEConfig, w, x, aux, positions, window):
    b, s, d = x.shape
    h = rms_norm(x, w["attn_norm"], cfg.norm_eps)
    q, k, v = _qkv(w, h, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    o = attn.attend(q, k, v, positions, positions, causal=True, window=window)
    o = o.reshape(b, s, cfg.q_dim)
    x = x + shard_hint(o @ w["wo"], "batch", None, None)
    h = rms_norm(x, w["mlp_norm"], cfg.norm_eps)
    y, aux_l = moe_ffn(h, w, cfg)
    return x + y, aux + aux_l


def forward(cfg: MoEConfig, params, batch, mat: Materializer):
    tokens = batch["tokens"]
    b = tokens.shape[0]
    emb_w = mat({"embed": params["embed"]}, {"embed": param_specs(cfg)["embed"]})
    x = _embed_lookup(emb_w["embed"], tokens)
    x = shard_hint(x, "batch", None, None)
    s = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    specs = block_specs(cfg)

    def body(carry, w, _):
        x_, aux = carry
        return _block_apply(cfg, w, x_, aux, positions, cfg.window)

    x, aux = scan_blocks(body, params["blocks"], (x, jnp.float32(0.0)), mat, specs)
    return rms_norm(x, mat.leaf(params["final_norm"]), cfg.norm_eps), aux


def loss(cfg: MoEConfig, params, batch, mat: Materializer) -> jax.Array:
    hidden, aux = forward(cfg, params, batch, mat)
    ce = softmax_xent_chunked(hidden, _head_weight(cfg, params, mat), batch["labels"],
                              batch.get("mask"))
    return ce + aux / cfg.n_layers


def init_decode_state(cfg: MoEConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    buf = max_len if cfg.window is None else min(max_len, cfg.window)
    return attn.init_cache(cfg.n_layers, batch, buf, cfg.n_kv_heads, cfg.hd, dtype)


def prefill(cfg: MoEConfig, params, batch, mat: Materializer, cache):
    x = _embed_lookup(
        mat({"embed": params["embed"]}, {"embed": param_specs(cfg)["embed"]})["embed"],
        batch["tokens"],
    )
    x = shard_hint(x, "batch", None, None)
    b, s = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    specs = block_specs(cfg)
    buf = cache.buf_len

    def body_fn(carry, xs):
        x_, aux = carry
        w = mat(xs[0], specs)
        h = rms_norm(x_, w["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(w, h, cfg)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        o = attn.attend(q, k, v, positions, positions, causal=True, window=cfg.window)
        o = o.reshape(b, s, cfg.q_dim)
        x_ = x_ + shard_hint(o @ w["wo"], "batch", None, None)
        h = rms_norm(x_, w["mlp_norm"], cfg.norm_eps)
        y, aux_l = moe_ffn(h, w, cfg)
        x_ = x_ + y
        t = min(buf, s)
        kc, vc, pc = k[:, -t:], v[:, -t:], positions[:, -t:]
        if t < buf:
            pad = buf - t
            kc = jnp.pad(kc, ((0, 0), (0, pad), (0, 0), (0, 0)))
            vc = jnp.pad(vc, ((0, 0), (0, pad), (0, 0), (0, 0)))
            pc = jnp.pad(pc, ((0, 0), (0, pad)), constant_values=-1)
        return (x_, aux + aux_l), (kc.astype(cache.k.dtype), vc.astype(cache.v.dtype), pc)

    body_fn = jax.checkpoint(body_fn, prevent_cse=False)
    (x, _aux), (ks, vs, ps) = jax.lax.scan(
        body_fn, (x, jnp.float32(0.0)), (params["blocks"], None)
    )
    if cfg.window is not None and s >= buf:
        roll = s % buf
        ks, vs, ps = (jnp.roll(a, roll, axis=2) for a in (ks, vs, ps))
    new_cache = attn.cache_shard_hint(
        attn.KVCache(k=ks, v=vs, pos=ps, length=jnp.asarray(s, jnp.int32))
    )
    x = rms_norm(x, mat.leaf(params["final_norm"]), cfg.norm_eps)
    logits = x[:, -1:] @ _head_weight(cfg, params, mat)
    return new_cache, shard_hint(logits, "batch", None, "tensor")


def decode_step(cfg: MoEConfig, params, cache, tokens, mat: Materializer):
    b = tokens.shape[0]
    x = _embed_lookup(
        mat({"embed": params["embed"]}, {"embed": param_specs(cfg)["embed"]})["embed"],
        tokens,
    )
    x = shard_hint(x, "batch", None, None)
    position = cache.length
    positions = jnp.full((b, 1), position, jnp.int32)
    specs = block_specs(cfg)
    ring = cfg.window is not None

    def body(carry, xs):  # the cache read only, written after (transformer.decode_step)
        x_, aux = carry
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
        y, aux_l = moe_ffn(h, w, cfg)
        return (x_ + y, aux + aux_l), (k, v)

    (x, _aux), (ks, vs) = jax.lax.scan(
        body, (x, jnp.float32(0.0)), (params["blocks"], (cache.k, cache.v, cache.pos))
    )
    c = attn.cache_write(cache, ks, vs, position, ring)
    new_cache = attn.cache_shard_hint(c._replace(length=cache.length + 1))
    x = rms_norm(x, mat.leaf(params["final_norm"]), cfg.norm_eps)
    logits = x @ _head_weight(cfg, params, mat)
    return new_cache, shard_hint(logits, "batch", None, "tensor")
