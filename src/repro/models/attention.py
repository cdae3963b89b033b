"""Grouped-query attention with flash-style chunking, SWA, and a KV cache.

TPU adaptation notes (DESIGN.md §2):
  * Train/prefill attention is double-chunked (outer scan over Q blocks,
    inner scan over KV blocks with an online softmax) so the score transient
    is a bounded [B, q_blk, H, kv_blk] tile — never the full S x S matrix.
    This is the memory behaviour a fused TPU flash kernel gives; expressing
    it as jnp + lax.scan lets XLA keep it in registers/VMEM-sized chunks and
    keeps the dry-run memory analysis honest at 32k/500k sequence lengths.
  * GQA is computed grouped (q reshaped to [B, S, KVH, G, hd]) instead of
    repeating KV heads — no materialized KV repeat.
  * Sliding-window attention (mistral/danube/mixtral) is a positional mask;
    the decode cache for SWA archs is a ring buffer of width W, which is what
    bounds the ``long_500k`` working set.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .common import current_mesh, shard_hint

NEG_INF = -1e30


def _qblk_axis_size() -> int:
    """Size of the mesh axis the q-block dim shards over (1 if no mesh)."""
    mesh = current_mesh()
    if mesh is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("model", 1)


def _pick_q_block(sq: int, target: int, m: int) -> int:
    """q_block such that nq = sq/q_block is a multiple of the model axis.

    Without this, head-count-agnostic sequence sharding silently drops
    (e.g. nq=8 on a 16-way axis) and the attention core replicates over
    `model` — 16x wasted compute.
    """
    if m > 1 and sq % m == 0:
        # candidate nq values: multiples of m closest to sq/target
        want_nq = max(1, round(sq / max(target, 1)))
        nq = max(m, ((want_nq + m - 1) // m) * m)
        while sq % nq and nq > m:
            nq -= m
        if sq % nq == 0:
            return sq // nq
    q_block = min(target, sq)
    while sq % q_block:
        q_block -= 1
    return q_block


def _mask_bias(q_pos, k_pos, *, causal: bool, window: Optional[int], k_valid=None):
    """[.., Sq, Sk] additive bias from positional visibility rules."""
    ok = jnp.ones(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]), bool)
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    if causal:
        ok = ok & (kp <= qp)
    if window is not None:
        ok = ok & (kp > qp - window)
    if k_valid is not None:
        ok = ok & k_valid[..., None, :]
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def attend(
    q: jax.Array,  # [B, Sq, H, hd]
    k: jax.Array,  # [B, Sk, KVH, hd]
    v: jax.Array,  # [B, Sk, KVH, hd_v]
    q_pos: jax.Array,  # [B, Sq] int32 absolute positions
    k_pos: jax.Array,  # [B, Sk]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    k_valid: Optional[jax.Array] = None,  # [B, Sk] bool
    q_block: int = 512,
    kv_block: int = 512,
    scale: Optional[float] = None,
) -> jax.Array:
    """Online-softmax chunked attention. Returns [B, Sq, H, hd_v] (q dtype).

    Scores are scaled by ``scale``, ``1/sqrt(hd)`` by default; the value head
    size ``hd_v`` may differ from the query/key size ``hd`` (latent attention:
    192 against 128).

    Layout: the q-block index is a *tensor dimension* sharded over the model
    axis (Ulysses-style sequence parallelism) — q blocks are independent given
    the KV stream, so this gives the attention core model-parallelism that
    works for any (H, KVH) combination (GQA head counts rarely divide a
    16-way TP axis).  The KV stream is consumed block-by-block with a
    ``lax.scan`` carrying online-softmax stats, so the score transient is a
    bounded [B, nq_shard, q_block, H, kv_block] tile, never the full Sq × Sk
    matrix.
    """
    b, sq, h, hd = q.shape
    _, sk, kvh, _ = k.shape
    hd_v = v.shape[-1]
    g = h // kvh
    if scale is None:
        scale = 1.0 / np.sqrt(hd)

    # Pin head/hd dims replicated: the 4-D reshape from the tensor-sharded
    # projection otherwise lets GSPMD shard head_dim, which turns the score
    # einsum into a per-kv-block psum (catastrophic wire traffic).
    q = shard_hint(q, "batch", None, None, None)
    k = shard_hint(k, "batch", None, None, None)
    v = shard_hint(v, "batch", None, None, None)

    q_block = _pick_q_block(sq, q_block, _qblk_axis_size())
    kv_block = min(kv_block, sk)
    while sk % kv_block:
        kv_block -= 1
    nq, nk = sq // q_block, sk // kv_block

    qg = (q.astype(jnp.float32) * scale).reshape(b, nq, q_block, kvh, g, hd)
    qg = shard_hint(qg, "batch", "qblk", None, None, None, None)
    qp = q_pos.reshape(b, nq, q_block)
    qp = shard_hint(qp, "batch", "qblk", None)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_valid is None:
        k_valid = jnp.ones((b, sk), bool)

    # [nk, B, kv_block, ...] scan layouts — each block is replicated over the
    # model axis while it streams past every (sharded) q block.
    k_js = kf.reshape(b, nk, kv_block, kvh, hd).transpose(1, 0, 2, 3, 4)
    v_js = vf.reshape(b, nk, kv_block, kvh, hd_v).transpose(1, 0, 2, 3, 4)
    kp_js = k_pos.reshape(b, nk, kv_block).transpose(1, 0, 2)
    kv_js = k_valid.reshape(b, nk, kv_block).transpose(1, 0, 2)

    def kv_chunk(carry, kv_xs_j):
        m, l, acc = carry
        kj, vj, kpj, kvj = kv_xs_j
        s = jnp.einsum("bnqhgd,bkhd->bnqhgk", qg, kj)  # [B,nq,qb,KVH,G,kb]
        bias = _mask_bias(qp, kpj[:, None], causal=causal, window=window,
                          k_valid=kvj[:, None])  # [B, nq, qb, kb]
        s = s + bias[:, :, :, None, None, :]
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bnqhgk,bkhd->bnqhgd", p, vj)
        return (m_new, l_new, acc_new), None

    # Flash-attention backward semantics: recompute the score/softmax tiles
    # per kv block instead of letting the scan stack them for backward —
    # without this every layer materializes the full Sq x Sk probability
    # tensor in HBM during the backward pass (measured as the dominant
    # memory-roofline contributor across all attention archs).
    kv_chunk = jax.checkpoint(kv_chunk, prevent_cse=False)

    m0 = jnp.full((b, nq, q_block, kvh, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, nq, q_block, kvh, g), jnp.float32)
    a0 = shard_hint(
        jnp.zeros((b, nq, q_block, kvh, g, hd_v), jnp.float32),
        "batch", "qblk", None, None, None, None,
    )
    (m, l, acc), _ = jax.lax.scan(kv_chunk, (m0, l0, a0), (k_js, v_js, kp_js, kv_js))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.reshape(b, sq, h, hd_v)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache (decode)
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    """Per-layer-stacked KV cache.

    k/v: [L, B, S_buf, KVH, hd].  For SWA archs S_buf = window (ring buffer),
    otherwise S_buf = max context.  ``pos`` holds absolute positions written
    at each slot (-1 = empty); used for masking and ring-buffer decode.
    """

    k: jax.Array
    v: jax.Array
    pos: jax.Array  # [L, B, S_buf] int32, -1 where invalid
    length: jax.Array  # [] int32 — tokens generated so far (absolute)

    @property
    def buf_len(self) -> int:
        return self.k.shape[2]


def init_cache(
    n_layers: int,
    batch: int,
    buf_len: int,
    kv_heads: int,
    head_dim: int,
    dtype=jnp.float32,
) -> KVCache:
    return KVCache(
        k=jnp.zeros((n_layers, batch, buf_len, kv_heads, head_dim), dtype),
        v=jnp.zeros((n_layers, batch, buf_len, kv_heads, head_dim), dtype),
        pos=jnp.full((n_layers, batch, buf_len), -1, jnp.int32),
        length=jnp.zeros((), jnp.int32),
    )


class LatentCache(NamedTuple):
    """Per-layer-stacked cache of multi-head latent attention (MLA).

    c: [L, B, rank, S_buf], each position's normalised latent, shared by all
    heads; k_pe: [L, B, rope_dim, S_buf], its rotated key part (one head,
    read by all).  Slots ``0 .. length-1`` hold positions ``0 .. length-1``.
    Positions are the minor axis, the layout XLA gives the prefill's
    per-layer latents on the TPU, so writing them needs no relayout copy of
    the cache.
    """

    c: jax.Array
    k_pe: jax.Array
    length: jax.Array  # [] int32

    @property
    def buf_len(self) -> int:
        return self.c.shape[3]


def init_latent_cache(n_layers: int, batch: int, buf_len: int, rank: int,
                      rope_dim: int, dtype=jnp.float32) -> LatentCache:
    return LatentCache(
        c=jnp.zeros((n_layers, batch, rank, buf_len), dtype),
        k_pe=jnp.zeros((n_layers, batch, rope_dim, buf_len), dtype),
        length=jnp.zeros((), jnp.int32),
    )


def cache_shard_hint(c: KVCache) -> KVCache:
    """Sharding: batch->data; KV heads->tensor when divisible else seq->model."""
    return KVCache(
        k=shard_hint(c.k, None, "batch", "kv_seq", "tensor", None),
        v=shard_hint(c.v, None, "batch", "kv_seq", "tensor", None),
        pos=shard_hint(c.pos, None, "batch", "kv_seq"),
        length=c.length,
    )


def cache_insert(layer_k, layer_v, layer_pos, k_new, v_new, position, ring: bool):
    """Insert one token's K/V at absolute ``position`` (ring-buffered if SWA).

    layer_k/v: [B, S_buf, KVH, hd]; k_new/v_new: [B, 1, KVH, hd];
    position: [] int32.
    """
    s_buf = layer_k.shape[1]
    slot = jnp.where(ring, position % s_buf, jnp.minimum(position, s_buf - 1))
    k = jax.lax.dynamic_update_slice(layer_k, k_new.astype(layer_k.dtype), (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(layer_v, v_new.astype(layer_v.dtype), (0, slot, 0, 0))
    pos = jax.lax.dynamic_update_slice(
        layer_pos,
        jnp.full((layer_pos.shape[0], 1), position, jnp.int32),
        (0, slot),
    )
    return k, v, pos


def cache_write(c: KVCache, k_new, v_new, position, ring: bool) -> KVCache:
    """Write one token's K/V of every layer (``k_new``/``v_new`` [L, B, 1,
    KVH, hd]) at absolute ``position``, in the slot ``cache_insert`` picks,
    with one update of each stacked array: after a decode step's layers have
    read the cache, so a donated cache changes in place.  The slot a ring
    buffer overwrites holds a position the window already masks."""
    s_buf = c.k.shape[2]
    slot = jnp.where(ring, position % s_buf, jnp.minimum(position, s_buf - 1))
    at = (0, 0, slot, 0, 0)
    return c._replace(
        k=jax.lax.dynamic_update_slice(c.k, k_new.astype(c.k.dtype), at),
        v=jax.lax.dynamic_update_slice(c.v, v_new.astype(c.v.dtype), at),
        pos=jax.lax.dynamic_update_slice(
            c.pos, jnp.full(c.pos.shape[:2] + (1,), position, jnp.int32), at[:3]),
    )


def decode_attend(
    q: jax.Array,  # [B, 1, H, hd]
    layer_k: jax.Array,  # [B, S_buf, KVH, hd]
    layer_v: jax.Array,
    layer_pos: jax.Array,  # [B, S_buf]
    q_position,  # [] int32 absolute
    *,
    window: Optional[int] = None,
    causal: bool = True,  # False for cross-attention memory
    k_new: Optional[jax.Array] = None,  # [B, 1, KVH, hd]: the query's own
    v_new: Optional[jax.Array] = None,  # key/value, attended beside the cache
) -> jax.Array:
    """Single-token attention against the cache (no chunking needed: Sq=1).
    With ``k_new``/``v_new`` the query also attends its own position, which
    the cache does not hold yet (``cache_write`` adds it after the layers)."""
    b, _, h, hd = q.shape
    kvh = layer_k.shape[2]
    g = h // kvh
    scale = 1.0 / np.sqrt(hd)
    qg = (q.astype(jnp.float32) * scale).reshape(b, 1, kvh, g, hd)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, layer_k.astype(jnp.float32))
    q_pos = jnp.full((b, 1), q_position, jnp.int32)
    valid = layer_pos >= 0
    bias = _mask_bias(q_pos, layer_pos, causal=causal, window=window, k_valid=valid)
    s = s + bias[:, :, None, None, :]
    if k_new is not None:
        s = jnp.concatenate(
            [s, jnp.einsum("bqhgd,bkhd->bqhgk", qg, k_new.astype(jnp.float32))], -1)
    p = jax.nn.softmax(s, axis=-1)
    if k_new is not None:
        out = (jnp.einsum("bqhgk,bkhd->bqhgd", p[..., :-1], layer_v.astype(jnp.float32))
               + jnp.einsum("bqhgk,bkhd->bqhgd", p[..., -1:], v_new.astype(jnp.float32)))
    else:
        out = jnp.einsum("bqhgk,bkhd->bqhgd", p, layer_v.astype(jnp.float32))
    return out.reshape(b, 1, h, hd).astype(q.dtype)
