"""Shared model infrastructure: sharding hints, param specs, norms, losses.

Design notes
------------
* Models are pure-JAX functional modules: ``init(key, cfg) -> params`` (nested
  dicts of f32 arrays) and ``loss(cfg, params, batch, mat) -> scalar``.
* Per-block parameters are stacked along a leading layer axis and consumed by
  ``jax.lax.scan`` — this keeps the HLO compact (compile time matters for the
  512-device dry-run) and gives the OMC materializer a single per-layer hook.
* ``mat`` (a :class:`Materializer`) is called on each scanned layer slice (and
  once on the non-block params).  The FP32 baseline materializer only applies
  the FSDP all-gather sharding hint; the OMC materializer all-gathers the
  *compressed bitfields* and decompresses layer-by-layer under remat — the
  paper's decompress-on-the-fly, realized TPU-natively (DESIGN.md §2).
* Sharding is expressed with *logical axes* resolved against the active mesh
  (MaxText-style).  ``shard_hint`` silently drops a mesh axis when the dim is
  not divisible by it, which uniformly handles kv-heads < model-axis, batch=1
  long-context decode, odd head counts, etc.
"""

from __future__ import annotations

import dataclasses
import math
import threading
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# Logical axis rules and the mesh context
# ---------------------------------------------------------------------------

# logical axis -> tuple of mesh axis names (tried in order, divisibility wins)
DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "fsdp": ("pod", "data"),  # weight storage shard (ZeRO-3 style)
    "tensor": ("model",),  # tensor-parallel dim (heads / ffn / vocab)
    "kv_seq": ("model",),  # decode KV-cache sequence sharding (MQA/GQA)
    "expert": ("model",),  # expert-parallel dim (only when divisible)
    "qblk": ("model",),  # train/prefill attention: q-block dim (Ulysses-style)
    "seq": ("model",),  # sequence-sharded residual stream (Megatron-SP)
    "dstate": ("model",),  # recurrent state feature dim (mLSTM/RG-LRU TP)
    "replicated": (),
}


class _MeshCtx(threading.local):
    def __init__(self):
        self.mesh: Optional[jax.sharding.Mesh] = None
        self.rules: Dict[str, Tuple[str, ...]] = dict(DEFAULT_RULES)


_CTX = _MeshCtx()


class activate_mesh:
    """Context manager: resolve logical-axis hints against ``mesh``.

    Outside the context every hint is an identity — models run un-annotated
    on CPU (smoke tests) with zero overhead.
    """

    def __init__(self, mesh, rules: Optional[Dict[str, Tuple[str, ...]]] = None):
        self.mesh = mesh
        self.rules = dict(DEFAULT_RULES)
        if rules:
            self.rules.update(rules)

    def __enter__(self):
        self._old = (_CTX.mesh, _CTX.rules)
        _CTX.mesh, _CTX.rules = self.mesh, self.rules
        return self.mesh

    def __exit__(self, *exc):
        _CTX.mesh, _CTX.rules = self._old
        return False


def current_mesh():
    return _CTX.mesh


def _mesh_axis_sizes(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def resolve_spec(
    logical: Sequence[Optional[str]],
    shape: Sequence[int],
    mesh=None,
    rules=None,
) -> P:
    """Logical axes -> PartitionSpec, dropping non-divisible mesh axes."""
    mesh = mesh if mesh is not None else _CTX.mesh
    rules = rules if rules is not None else _CTX.rules
    if mesh is None:
        return P()
    sizes = _mesh_axis_sizes(mesh)
    out, used = [], set()
    for dim, name in zip(shape, logical):
        if name is None or name == "replicated":
            out.append(None)
            continue
        axes = []
        prod = 1
        for ax in rules.get(name, ()):
            if ax in used or ax not in sizes:
                continue
            if dim % (prod * sizes[ax]) == 0:
                axes.append(ax)
                prod *= sizes[ax]
        for ax in axes:
            used.add(ax)
        out.append(tuple(axes) if len(axes) > 1 else (axes[0] if axes else None))
    return P(*out)


def shard_hint(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint via logical axes; identity when no mesh."""
    mesh = _CTX.mesh
    if mesh is None or not hasattr(x, "shape"):
        return x
    spec = resolve_spec(logical, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(logical: Sequence[Optional[str]], shape, mesh=None):
    mesh = mesh if mesh is not None else _CTX.mesh
    return NamedSharding(mesh, resolve_spec(logical, shape, mesh))


# ---------------------------------------------------------------------------
# Param specs — each model exposes the (storage, gathered) logical axes of
# every parameter so that the runtime can build in_shardings and the OMC
# materializer knows what to all-gather.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Logical axes of one parameter.

    storage:  sharding at rest (server state) — includes the fsdp axis.
    gathered: sharding during compute — fsdp axis removed, tensor axis kept.
    """

    storage: Tuple[Optional[str], ...]
    gathered: Tuple[Optional[str], ...]


def wspec(*axes: Optional[str]) -> ParamSpec:
    """Weight spec: storage as given; gathered = with 'fsdp' removed."""
    return ParamSpec(
        storage=tuple(axes),
        gathered=tuple(None if a == "fsdp" else a for a in axes),
    )


RSPEC = ParamSpec(storage=("replicated",), gathered=("replicated",))  # any rank


def spec_leaf_for(path_unused, leaf_spec: ParamSpec, leaf: jax.Array):
    return leaf_spec


# ---------------------------------------------------------------------------
# Materializer
# ---------------------------------------------------------------------------


class Materializer:
    """Maps stored layer params -> full-precision compute weights.

    The baseline ("fp32") materializer applies the gathered sharding hint
    (triggering the FSDP all-gather in f32).  The OMC materializer (see
    ``repro.federated.materialize``) replaces the stored leaf with
    (codes, s, b[, master]) structures, all-gathers the *codes*, and decodes.
    """

    def __init__(self, spec_tree=None):
        self.spec_tree = spec_tree

    def __call__(self, subtree, spec_subtree=None):
        spec_subtree = spec_subtree if spec_subtree is not None else self.spec_tree

        def f(spec, leaf):
            if spec is None or _CTX.mesh is None:
                return leaf
            return shard_hint(leaf, *_pad_spec(spec.gathered, leaf.ndim))

        if spec_subtree is None:
            return subtree
        return jax.tree_util.tree_map(
            f, spec_subtree, subtree, is_leaf=lambda s: isinstance(s, ParamSpec)
        )

    def leaf(self, x):
        """Materialize a single small (replicated) leaf — norms, biases."""
        return x


def _pad_spec(axes: Tuple[Optional[str], ...], ndim: int):
    """Right-align a spec to the leaf rank (scan slicing drops the L dim)."""
    axes = tuple(axes)
    if len(axes) >= ndim:
        return axes[len(axes) - ndim :]
    return (None,) * (ndim - len(axes)) + axes


IDENTITY_MAT = Materializer(None)


# ---------------------------------------------------------------------------
# Initializers / basic layers
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, scale: float = 1.0) -> jax.Array:
    std = scale / np.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * std).astype(
        jnp.float32
    )


def embed_init(key, vocab: int, d: int) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(jnp.float32)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def layer_norm(x, scale, bias, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


def group_norm(x, scale, bias, groups: int, eps: float = 1e-6):
    """GroupNorm over the channel dim (paper swaps BN->GN for FL)."""
    *lead, c = x.shape
    xf = x.astype(jnp.float32).reshape(*lead, groups, c // groups)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    xn = ((xf - mu) * jax.lax.rsqrt(var + eps)).reshape(*lead, c)
    return (xn * scale + bias).astype(x.dtype)


def swiglu(x, w1, w3, w2):
    """LLaMA-style gated MLP: (silu(x@w1) * (x@w3)) @ w2."""
    h = jax.nn.silu(x @ w1) * (x @ w3)
    h = shard_hint(h, "batch", None, "tensor")
    return h @ w2


def gelu_mlp(x, w1, b1, w2, b2):
    h = jax.nn.gelu(x @ w1 + b1)
    h = shard_hint(h, "batch", None, "tensor")
    return h @ w2 + b2


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float = 10000.0) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0):
    """x: [..., S, H, hd]; positions: [..., S] (int)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # [hd/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention-temperature factor ``0.1·mscale·ln(factor) + 1``
    (1 for no scaling)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(dim: int, theta: float, original_max: int,
                          beta_fast: float, beta_slow: float) -> Tuple[int, int]:
    """Rotary pair indices between which YaRN ramps from extrapolated to
    interpolated frequencies: ``floor``/``ceil`` of the index that turns
    ``beta`` times over ``original_max`` positions, clamped to [0, dim-1]."""

    def index(rotations):
        return dim * math.log(original_max / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    return max(math.floor(index(beta_fast)), 0), min(math.ceil(index(beta_slow)), dim - 1)


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN inverse frequencies [dim/2] (arXiv 2309.00071, DeepSeek-V2's
    rotary): ``θ^(-2i/dim)`` below the correction range, that over ``factor``
    above it, and a linear ramp between."""
    freq_extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    freq_inter = freq_extra / factor
    low, high = yarn_correction_range(dim, theta, original_max, beta_fast, beta_slow)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (freq_inter * ramp + freq_extra * (1.0 - ramp)).astype(np.float32)


def apply_rope_interleaved(x: jax.Array, positions: jax.Array, inv_freq) -> jax.Array:
    """Rotary on adjacent pairs ``(x[2i], x[2i+1])`` (DeepSeek's layout): the
    result holds the rotated even members, then the rotated odd ones.
    x: [..., S, H, d]; positions: [..., S]; inv_freq: [d/2]."""
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate(
        [even * cos - odd * sin, odd * cos + even * sin], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# Loss — chunked cross-entropy (bounds the [B, S, V] logits transient)
# ---------------------------------------------------------------------------


def softmax_xent_chunked(
    hidden: jax.Array,  # [B, S, D]
    head_w: jax.Array,  # [D, V]
    labels: jax.Array,  # [B, S] int32
    mask: Optional[jax.Array] = None,  # [B, S] 0/1
    chunk: int = 1024,
) -> jax.Array:
    """Mean CE over (masked) tokens, computing logits in seq chunks."""
    b, s, d = hidden.shape
    chunk = min(chunk, s)
    while s % chunk:
        chunk -= 1
    n_chunks = s // chunk
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    mask = mask.astype(jnp.float32)

    hs = hidden.reshape(b, n_chunks, chunk, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
    ms = mask.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        h, lab, m = xs
        # Pin h to D-replicated: GSPMD otherwise solves the dot with the
        # *contraction* dim sharded and satisfies the vocab-sharded logits
        # constraint via an all-reduce of the FULL-vocab partial product
        # (measured: 314 GB wire on recurrentgemma train_4k).
        h = shard_hint(h, "batch", None, None)
        logits = (h @ head_w).astype(jnp.float32)  # [B, c, V]
        logits = shard_hint(logits, "batch", None, "tensor")
        lse = jax.nn.logsumexp(logits, axis=-1)
        # label pick via masked sum, NOT take_along_axis: a gather over the
        # vocab-sharded axis makes GSPMD all-gather the full logits (and
        # all-reduce the full-vocab scatter in backward) — measured 314 GB
        # of wire on recurrentgemma train_4k.  The mask is local per shard
        # and its backward is an elementwise product.
        v = logits.shape[-1]
        onehot = (jnp.arange(v, dtype=lab.dtype) == lab[..., None])
        picked = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
        nll = (lse - picked) * m
        loss_sum, cnt = carry
        return (loss_sum + nll.sum(), cnt + m.sum()), None

    body = jax.checkpoint(body)
    (loss_sum, cnt), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), (hs, ls, ms)
    )
    return loss_sum / jnp.maximum(cnt, 1.0)


def scan_blocks(
    block_fn: Callable,
    stacked_params,
    x,
    mat: Materializer,
    spec_tree=None,
    extra_xs=None,
):
    """scan over stacked layer params with per-layer materialize + remat.

    ``block_fn(carry, layer_f32_params, extra_slice) -> carry``.
    The remat wrapper is what frees the decompressed per-layer weights after
    use — the paper's transient-copy semantics (Fig. 1), enforced by XLA
    liveness instead of manual deallocation.
    """

    def body(carry, xs):
        layer_params, extra = xs
        w = mat(layer_params, spec_tree)
        return block_fn(carry, w, extra), None

    body = jax.checkpoint(body, prevent_cse=False)
    xs = (stacked_params, extra_xs)
    carry, _ = jax.lax.scan(body, x, xs)
    return carry


def stack_layer_params(layer_list):
    """[{...}, {...}] -> {...} with leaves stacked on a new leading axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, 0), *layer_list)
