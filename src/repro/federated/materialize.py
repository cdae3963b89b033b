"""OMC materialization inside the distributed step (DESIGN.md §2/§4).

The server state stores policy-selected variables as ``CompressedVariable``
(uint bitfield codes + PVT scalars).  Inside the jitted round each scanned
layer slice is materialized:

  1. the *codes* are all-gathered over the fsdp axis (u8/u16/u32 on the wire
     — the paper's compressed server->client transport, 6–19 bits/param
     instead of 32),
  2. decoded + PVT-corrected to f32 — a transient that remat frees after the
     layer consumes it (the paper's decompress-on-the-fly, Fig. 1),
  3. grafted onto a zero-valued f32 "gradient sink" so that
     ``jax.grad(loss)(sinks)`` yields d loss / d W_effective — the client
     delta — without a persistent f32 master copy ever existing.

The graft is the straight-through identity
    w = stop_grad(decoded) + sink - stop_grad(sink)
whose forward value is exactly ``decoded`` (sink is zeros) and whose
backward routes the full cotangent into ``sink``.  No custom_vjp is needed
and no gradient ever flows into the integer codes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.formats import decode
from repro.core.pvt import pvt_apply
from repro.core.store import CompressedVariable, is_compressed
from repro.models.common import Materializer, ParamSpec, _pad_spec, shard_hint


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QParam:
    """Storage-form parameter paired with its gradient sink.

    value: CompressedVariable (selected vars) or f32 array (the rest).
    sink:  f32 zeros of the decompressed shape; grad(loss)(sinks) = client
           delta.  None in inference mode (no grads wanted).
    """

    value: Any
    sink: Optional[jax.Array] = None


def _is_leaf(x):
    return is_compressed(x) or isinstance(x, QParam)


def make_sinks(params, specs=None):
    """f32 zero tree shaped like the decompressed params (created in-jit —
    XLA keeps them as broadcast constants, no memory).

    With ``specs`` the zeros carry the *storage* sharding constraint: the
    cotangent of each per-layer graft then lands on a storage-sharded
    accumulator, so GSPMD reduce-scatters the client-delta mean inside the
    backward scan instead of accumulating full-size replicated grads (which
    would be ~4 bytes/param *per device* — fatal at 110 B scale).
    """

    def zero(leaf):
        if is_compressed(leaf):
            return jnp.zeros(leaf.codes.shape, jnp.float32)
        return jnp.zeros(leaf.shape, jnp.float32)

    if specs is None:
        return jax.tree_util.tree_map(zero, params, is_leaf=_is_leaf)

    def zero_spec(spec, leaf):
        z = zero(leaf)
        return shard_hint(z, *_pad_spec(spec.storage, z.ndim))

    return jax.tree_util.tree_map(
        zero_spec, specs, params,
        is_leaf=lambda s: isinstance(s, ParamSpec),
    )


def pack_qparams(params, sinks=None):
    """Zip storage params with sinks into a QParam tree (model input)."""
    if sinks is None:
        return jax.tree_util.tree_map(
            lambda v: QParam(v, None), params, is_leaf=_is_leaf
        )
    return jax.tree_util.tree_map(
        lambda v, s: QParam(v, s), params, sinks, is_leaf=_is_leaf
    )


#: Named scope of a leaf's materialization (decode of the codes, PVT
#: affine, cast) in the programs that run a model (DESIGN.md §15).
MATERIALIZE = "omc.materialize"


class OMCMaterializer(Materializer):
    """Materializer that understands QParam / CompressedVariable leaves.

    Per leaf:
      * CompressedVariable: gather codes (compressed collective) -> decode ->
        PVT affine -> graft sink.
      * f32 array: gather (f32 collective — unselected vars travel at full
        precision, as in the paper) -> graft sink.
    """

    def __init__(self, spec_tree=None, compute_dtype=jnp.float32):
        super().__init__(spec_tree)
        self.compute_dtype = compute_dtype

    def __call__(self, subtree, spec_subtree=None):
        spec_subtree = spec_subtree if spec_subtree is not None else self.spec_tree
        if spec_subtree is None:
            return jax.tree_util.tree_map(
                lambda q: self._leaf(q, None), subtree, is_leaf=_is_leaf
            )
        return jax.tree_util.tree_map(
            lambda sp, q: self._leaf(q, sp),
            spec_subtree,
            subtree,
            is_leaf=lambda s: isinstance(s, ParamSpec),
        )

    def leaf(self, x):
        return self._leaf(x, None)

    def _leaf(self, q, spec: Optional[ParamSpec]):
        with jax.named_scope(MATERIALIZE):
            return self._materialize(q, spec)

    def _materialize(self, q, spec: Optional[ParamSpec]):
        if not isinstance(q, QParam):
            # plain leaf (e.g. fp32 baseline without sinks)
            if is_compressed(q):
                codes = self._gather(q.codes, spec)
                return pvt_apply(decode(codes, q.fmt), q.s, q.b).astype(
                    self.compute_dtype
                )
            return self._gather(q, spec).astype(self.compute_dtype)
        v = q.value
        if is_compressed(v):
            codes = self._gather(v.codes, spec)
            w = pvt_apply(decode(codes, v.fmt), v.s, v.b)
        else:
            w = self._gather(v, spec)
        if q.sink is not None:
            w = jax.lax.stop_gradient(w) + (q.sink - jax.lax.stop_gradient(q.sink))
        return w.astype(self.compute_dtype)

    @staticmethod
    def _gather(x, spec: Optional[ParamSpec]):
        if spec is None:
            return x
        return shard_hint(x, *_pad_spec(spec.gathered, x.ndim))
