"""Vectorized heterogeneous-cohort simulation engine (DESIGN.md §9).

The reference loop (:mod:`repro.federated.simulate`) runs one jitted client
at a time — perfect for auditing paper numerics, quadratically painful for
production-scale cohorts.  This module executes the whole round — built
from the *same* single-client round body
(:func:`repro.federated.simulate.make_client_fn`) — as ONE compiled XLA
program (:func:`make_round_fn`):

  * **stacked client states** — client ids, per-client RNG-derived PPQ mask
    bits, and local batches all carry a leading cohort axis; the client
    update is ``vmap``-ped over it (optionally chunked through ``lax.map``
    — a scan of vmapped blocks — to bound peak memory at huge cohorts),
  * **heterogeneous device tiers** — a cohort may mix bitwidths (e.g.
    S1E3M7 / S1E4M3 / f32 clients).  Tier populations are disjoint
    (round-robin over client ids) and the server samples a fixed per-tier
    quota each round (stratified sampling — how production FL hits per-tier
    report goals), so each tier is a static-shape segment of the round
    program and nothing recompiles as cohort composition varies,
  * **wire-byte accounting** — per-round download/upload bytes from the
    shared :mod:`repro.federated.accounting` table, reconciled exactly
    against :mod:`repro.api.codecs` payload sizes.

Equivalence contract (tested in ``tests/test_engine.py``): with a single
default tier, the engine consumes the same cohort sample, survival mask,
PPQ masks, and data stream as the reference loop; client models differ only
by batched-op reassociation (documented tolerance), and wire-byte
accounting matches the loop path bit-for-bit.  See DESIGN.md §9 for the
layout and the loop-vs-vectorized decision guide.

With ``fused_agg=True`` the server half of the round runs in the compressed
domain: client uploads are transport-encoded and aggregated by the fused
Pallas dequant→masked-weighted-accumulate→requant kernel without ever
materializing f32 cohort state for selected variables — contract and gating
rules in DESIGN.md §13.

Every round here is still a hard barrier — the program returns when the
whole cohort has trained.  When the fleet is straggler-dominated (heavy-tail
latency, diurnal availability), use the event-driven non-barrier runtime
:mod:`repro.federated.async_engine` (DESIGN.md §10), which batches its
local training through the same ``make_client_fn`` body.

Both this engine and the async runtime still stack the whole cohort in one
program, so cohort size is bounded by device memory.  For populations far
beyond that — 100k–1M simulated clients streamed through fixed memory with
two-level tree aggregation over a :class:`repro.scale.store.ShardLayout` —
use :mod:`repro.scale` (DESIGN.md §14), which chunks the same client body
through :func:`repro.scale.stream.make_stream_fn` and reuses this module's
:func:`mask_dead_rows` / :func:`apply_server_step` so the server algebra
cannot drift between the flat and treed paths.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import FloatFormat, value_quantize, encode
from repro.core.omc import OMCConfig
from repro.core.partial import ppq_mask
from repro.core.policy import path_str
from repro.core.pvt import pvt_solve_fast
from repro.core.store import CompressedVariable, compress_variable, \
    decompress_tree, is_compressed
from repro.kernels import ops as kernel_ops
from repro.models.common import ParamSpec
from repro.obs import metrics as obs_metrics
from repro.obs import null_span

from . import accounting
from . import cohort as cohort_lib
from . import simulate
from .simulate import SimConfig
from .state import compress_params, n_stack_axes


# ---------------------------------------------------------------------------
# Device profiles — per-client bitwidth tiers
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DeviceProfile:
    """One device tier: how its clients quantize compute and transport.

    ``fmt`` / ``quantize_fraction`` override the server's base
    :class:`OMCConfig` for clients of this tier; ``None`` inherits.  A tier
    with the identity format and fraction 1.0 runs f32 end to end (its
    uploads travel uncompressed — the "new flagship phone" tier).
    """

    name: str = "default"
    fmt: Optional[str] = None  # e.g. "S1E4M3"; None -> server format
    quantize_fraction: Optional[float] = None  # None -> server fraction

    def resolve(self, base: OMCConfig) -> OMCConfig:
        kw: Dict[str, Any] = {}
        if self.fmt is not None:
            kw["fmt"] = FloatFormat.parse(self.fmt)
        if self.quantize_fraction is not None:
            kw["quantize_fraction"] = float(self.quantize_fraction)
        return dataclasses.replace(base, **kw) if kw else base


#: Ready-made tiers for the scenario cookbook (README) and benchmarks.
PROFILES: Dict[str, DeviceProfile] = {
    "default": DeviceProfile(),
    "f32": DeviceProfile("f32", fmt="S1E8M23", quantize_fraction=1.0),
    "s1e3m7": DeviceProfile("s1e3m7", fmt="S1E3M7"),
    "s1e4m3": DeviceProfile("s1e4m3", fmt="S1E4M3"),
    "s1e4m14": DeviceProfile("s1e4m14", fmt="S1E4M14"),
}


def profile(name: str) -> DeviceProfile:
    try:
        return PROFILES[name]
    except KeyError:
        raise KeyError(
            f"unknown device profile {name!r}; known: {sorted(PROFILES)}"
        ) from None


# ---------------------------------------------------------------------------
# Cohort spec — plan + tiers + per-tier quotas
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CohortSpec:
    """A cohort plan plus its device-tier composition.

    With no ``tiers`` the cohort is homogeneous under the server's OMC
    config and the engine reproduces the reference loop's sampling exactly.
    With tiers, the population is partitioned round-robin (client ``i``
    belongs to tier ``i % n_tiers``) and each round samples ``quotas[t]``
    clients from tier ``t``'s population without replacement — every tier
    is a static-shape segment of the single compiled round program.
    """

    plan: cohort_lib.CohortPlan
    tiers: Tuple[DeviceProfile, ...] = ()
    quotas: Optional[Tuple[int, ...]] = None  # default: even split
    client_chunk: Optional[int] = None  # lax.map chunk; None -> pure vmap

    def __post_init__(self):
        if self.tiers:
            n = len(self.tiers)
            if self.quotas is None:
                base, rem = divmod(self.plan.cohort_size, n)
                object.__setattr__(
                    self, "quotas",
                    tuple(base + (1 if t < rem else 0) for t in range(n)),
                )
            if len(self.quotas) != n:
                raise ValueError("quotas must have one entry per tier")
            if sum(self.quotas) != self.plan.cohort_size:
                raise ValueError(
                    f"quotas {self.quotas} must sum to cohort_size "
                    f"{self.plan.cohort_size}"
                )
            for t, q in enumerate(self.quotas):
                pop = self.tier_population(t).shape[0]
                if q > pop:
                    raise ValueError(
                        f"tier {t} quota {q} exceeds its population {pop}"
                    )
        elif self.quotas is not None:
            raise ValueError("quotas given but no tiers")
        for q in self.group_sizes:
            # mirror the runtime gate: a segment is only chunked when it is
            # larger than the chunk (smaller quotas run as pure vmap)
            if self.client_chunk and q > self.client_chunk and (
                q % self.client_chunk
            ):
                raise ValueError(
                    f"client_chunk {self.client_chunk} must divide tier "
                    f"quotas larger than it (got {q})"
                )

    @property
    def n_tiers(self) -> int:
        return max(len(self.tiers), 1)

    @property
    def is_hetero(self) -> bool:
        return bool(self.tiers)

    @property
    def group_sizes(self) -> Tuple[int, ...]:
        return self.quotas if self.is_hetero else (self.plan.cohort_size,)

    def tier_population(self, t: int) -> np.ndarray:
        return np.arange(t, self.plan.num_clients, self.n_tiers,
                         dtype=np.int32)

    def tier_omcs(self, base: OMCConfig) -> List[OMCConfig]:
        tiers = self.tiers or (DeviceProfile(),)
        return [p.resolve(base) for p in tiers]


def sample_tiered_cohort(
    key: jax.Array, spec: CohortSpec, round_index
) -> List[jax.Array]:
    """Per-tier int32 id arrays (concat order = survival-mask order).

    Homogeneous specs defer to :func:`repro.federated.cohort.sample_cohort`
    so the engine sees the identical cohort the reference loop would.
    """
    if not spec.is_hetero:
        return [cohort_lib.sample_cohort(key, spec.plan, round_index)]
    k = jax.random.fold_in(key, round_index)
    out = []
    for t, q in enumerate(spec.quotas):
        pop = jnp.asarray(spec.tier_population(t))
        perm = jax.random.permutation(
            jax.random.fold_in(k, 0x7E0 + t), pop.shape[0]
        )
        out.append(pop[perm[:q]].astype(jnp.int32))
    return out


# ---------------------------------------------------------------------------
# Compressed-domain (fused) aggregation — DESIGN.md §13
# ---------------------------------------------------------------------------


def fused_aggregation_supported(spec: "CohortSpec", omc: OMCConfig,
                                strategy=None) -> bool:
    """When the fused compressed-domain server path can be picked (§13).

    Requires a homogeneous cohort (mixed tiers stack containers of different
    dtypes), OMC enabled, and no zoo strategy (strategies define their own
    decode/aggregate algebra, incl. error feedback).
    """
    return omc.enabled and not spec.is_hetero and strategy is None


def transport_encode_stacked(stacked_leaf, fmt: FloatFormat, pvt: bool,
                             batch_axes: int):
    """Encode a [C, ...] stack of client uploads to transport form.

    The wire-path math of ``compress_variable(..., fast=True)`` per client
    row, batched: (codes, s, b) with the client axis leading.  Dead-client
    rows may hold garbage — they encode to garbage codes/scalars, and the
    fused kernel's ``where(w > 0, ·, 0)`` guard discards them exactly.
    """
    vq = value_quantize(stacked_leaf, fmt)
    if pvt:
        s, b = pvt_solve_fast(stacked_leaf, vq, batch_axes + 1)
    else:
        c = stacked_leaf.shape[0]
        s = jnp.ones((c,), jnp.float32)
        b = jnp.zeros((c,), jnp.float32)
    return encode(vq, fmt, quantize=False), s, b


# ---------------------------------------------------------------------------
# Server-side round algebra — shared with the sharded runtime (repro.scale)
# ---------------------------------------------------------------------------


def mask_dead_rows(stacked, alive):
    """Zero dead clients' rows in a ``[C, ...]`` stack (NaN-safe FedAvg).

    The reference loop never computes dropped clients; the engine computes
    them and weights them 0.  ``0·x`` annihilates exactly for finite x, but
    a diverged dead client (non-finite update) would poison the mean as
    ``0·inf = NaN`` — zero dead entries outright so the paths stay
    equivalent even when clients blow up.  The streamed partial-aggregate
    program (:mod:`repro.scale.stream`) applies the identical guard before
    its weighted sums.
    """
    return jax.tree_util.tree_map(
        lambda x: jnp.where(
            jnp.asarray(alive).reshape((-1,) + (1,) * (x.ndim - 1)), x, 0.0
        ),
        stacked,
    )


def apply_server_step(server_f32, mean_model, specs, omc: OMCConfig,
                      server_lr: float):
    """The server half of every unfused round, in one place.

    Interpolate toward the cohort mean with ``server_lr`` and re-compress
    under the policy — used verbatim by this engine's ``finish`` and by
    the sharded root combine (:func:`repro.scale.hierarchy.make_root_fn`),
    so flat and tree-aggregated rounds share one requantization step and
    one interpolation formula by construction.
    """
    new_f32 = jax.tree_util.tree_map(
        lambda old, new: old + server_lr * (new - old),
        server_f32, mean_model,
    )
    return compress_params(new_f32, specs, omc) if omc.enabled else new_f32


# ---------------------------------------------------------------------------
# The compiled round: data gen + vmapped clients + aggregation + re-compress,
# all tiers, one XLA program.
# ---------------------------------------------------------------------------

#: Named scopes of the round program (DESIGN.md §15).  Side by side, never
#: nested, they cover the whole program; a device op's ``tf_op`` path in a
#: profiler trace names the one it belongs to.
DECOMPRESS = "omc.decompress"
CLIENT = "omc.client"
TRANSPORT_ENCODE = "omc.transport_encode"
SERVER_STEP = "omc.server_step"


def _run_cohort(one, server_f32, batches, round_index, ids,
                client_chunk: Optional[int], ef_rows=None):
    """vmap (or chunked lax.map) of the client body over one tier segment.

    ``ef_rows`` (a ``{name: [q, *shape]}`` dict of error-feedback residual
    rows, DESIGN.md §12) switches to the residual-threading client
    signature and adds the updated rows as a third output."""
    if ef_rows is not None:
        run3 = lambda b, c, e: one(server_f32, b, round_index, c, e)
        if client_chunk and ids.shape[0] > client_chunk:
            g = ids.shape[0] // client_chunk
            bs = jax.tree_util.tree_map(
                lambda x: x.reshape((g, client_chunk) + x.shape[1:]), batches
            )
            cs = ids.reshape(g, client_chunk)
            es = jax.tree_util.tree_map(
                lambda x: x.reshape((g, client_chunk) + x.shape[1:]), ef_rows
            )
            models, losses, rows = jax.lax.map(
                lambda xs: jax.vmap(run3)(*xs), (bs, cs, es)
            )
            unchunk = lambda x: x.reshape((-1,) + x.shape[2:])
            return (jax.tree_util.tree_map(unchunk, models),
                    losses.reshape(-1),
                    jax.tree_util.tree_map(unchunk, rows))
        return jax.vmap(run3)(batches, ids, ef_rows)
    run = lambda b, c: one(server_f32, b, round_index, c)
    if client_chunk and ids.shape[0] > client_chunk:
        # scan of vmapped blocks: same results, bounded live memory
        g = ids.shape[0] // client_chunk
        bs = jax.tree_util.tree_map(
            lambda x: x.reshape((g, client_chunk) + x.shape[1:]), batches
        )
        cs = ids.reshape(g, client_chunk)
        models, losses = jax.lax.map(
            lambda xs: jax.vmap(run)(*xs), (bs, cs)
        )
        models = jax.tree_util.tree_map(
            lambda x: x.reshape((-1,) + x.shape[2:]), models
        )
        return models, losses.reshape(-1)
    return jax.vmap(run)(batches, ids)


def make_round_fn(
    family,
    cfg,
    specs,
    omc: OMCConfig,
    sim: SimConfig,
    spec: CohortSpec,
    data_fn: Callable[[Any, Any, Any], Any],
    data_mode: str = "vmap",
    strategy=None,
    ste: bool = False,
    fused_agg: bool = False,
    collect_metrics: bool = False,
):
    """Build the engine's compiled round.

    ``(storage, ids_per_tier, alive, round_index) ->
    (new_storage, mean_loss, n_alive)`` — the whole round is ONE XLA
    program: server decompress, per-tier data generation, the ``vmap``-ped
    client updates, zero-weight FedAvg aggregation, the server
    interpolation step, and the re-compress of the new state.  The
    reference loop runs the identical ops eagerly at client granularity;
    here nothing leaves the runtime between rounds, which is where the
    order-of-magnitude throughput gap at large cohorts comes from
    (``benchmarks/cohort_scale.py``).

    ``data_mode="vmap"`` traces ``data_fn`` inside the program (it must be
    a pure function of traced ``(client_id, round_index, step)`` — the
    synthetic tasks and partitioned batch fns are); ``"host"`` takes
    pre-stacked per-tier batches as an extra argument, for data sources
    that cannot be traced (:func:`run_round_vectorized` stacks them).

    ``strategy``/``ste`` train under a zoo compression strategy
    (DESIGN.md §12): every tier's client body applies the strategy's qdq
    under its own tier OMC config.  When the strategy threads an
    error-feedback residual, the round program takes the population
    residual state ``ef`` as a final argument and returns the updated
    state as a fourth output — gather, per-client update, and the
    alive-masked scatter all stay inside the one compiled program.

    ``fused_agg=True`` aggregates selected variables entirely in the
    compressed domain (DESIGN.md §13): client uploads are transport-encoded
    and the server round runs the fused dequant→masked-weighted-accumulate→
    requant kernel (``repro.kernels.agg`` via ``kernels.ops``) — the server
    never materializes f32 cohort state for those variables.  Requires
    :func:`fused_aggregation_supported`; results match the unfused path
    within one quantization step with byte-identical wire ledgers
    (gated in tests/test_engine.py).

    ``collect_metrics=True`` appends the cohort mean the round already
    computes as the program's **final** output (``None`` on the fused
    path, where no f32 mean exists); :func:`run_round_vectorized` builds
    the metric bundle (DESIGN.md §15) eagerly on the host from that mean
    plus the round's outputs, so the compiled round math is identical
    with metrics on or off — main outputs stay bit-identical (gated in
    tests/test_obs.py).  Off by default so the program signature is
    unchanged for every existing caller.
    """
    if data_mode not in ("vmap", "host"):
        raise ValueError(f"data_mode must be 'vmap' or 'host', got {data_mode!r}")
    if fused_agg and not fused_aggregation_supported(spec, omc, strategy):
        raise ValueError(
            "fused_agg=True needs a homogeneous cohort, OMC enabled, and no "
            "zoo strategy (DESIGN.md §13)"
        )
    takes_ef = simulate.ef_lib.takes_residual(omc, strategy)
    ones = [
        simulate.make_client_fn(family, cfg, specs, omc_t, sim,
                                strategy, ste, takes_residual=takes_ef)
        for omc_t in spec.tier_omcs(omc)
    ]
    steps = jnp.arange(sim.local_steps)

    def finish(server_f32, stacked, loss_c, alive):
        w = alive.astype(jnp.float32)
        stacked = mask_dead_rows(stacked, alive)
        loss_c = jnp.where(alive, loss_c, 0.0)
        mean_model = cohort_lib.aggregate_weighted(stacked, w)
        new_storage = apply_server_step(server_f32, mean_model, specs, omc,
                                        sim.server_lr)
        n_alive = w.sum()
        loss = (loss_c * w).sum() / jnp.maximum(n_alive, 1.0)
        # collect_metrics: expose the cohort mean (already computed above)
        # so the host can build the metric bundle eagerly AFTER the round —
        # bundle math never runs inside this program, so the main outputs
        # compile identically with metrics on or off (DESIGN.md §15)
        aux = mean_model if collect_metrics else None
        return new_storage, loss, n_alive, aux

    def finish_fused(storage, stacked, loss_c, alive):
        # Compressed-domain server round (§13): selected variables never
        # exist as an f32 cohort stack on the server — each client row is
        # transport-encoded and the fused kernel aggregates codes directly.
        # Each leaf enters the encode scope and then the server-step scope,
        # so the two stay side by side in the trace in the ops' own order.
        with jax.named_scope(SERVER_STEP):
            w = alive.astype(jnp.float32)
            loss_c = jnp.where(alive, loss_c, 0.0)
            n_alive = w.sum()
            loss = (loss_c * w).sum() / jnp.maximum(n_alive, 1.0)

        def f(path, spec_t, srv, stack):
            if is_compressed(srv):
                ba = n_stack_axes(spec_t, srv.codes)
                with jax.named_scope(TRANSPORT_ENCODE):
                    codes_c, s_c, b_c = transport_encode_stacked(
                        stack, srv.fmt, omc.pvt, ba
                    )
                with jax.named_scope(SERVER_STEP):
                    new_codes, s, b = kernel_ops.fused_aggregate(
                        srv.codes, srv.s, srv.b, codes_c, s_c, b_c, w,
                        sim.server_lr, srv.fmt, batch_axes=ba, pvt=omc.pvt,
                    )
                return CompressedVariable(new_codes, s, b, srv.fmt)
            # Unselected leaves keep the classic f32 mean + interpolation.
            with jax.named_scope(SERVER_STEP):
                x = jnp.where(
                    alive.reshape((-1,) + (1,) * (stack.ndim - 1)), stack, 0.0
                )
                mean = cohort_lib.aggregate_weighted(x, w)
                return srv + sim.server_lr * (mean - srv)

        new_storage = jax.tree_util.tree_map_with_path(
            f, specs, storage, stacked,
            is_leaf=lambda s: isinstance(s, ParamSpec),
        )
        # compressed-domain round: no f32 cohort mean exists — the host-side
        # bundle degrades to the update norm (DESIGN.md §15)
        return new_storage, loss, n_alive, None

    def body(storage, ids_per_tier, batches_per_tier, alive, round_index, ef):
        with jax.named_scope(DECOMPRESS):
            server_f32 = decompress_tree(storage)
        with jax.named_scope(CLIENT):
            models, losses, rows = [], [], []
            for t, (one, ids_t) in enumerate(zip(ones, ids_per_tier)):
                if batches_per_tier is None:
                    batches = jax.vmap(
                        lambda c: jax.vmap(
                            lambda s: data_fn(c, round_index, s)
                        )(steps)
                    )(ids_t)
                else:
                    batches = batches_per_tier[t]
                if takes_ef:
                    rows_t = {k: v[ids_t] for k, v in ef.items()}
                    m, l, nr = _run_cohort(one, server_f32, batches,
                                           round_index, ids_t,
                                           spec.client_chunk, rows_t)
                    rows.append(nr)
                else:
                    m, l = _run_cohort(one, server_f32, batches, round_index,
                                       ids_t, spec.client_chunk)
                models.append(m)
                losses.append(l)
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(xs, 0), *models
            )
            loss_c = jnp.concatenate(losses)
        if fused_agg:
            new_storage, loss, n_alive, aux = finish_fused(
                storage, stacked, loss_c, alive
            )
        else:
            with jax.named_scope(SERVER_STEP):
                new_storage, loss, n_alive, aux = finish(
                    server_f32, stacked, loss_c, alive
                )
        out: Tuple[Any, ...] = (new_storage, loss, n_alive)
        if takes_ef:
            # scatter the cohort's updated residual rows back into the
            # population state; dead clients keep their previous residual
            # (they never uploaded — the loop path skips them entirely)
            with jax.named_scope(SERVER_STEP):
                ids_all = jnp.concatenate(list(ids_per_tier), 0)
                new_ef = {}
                for k, old in ef.items():
                    nr = jnp.concatenate([r[k] for r in rows], 0)
                    keep = alive.reshape((-1,) + (1,) * (nr.ndim - 1))
                    new_ef[k] = old.at[ids_all].set(
                        jnp.where(keep, nr, old[ids_all])
                    )
            out = out + (new_ef,)
        if collect_metrics:
            out = out + (aux,)
        return out

    if data_mode == "vmap":
        if takes_ef:

            @jax.jit
            def round_fn_ef(storage, ids_per_tier, alive, round_index, ef):
                return body(storage, ids_per_tier, None, alive, round_index,
                            ef)

            return round_fn_ef

        @jax.jit
        def round_fn(storage, ids_per_tier, alive, round_index):
            return body(storage, ids_per_tier, None, alive, round_index, None)

        return round_fn

    if takes_ef:

        @jax.jit
        def round_fn_host_ef(storage, ids_per_tier, batches_per_tier, alive,
                             round_index, ef):
            return body(storage, ids_per_tier, batches_per_tier, alive,
                        round_index, ef)

        return round_fn_host_ef

    @jax.jit
    def round_fn_host(storage, ids_per_tier, batches_per_tier, alive,
                      round_index):
        return body(storage, ids_per_tier, batches_per_tier, alive,
                    round_index, None)

    return round_fn_host


def _host_batches(data_fn, ids_per_tier, round_index, local_steps):
    out = []
    for ids_t in ids_per_tier:
        per_client = [
            jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs),
                *[data_fn(int(c), int(round_index), s)
                  for s in range(local_steps)],
            )
            for c in np.asarray(ids_t)
        ]
        out.append(
            jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_client)
        )
    return out


# ---------------------------------------------------------------------------
# Rounds and training
# ---------------------------------------------------------------------------


def run_round_vectorized(
    family,
    cfg,
    specs,
    omc: OMCConfig,
    sim: SimConfig,
    server_params,  # storage tree (CompressedVariable | f32)
    data_fn,
    spec: CohortSpec,
    round_index: int,
    key: jax.Array,
    round_fn=None,
    wire_table: Optional[accounting.WireTable] = None,
    data_mode: str = "vmap",
    strategy=None,
    ste: bool = False,
    ef=None,
    fused_agg: bool = False,
    obs=None,
) -> Tuple[Any, Dict[str, float]]:
    """One vectorized round.  Returns (new server storage, metrics).

    Semantics match :func:`repro.federated.simulate.run_round`: dead clients
    contribute weight 0 to the FedAvg mean (numerically identical to
    dropping them — zero-weight terms vanish exactly), the server
    interpolates toward the cohort mean and re-compresses.  Pass a cached
    ``round_fn`` (from :func:`make_round_fn`) when looping — building it
    here costs a compile.  ``strategy``/``ste``/``ef`` mirror the loop path
    (§12); the error-feedback state dict is updated in place.

    ``obs`` (a :class:`repro.obs.Obs` or None, DESIGN.md §15): when set
    and ``obs.collect_metrics``, the round program additionally returns the
    cohort mean it already computes, and the metric bundle (quantization
    error, update norm, EF residual norm) is assembled eagerly on the host
    AFTER the round — the compiled round math itself is untouched, so with
    obs enabled the trained trees and ledgers stay bit/byte-identical to
    ``obs=None`` (tier-1 gated).  A cached ``round_fn`` must have been
    built with matching ``collect_metrics``.

    The round is the span ``round`` (a profiler step numbered by
    ``round_index``) holding ``round.sample`` (cohort and survival mask),
    ``round.call`` (dispatch of the round program) and ``round.readback``
    (the wait for its loss and alive count).
    """
    takes_ef = simulate.ef_lib.takes_residual(omc, strategy)
    collect = obs is not None and obs.collect_metrics
    if round_fn is None:
        round_fn = make_round_fn(family, cfg, specs, omc, sim, spec, data_fn,
                                 data_mode, strategy=strategy, ste=ste,
                                 fused_agg=fused_agg, collect_metrics=collect)
    if takes_ef and ef is None:
        raise ValueError(
            f"strategy {strategy.label!r} uses error feedback: pass the "
            f"ef= state (repro.compress.feedback.init_ef_state)"
        )
    with null_span(obs, "round", step=int(round_index),
                   round=int(round_index)):
        with null_span(obs, "round.sample"):
            ids_per_tier = sample_tiered_cohort(key, spec, round_index)
            alive = cohort_lib.survival_mask(key, spec.plan, round_index)

        args = [server_params, ids_per_tier]
        if data_mode == "host":
            args.append(_host_batches(data_fn, ids_per_tier, round_index,
                                      sim.local_steps))
        args += [alive, jnp.int32(round_index)]
        with null_span(obs, "round.call"):
            res = round_fn(*args, ef) if takes_ef else round_fn(*args)
        base = 4 if takes_ef else 3
        mean_model = res[base] if len(res) > base else None
        if takes_ef:
            new_storage, loss, n_alive, new_ef = res[:4]
            for k in ef:
                ef[k] = new_ef[k]
        else:
            new_storage, loss, n_alive = res[:3]

        bundle = None
        if collect:
            # eager host-side bundle from the round's outputs (DESIGN.md
            # §15): the compiled program is never asked to compute metric
            # values, so enabling obs cannot perturb the trained tree
            bundle = obs_metrics.server_round_bundle(
                specs, server_params, new_storage, mean_model, sim.server_lr,
            )
            bundle["loss"] = loss
            bundle["alive"] = n_alive
            if takes_ef:
                ids_all = jnp.concatenate(
                    [jnp.asarray(i) for i in ids_per_tier], 0
                )
                bundle["ef_norm"] = obs_metrics.ef_rows_norm(
                    {k: v[ids_all] for k, v in ef.items()}
                )

        with null_span(obs, "round.readback"):
            n_alive = int(n_alive)
            loss = float(loss)
        metrics: Dict[str, float] = dict(
            loss=loss,
            cohort=n_alive,
            dropped=int(spec.plan.cohort_size - n_alive),
        )
        if wire_table is not None:
            metrics.update(
                round_wire_metrics(wire_table, omc, spec.tier_omcs(omc),
                                   ids_per_tier, alive, round_index,
                                   strategy=strategy)
            )
    if obs is not None:
        obs.record("round", bundle, round=int(round_index), **metrics)
    return new_storage, metrics


def round_wire_metrics(
    table: accounting.WireTable,
    omc: OMCConfig,
    tier_omcs: Sequence[OMCConfig],
    ids_per_tier: Sequence[jax.Array],
    alive: jax.Array,
    round_index,
    strategy=None,
) -> Dict[str, int]:
    """Exact per-round wire bytes: every invited client downloads the full
    compressed server state; every *surviving* client uploads its
    PPQ-masked, tier-format transport payload.  With ``strategy`` the
    per-client upload sizes come from the strategy's plan (§12) — raises
    for data-dependent strategies (train those with ``wire=False``)."""
    invited = sum(int(np.asarray(i).shape[0]) for i in ids_per_tier)
    down = accounting.download_bytes_train(table, omc, strategy) * invited
    alive_np = np.asarray(alive, bool)
    up = 0
    off = 0
    for omc_t, ids_t in zip(tier_omcs, ids_per_tier):
        q = int(np.asarray(ids_t).shape[0])
        if strategy is None:
            per_client = accounting.cohort_upload_bytes(
                table, omc_t, round_index, ids_t
            )
        else:
            per_client = accounting.cohort_upload_bytes_strategy(
                table, omc_t, strategy, round_index, ids_t
            )
        up += int(per_client[alive_np[off:off + q]].sum())
        off += q
    return dict(down_bytes=int(down), up_bytes=int(up))


def run_training_vectorized(
    family,
    cfg,
    omc: OMCConfig,
    sim: SimConfig,
    spec: CohortSpec,
    data_fn,
    init_key,
    num_rounds: int,
    eval_fn: Optional[Callable[[Any, int], float]] = None,
    eval_every: int = 10,
    init_params=None,
    log: Optional[Callable[[str], None]] = None,
    data_mode: str = "vmap",
    wire: bool = True,
    strategy=None,
    ste: bool = False,
    ef=None,
    fused_agg: bool = False,
    obs=None,
):
    """Vectorized mirror of :func:`repro.federated.simulate.run_training`.

    The round program compiles once (round 0) and is reused; history rows
    carry per-round ``down_bytes`` / ``up_bytes`` when ``wire=True``.
    Unlike the loop mirror (which defaults to ``wire=False`` — scalar
    accounting costs a host round-trip per client), the engine's batched
    accounting is a few ms per round, so it is on by default; pass
    ``wire=False`` for history rows schema-identical to the loop's default.
    ``strategy``/``ste``/``ef`` mirror the loop path (§12); ``obs``
    attaches telemetry (§15) — a host-assembled metric bundle per round
    plus a wall span per round (the round-0 span includes the XLA
    compile).
    """
    specs = family.param_specs(cfg)
    params = family.init(init_key, cfg) if init_params is None else init_params
    storage = compress_params(params, specs, omc) if omc.enabled else params
    collect = obs is not None and obs.collect_metrics
    round_fn = make_round_fn(family, cfg, specs, omc, sim, spec, data_fn,
                             data_mode, strategy=strategy, ste=ste,
                             fused_agg=fused_agg, collect_metrics=collect)
    if ef is None and simulate.ef_lib.takes_residual(omc, strategy):
        ef = simulate.ef_lib.init_ef_state(params, specs, omc,
                                           spec.plan.num_clients)
    table = accounting.build_wire_table(params, specs, omc) if wire else None
    key = jax.random.fold_in(init_key, 0xC047)
    history = []
    for r in range(num_rounds):
        storage, metrics = run_round_vectorized(
            family, cfg, specs, omc, sim, storage, data_fn, spec, r, key,
            round_fn=round_fn, wire_table=table, data_mode=data_mode,
            strategy=strategy, ste=ste, ef=ef, obs=obs,
        )
        if eval_fn is not None and (r + 1) % eval_every == 0:
            metrics["eval"] = float(eval_fn(decompress_tree(storage), r))
        history.append(dict(round=r, **metrics))
        if log and ((r + 1) % eval_every == 0 or r == 0):
            log(f"round {r + 1}/{num_rounds}: " +
                ", ".join(f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
                          for k, v in metrics.items()))
    return storage, history


# ---------------------------------------------------------------------------
# Codec reconciliation helper — what a client's upload actually serializes
# ---------------------------------------------------------------------------


def masked_upload_tree(trained_f32, specs, omc: OMCConfig, round_index,
                       client_id):
    """Storage tree of one client's transport payload: PPQ-selected
    variables compressed under ``omc.fmt``, everything else f32.  Feeding it
    to :func:`repro.api.codecs.encode_payload` / ``payload_bytes_report``
    must reproduce :func:`repro.federated.accounting.client_upload_bytes`
    exactly (asserted in ``tests/test_engine.py``)."""
    if not omc.enabled:
        return trained_f32
    names = accounting.selected_names(trained_f32, specs, omc)
    if not names:
        return trained_f32
    mask = np.asarray(
        ppq_mask(omc.ppq_key(), round_index, client_id, len(names),
                 omc.quantize_fraction),
        bool,
    )
    index = {n: i for i, n in enumerate(names)}

    def f(path, spec, leaf):
        i = index.get(path_str(path))
        if i is None or not mask[i]:
            return leaf
        return compress_variable(
            leaf, omc.fmt, pvt=omc.pvt,
            batch_axes=n_stack_axes(spec, leaf), fast=True,
        )

    return jax.tree_util.tree_map_with_path(
        f, specs, trained_f32, is_leaf=lambda s: isinstance(s, ParamSpec)
    )
