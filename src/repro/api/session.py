"""FL and serving sessions over the wire codec (DESIGN.md §7).

``FLSession`` is the server side of the paper's training loop expressed at
the client/server boundary: the server state is *compressed at rest*
(``CompressedVariable`` leaves), each round it hands out a wire payload of
that state (full, or sparse-delta against the previous round for clients
that held it), ingests client uploads (themselves wire payloads, usually
delta-encoded against the download), aggregates with cohort-aware weighting
(:mod:`repro.federated.cohort` semantics — failures and stragglers drop
reports), and re-compresses.  No persistent f32 master exists between
rounds, matching :mod:`repro.federated.simulate` numerics.

``ServeSession`` is the inference side: batched prefill/decode over the
compressed weights via ``make_serve_fns``, with ``hot_swap`` ingesting a new
round's payload *without recompiling* — the storage pytree keeps its
treedef/shapes/dtypes, so the jitted functions are reused as-is.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.omc import OMCConfig
from repro.core.store import decompress_tree
from repro.federated import cohort as cohort_lib
from repro.federated.async_engine import flush_weights
from repro.federated.round import make_serve_fns
from repro.federated.state import compress_params, state_bytes_report
from repro.obs import null_span

from . import codecs


def _resolve_strategy(strategy):
    """Accept a CompressionStrategy, a registry name, or None."""
    if strategy is None or not isinstance(strategy, str):
        return strategy
    from repro.compress import get_strategy

    return get_strategy(strategy)


def _reported_model(tree, base_storage, strategy):
    """Server-side view of one decoded upload (DESIGN.md §12).

    ``strategy=None``: the classic OMC path — dequantize the report.
    Upload-only strategies ship the client's *update*; reconstruct
    ``base + update`` so sparse frames (zeros off-support) never shrink
    the aggregated model.  Dense strategies ship the full model."""
    from repro.compress import decode_tree

    if strategy is None:
        return decompress_tree(tree)
    decoded = decode_tree(tree)
    if not strategy.upload_only:
        return decoded
    base_f32 = decompress_tree(base_storage)
    return jax.tree_util.tree_map(jnp.add, base_f32, decoded)


@dataclasses.dataclass
class RoundTicket:
    """What the server hands a transport for one round of downloads.

    ``profiles`` maps each invited client to its device-profile name
    (heterogeneous-tier cohorts, DESIGN.md §9): the download payload is the
    same server-format model for every tier, but the transport uses the
    profile to anticipate the client's upload format and the engine's wire
    accounting budgets per-tier bytes from it."""

    round_index: int
    client_ids: List[int]
    payload: bytes  # full payload (new / fallen-behind clients)
    delta_payload: Optional[bytes]  # vs the previous round's model, if any
    delta_base_digest: int = 0  # tree_digest the delta applies to (0: none)
    issued_bytes: List[int] = dataclasses.field(default_factory=list)
    issued_delta: int = 0  # how many clients actually took the delta
    profiles: Dict[int, str] = dataclasses.field(default_factory=dict)

    def payload_for(self, *, has_previous_round: bool) -> bytes:
        """Pick the download for one client and record its size (the
        session folds ``issued_bytes`` into traffic at close_round)."""
        if has_previous_round and self.delta_payload is not None:
            blob = self.delta_payload
            self.issued_delta += 1
        else:
            blob = self.payload
        self.issued_bytes.append(len(blob))
        return blob


@dataclasses.dataclass
class AsyncTicket:
    """A version-stamped download handed to one checking-in client.

    The async counterpart of :class:`RoundTicket` (DESIGN.md §10): instead
    of a per-round cohort broadcast, each ticket belongs to exactly one
    client and records the ``server_version`` whose state it carries — the
    upload that eventually comes back is decoded against *that* version's
    storage and its staleness is ``current_version - server_version``.
    ``delta_payload`` (vs the version the client said it holds) is taken
    only when the client's digest matches; the session folds the actually
    issued bytes into traffic at ingestion.
    """

    client_id: int
    server_version: int
    payload: bytes  # full state at server_version
    delta_payload: Optional[bytes] = None  # vs the client's held version
    delta_base_digest: int = 0
    issued_bytes: int = 0
    took_delta: bool = False

    def payload_for(self, *, held_digest: int = 0) -> bytes:
        """Pick delta when the client verifiably holds the base, else full."""
        if (self.delta_payload is not None
                and held_digest == self.delta_base_digest):
            blob = self.delta_payload
            self.took_delta = True
        else:
            blob = self.payload
        self.issued_bytes = len(blob)
        return blob


class FLSession:
    """Server-side federated session over compressed wire payloads.

    Lifecycle per round::

        ticket = sess.begin_round()            # cohort ids + download payload
        for cid in ticket.client_ids:          # transport delivers payloads,
            blob = client_train(...)           # clients train and upload
            sess.ingest(cid, blob)
        metrics = sess.close_round()           # aggregate + re-compress

    ``ingest`` accepts uploads delta-encoded against this round's download
    (the normal case) or full payloads; ``close_round`` FedAvg-aggregates
    whatever reports arrived (report-goal semantics: a partial cohort is
    fine) and applies the server update with learning rate ``server_lr``.

    ``strategy`` (a :class:`repro.compress.CompressionStrategy` or registry
    name) switches the *upload* direction to a zoo compressor (DESIGN.md
    §12): clients send strategy-encoded payloads — for upload-only
    strategies the payload carries the client's *update* and ``ingest``
    reconstructs ``download + update`` — while downloads stay the
    compressed-at-rest OMC state either way.
    """

    def __init__(
        self,
        family,
        cfg,
        omc: OMCConfig,
        *,
        plan: Optional[cohort_lib.CohortPlan] = None,
        server_lr: float = 1.0,
        seed: int = 0,
        init_params=None,
        profile_fn: Optional[Callable[[int], str]] = None,
        strategy=None,
        obs=None,
    ):
        self.family = family
        self.cfg = cfg
        self.omc = omc
        self.plan = plan
        self.strategy = _resolve_strategy(strategy)
        # telemetry (DESIGN.md §15): payload encode/decode + flush spans;
        # obs=None records nothing and changes nothing
        self.obs = obs
        # client id -> device-profile name (engine.PROFILES keys); stamped
        # onto every RoundTicket so transports know each client's tier
        self.profile_fn = profile_fn
        self.server_lr = float(server_lr)
        self.specs = family.param_specs(cfg)
        key = jax.random.PRNGKey(seed)
        params = family.init(key, cfg) if init_params is None else init_params
        self.storage = (
            compress_params(params, self.specs, omc) if omc.enabled else params
        )
        self._prev_storage = None  # round r-1 model: delta base for downloads
        self._cohort_key = jax.random.fold_in(key, 0xC047)
        self.round_index = 0
        self._reports: Dict[int, Any] = {}
        self._ticket: Optional[RoundTicket] = None
        # f32 baseline depends only on leaf shapes — constant for the session
        self._fp32_bytes = state_bytes_report(self.storage)["fp32_bytes"]
        self.traffic = dict(down_bytes=0, up_bytes=0, down_fp32_bytes=0,
                            up_fp32_bytes=0)

    # -- payload side -------------------------------------------------------

    def server_payload(self, *, delta: bool = False) -> bytes:
        """Wire payload of the current server model (optionally vs round-1)."""
        base = self._prev_storage if delta else None
        with null_span(self.obs, "encode_payload", delta=delta) as a:
            blob = codecs.encode_payload(
                self.storage, base=base, round_index=self.round_index
            )
            a["bytes"] = len(blob)
        return blob

    def begin_round(self) -> RoundTicket:
        """Sample the round's cohort and build its download payload(s)."""
        if self._ticket is not None:
            raise RuntimeError("round already open; call close_round() first")
        if self.plan is not None:
            ids = [
                int(i)
                for i in cohort_lib.sample_cohort(
                    self._cohort_key, self.plan, self.round_index
                )
            ]
        else:
            ids = [0]
        full = self.server_payload()
        delta = (
            self.server_payload(delta=True) if self._prev_storage is not None
            else None
        )
        self._ticket = RoundTicket(
            self.round_index, ids, full, delta,
            delta_base_digest=(
                codecs.header_base_digest(delta) if delta is not None else 0
            ),
            profiles=(
                {cid: self.profile_fn(cid) for cid in ids}
                if self.profile_fn is not None else {}
            ),
        )
        self._reports = {}
        return self._ticket

    def ingest(self, client_id: int, blob: bytes) -> codecs.PayloadInfo:
        """Accept one client upload (delta vs this round's download, or full)."""
        if self._ticket is None:
            raise RuntimeError("no open round; call begin_round() first")
        if client_id not in self._ticket.client_ids:
            raise KeyError(f"client {client_id} is not in this round's cohort")
        with null_span(self.obs, "decode_payload", client=client_id,
                       bytes=len(blob)):
            tree, info = codecs.decode_payload(blob, base=self.storage)
        self._reports[client_id] = _reported_model(
            tree, self.storage, self.strategy
        )
        self.traffic["up_bytes"] += info.total_bytes
        self.traffic["up_fp32_bytes"] += self._fp32_bytes
        return info

    def close_round(self) -> Dict[str, Any]:
        """Aggregate the received reports, apply the server step, re-compress."""
        if self._ticket is None:
            raise RuntimeError("no open round; call begin_round() first")
        if not self._reports:
            raise RuntimeError("round closed with zero reports")
        models = list(self._reports.values())
        weights = jnp.ones((len(models),), jnp.float32)
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *models)
        mean_model = cohort_lib.aggregate_weighted(stacked, weights)
        server_f32 = decompress_tree(self.storage)
        new_f32 = jax.tree_util.tree_map(
            lambda old, new: old + self.server_lr * (new - old),
            server_f32,
            mean_model,
        )
        self._prev_storage = self.storage
        self.storage = (
            compress_params(new_f32, self.specs, self.omc)
            if self.omc.enabled
            else new_f32
        )
        self.traffic["down_bytes"] += sum(self._ticket.issued_bytes)
        self.traffic["down_fp32_bytes"] += (
            self._fp32_bytes * len(self._ticket.issued_bytes)
        )
        metrics = dict(
            round=self.round_index,
            reports=len(models),
            invited=len(self._ticket.client_ids),
            **{k: int(v) for k, v in self.traffic.items()},
        )
        self.round_index += 1
        self._ticket = None
        self._reports = {}
        return metrics

    # -- async (buffered, version-stamped) side -----------------------------

    def enable_async(self, buffer_goal: int, *, decay: float = 0.0,
                     decay_mode: str = "poly",
                     delta_horizon: int = 4) -> None:
        """Switch the session to the non-barrier protocol (DESIGN.md §10).

        ``buffer_goal`` (K) — aggregate whenever K uploads accumulate —
        passes the same validation gate as the sync report goal.  After
        this, drive the session with :meth:`checkin` / :meth:`ingest_async`
        instead of the begin/ingest/close round cycle; the server applies a
        staleness-weighted FedBuff step at each flush and bumps
        ``server_version``.  ``delta_horizon`` bounds how many past version
        storages are kept as delta bases for returning clients (versions a
        pending ticket still references are always kept — uploads decode
        against their ticket's exact base).
        """
        cohort_lib.validate_report_goal(
            buffer_goal,
            self.plan.cohort_size if self.plan is not None else buffer_goal,
            what="buffer_goal",
        )
        if self._ticket is not None:
            raise RuntimeError("close the open sync round before enable_async")
        self.async_cfg = dict(buffer_goal=int(buffer_goal), decay=float(decay),
                              decay_mode=decay_mode,
                              delta_horizon=int(delta_horizon))
        self.server_version = 0
        self._full_cache: Optional[Tuple[int, bytes]] = None
        self._version_storages: Dict[int, Any] = {0: self.storage}
        self._async_pending: Dict[int, AsyncTicket] = {}
        self._async_buffer: List[Tuple[int, int, Any]] = []  # (cid, base, f32)
        self.async_history: List[Dict[str, Any]] = []

    def checkin(self, client_id: int,
                held_version: Optional[int] = None) -> AsyncTicket:
        """Issue one client a version-stamped download ticket.

        The full payload always carries the *current* state; if the client
        reports a ``held_version`` still in the delta window, a sparse
        delta against that version's storage rides along (digest-verified
        at the client, exactly like sync :class:`RoundTicket` routing).
        """
        if not hasattr(self, "async_cfg"):
            raise RuntimeError("call enable_async() first")
        if client_id in self._async_pending:
            raise RuntimeError(f"client {client_id} already has an open ticket")
        # the full payload is identical for every check-in under one server
        # version — encode it once per version, not once per client
        if self._full_cache is None or self._full_cache[0] != self.server_version:
            self._full_cache = (self.server_version, codecs.encode_payload(
                self.storage, round_index=self.server_version))
        full = self._full_cache[1]
        delta = None
        digest = 0
        base = (self._version_storages.get(held_version)
                if held_version is not None else None)
        if base is not None:
            delta = codecs.encode_payload(self.storage, base=base,
                                          round_index=self.server_version)
            digest = codecs.header_base_digest(delta)
        ticket = AsyncTicket(client_id, self.server_version, full, delta,
                             delta_base_digest=digest)
        self._async_pending[client_id] = ticket
        return ticket

    def ingest_async(self, client_id: int, blob: bytes) -> codecs.PayloadInfo:
        """Accept one upload against its ticket's base version; flush at K.

        The upload is decoded against the storage *at the ticket's version*
        (kept alive until the upload lands), so a stale client's delta
        still decodes exactly; its staleness is charged at aggregation
        time through the session's decay weights.
        """
        ticket = self._async_pending.pop(client_id, None)
        if ticket is None:
            raise KeyError(f"client {client_id} has no open ticket")
        base = self._version_storages[ticket.server_version]
        with null_span(self.obs, "decode_payload", client=client_id,
                       bytes=len(blob)):
            tree, info = codecs.decode_payload(blob, base=base)
        self._async_buffer.append(
            (client_id, ticket.server_version,
             _reported_model(tree, base, self.strategy))
        )
        self.traffic["up_bytes"] += info.total_bytes
        self.traffic["up_fp32_bytes"] += self._fp32_bytes
        self.traffic["down_bytes"] += ticket.issued_bytes
        self.traffic["down_fp32_bytes"] += self._fp32_bytes
        if len(self._async_buffer) >= self.async_cfg["buffer_goal"]:
            self._flush_async()
        return info

    def _flush_async(self) -> None:
        with null_span(self.obs, "flush",
                       version=getattr(self, "server_version", 0)):
            self._flush_async_inner()

    def _flush_async_inner(self) -> None:
        entries = self._async_buffer[: self.async_cfg["buffer_goal"]]
        self._async_buffer = self._async_buffer[self.async_cfg["buffer_goal"]:]
        staleness = jnp.asarray(
            [self.server_version - base for _, base, _ in entries],
            jnp.float32,
        )
        w = flush_weights(staleness, self.async_cfg["decay"],
                          self.async_cfg["decay_mode"])
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[m for _, _, m in entries]
        )
        mean_model = cohort_lib.aggregate_weighted(stacked, w)
        server_f32 = decompress_tree(self.storage)
        new_f32 = jax.tree_util.tree_map(
            lambda old, new: old + self.server_lr * (new - old),
            server_f32, mean_model,
        )
        self.storage = (
            compress_params(new_f32, self.specs, self.omc)
            if self.omc.enabled else new_f32
        )
        self.server_version += 1
        self._version_storages[self.server_version] = self.storage
        self._gc_version_storages()
        self.async_history.append(dict(
            version=self.server_version,
            buffer=len(entries),
            staleness_max=int(staleness.max()),
            **{k: int(v) for k, v in self.traffic.items()},
        ))

    def _gc_version_storages(self) -> None:
        keep = {t.server_version for t in self._async_pending.values()}
        keep.add(self.server_version)
        horizon = self.server_version - self.async_cfg["delta_horizon"]
        for v in [v for v in self._version_storages
                  if v not in keep and v < horizon]:
            del self._version_storages[v]


class FLClient:
    """Loopback client: decode download, train, upload a delta payload.

    ``train_fn(params_f32, client_id, round_index) -> params_f32`` is the
    local optimization (the demo uses a few SGD steps on the client's
    synthetic shard).  The client caches the last model it decoded and takes
    the delta download only when the delta's base digest matches that cache
    (a cohort-skipped client holds a stale model and falls back to the full
    payload — never a silent wrong-base decode).  The upload is
    re-compressed under the session policy (transport compression, paper §2)
    and delta-encoded against the *received* model, so unchanged codes cost
    ~0 wire bytes.

    With a ``strategy`` (matching the session's — DESIGN.md §12) the upload
    is strategy-encoded instead: dense strategies send the full trained
    model, upload-only strategies send the *update* ``trained - received``
    — with a host-side error-feedback residual carried across this
    client's rounds when the strategy opts in (the residual is exactly
    ``compensated - decode(encode(compensated))``, so the client and the
    server can never disagree about what was dropped).
    """

    def __init__(self, client_id: int, family, cfg, omc: OMCConfig,
                 train_fn: Callable[[Any, int, int], Any], strategy=None):
        self.client_id = client_id
        self.specs = family.param_specs(cfg)
        self.omc = omc
        self.train_fn = train_fn
        self.strategy = _resolve_strategy(strategy)
        self._cache = None  # last decoded download tree (this client's model)
        self._cache_digest = 0
        self._residual = None  # error-feedback accumulator (EF strategies)

    def run_round(self, ticket: RoundTicket) -> bytes:
        use_delta = (
            ticket.delta_payload is not None
            and self._cache is not None
            and ticket.delta_base_digest == self._cache_digest
        )
        blob = ticket.payload_for(has_previous_round=use_delta)
        tree, _ = codecs.decode_payload(
            blob, base=self._cache if use_delta else None
        )
        self._cache = tree
        self._cache_digest = codecs.tree_digest(tree)
        params = decompress_tree(tree)
        trained = self.train_fn(params, self.client_id, ticket.round_index)
        if self.strategy is not None:
            return self._strategy_upload(params, trained, ticket.round_index)
        upload_tree = (
            compress_params(trained, self.specs, self.omc)
            if self.omc.enabled
            else trained
        )
        return codecs.encode_payload(
            upload_tree, base=tree, round_index=ticket.round_index
        )

    def _strategy_upload(self, received, trained, round_index: int) -> bytes:
        from repro.compress import decode_tree, encode_tree

        tmap = jax.tree_util.tree_map
        if not self.strategy.upload_only:
            upload_tree = encode_tree(self.strategy, trained, self.omc,
                                      self.specs)
            return codecs.encode_payload(upload_tree,
                                         round_index=round_index)
        comp = tmap(jnp.subtract, trained, received)
        if self.strategy.error_feedback:
            if self._residual is None:
                self._residual = tmap(jnp.zeros_like, comp)
            comp = tmap(jnp.add, comp, self._residual)
        upload_tree = encode_tree(self.strategy, comp, self.omc, self.specs)
        if self.strategy.error_feedback:
            self._residual = tmap(jnp.subtract, comp,
                                  decode_tree(upload_tree))
        return codecs.encode_payload(upload_tree, round_index=round_index)


class ServeSession:
    """Batched decode over compressed weights with payload hot-swap.

    Wraps ``make_serve_fns``: prefill/decode are jitted once; ``hot_swap``
    replaces the storage tree from a wire payload between rounds without
    touching the compiled functions (same treedef/shapes/dtypes).  Both
    consume the cache they are given (donated): its buffers become the
    returned cache's, so one cache exists at a time, and the old one must
    not be used again.
    """

    def __init__(self, family, cfg, storage, compute_dtype=jnp.float32,
                 obs=None):
        self.family = family
        self.cfg = cfg
        self.storage = storage
        self.obs = obs
        prefill_fn, decode_fn = make_serve_fns(family, cfg, compute_dtype)
        # keep_unused: a prefill that only reads its cache's shape still
        # receives, and so frees, the buffers it was given
        self._prefill = jax.jit(prefill_fn, donate_argnums=2, keep_unused=True)
        self._decode = jax.jit(decode_fn, donate_argnums=1, keep_unused=True)
        self.swaps = 0
        self.queries = 0
        # per-swap wall ms (decode payload + materialize the new storage) —
        # the serve-under-swap driver (repro.scale.serve_driver) reads these
        self.swap_ms: List[float] = []

    @classmethod
    def from_payload(cls, family, cfg, payload: bytes, **kw) -> "ServeSession":
        storage, _ = codecs.decode_payload(payload)
        return cls(family, cfg, storage, **kw)

    def hot_swap(self, payload: bytes) -> codecs.PayloadInfo:
        """Ingest a new round's model; delta payloads apply against the
        currently-served tree (digest-verified — a wrong-round payload
        raises rather than corrupting the served weights).  Swap wall time
        (decode + materialized new storage) lands in ``swap_ms``."""
        import time

        t0 = time.perf_counter()
        with null_span(self.obs, "hot_swap", swap=int(self.swaps),
                       bytes=len(payload)):
            self.storage, info = codecs.decode_payload(
                payload, base=self.storage
            )
            jax.block_until_ready(
                [l for l in jax.tree_util.tree_leaves(self.storage)
                 if hasattr(l, "block_until_ready")]
            )
        self.swaps += 1
        self.swap_ms.append((time.perf_counter() - t0) * 1e3)
        return info

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        return self.family.init_decode_state(self.cfg, batch, max_len,
                                             dtype=dtype)

    def prefill(self, batch, cache):
        with null_span(self.obs, "serve.prefill"):
            return self._prefill(self.storage, batch, cache)

    def decode_step(self, cache, tokens):
        with null_span(self.obs, "serve.decode_step"):
            return self._decode(self.storage, cache, tokens)

    def generate(self, batch, cache, steps: int, *,
                 sample: Callable[[jax.Array], jax.Array] = None):
        """Greedy (or ``sample``-driven) generation; returns (cache, tokens)."""
        if steps < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        pick = sample or (lambda logits: jnp.argmax(logits, axis=-1))
        cache, logits = self.prefill(batch, cache)
        tok = pick(logits[:, -1])[:, None]
        out = [tok]
        for _ in range(steps - 1):
            cache, logits = self.decode_step(cache, tok)
            tok = pick(logits[:, -1])[:, None]
            out.append(tok)
        self.queries += 1
        return cache, jnp.concatenate(out, axis=1)

    def serve_stats(self) -> Dict[str, Any]:
        """Swap/query telemetry for serve-under-swap reporting."""
        return dict(
            swaps=int(self.swaps),
            queries=int(self.queries),
            swap_ms_mean=(float(jnp.mean(jnp.asarray(self.swap_ms)))
                          if self.swap_ms else 0.0),
            swap_ms_max=(float(max(self.swap_ms)) if self.swap_ms else 0.0),
        )
