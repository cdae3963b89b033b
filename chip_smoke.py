"""Bring-up smoke run of the main path on a TPU, in one process.

    python chip_smoke.py               # one chip: train, wire, serve
    python chip_smoke.py --four-chips  # four chips: the sharded round only

Default phases, each through the repo's own API at a published width, with
random weights made from a seed:

1. **train** — ``engine.run_training_vectorized`` on ``conformer_s``
   (103.5 M parameters), OMC S1E3M7, a seeded frame task, cohort 4,
   2 local steps, batch 8, 256 frames, 3 rounds; once unfused and once with
   ``fused_agg=True``.  The two must agree within the fused-vs-unfused
   tolerance of ``tests/test_engine.py``; the fused run must dispatch the
   compiled ``fused_aggregate`` kernel.
2. **wire** — ``api.codecs`` encode/decode of the trained storage: the
   digest must survive and the body must be ``payload_bytes_report``'s
   ``wire_bytes``.
3. **serve** — a ``ServeSession`` built from a ``qwen2.5-3b`` payload
   (3.09 B parameters, 6.17 GB of S1E3M7 codes): batched ``generate``
   calls, one ``hot_swap`` (which holds the old and the new storage at
   once), more calls.

``--four-chips`` runs only ``scale.hierarchy.run_round_sharded`` with the
population store's error-feedback rows placed over a 4-device
``("clients",)`` mesh, and compares it with the same round on one device.

The script exits non-zero, before any work, when JAX finds no TPU, and any
failed phase propagates its exception.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import codecs  # noqa: E402
from repro.api.session import ServeSession  # noqa: E402
from repro.compress import get_strategy  # noqa: E402
from repro.configs import conformer_s, qwen2_5_3b  # noqa: E402
from repro.core.omc import OMCConfig  # noqa: E402
from repro.core.store import decompress_tree  # noqa: E402
from repro.data.synthetic import make_frame_task  # noqa: E402
from repro.federated import engine, simulate  # noqa: E402
from repro.federated.cohort import CohortPlan  # noqa: E402
from repro.federated.state import compress_params  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_population_mesh  # noqa: E402
from repro.models import conformer as cf  # noqa: E402
from repro.models.registry import get_family  # noqa: E402
from repro.scale import (  # noqa: E402
    PopulationStore,
    ShardLayout,
    make_stream_fn,
    run_round_sharded,
)

OMC = OMCConfig.parse("S1E3M7")
# Fused vs unfused (tests/test_engine.py): one transport-quant step.
FUSED_MAX, FUSED_MEAN, LOSS_TOL = 6e-3, 1e-3, 1e-3
# Sharded vs one device (tests/test_scale.py, f32 reassociation only).
SHARD_MAX, SHARD_MEAN = 6e-3, 1e-4


def emit(phase: str, **rec) -> None:
    print(f"[{phase}] " + json.dumps(rec, sort_keys=True, default=str),
          flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (all phases)."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs

    def total(self) -> float:
        return self.seconds


def peak_bytes(device) -> int | None:
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def check_dispatch(op: str, backend: str = "pallas") -> dict:
    """Dispatch counts so far: ``op`` must have run, and every kernel must
    have taken ``backend`` (on the chip: never the ref or interpret one)."""
    counts = ops.dispatch_counts()
    need = f"{op}.{backend}"
    bad = [k for k in counts if not k.endswith(f".{backend}")]
    if bad or counts.get(need, 0) < 1:
        raise RuntimeError(f"kernel dispatch wrong: need {need}, got {counts}")
    return counts


def assert_trees_close(a_storage, b_storage, max_tol, mean_tol) -> dict:
    worst, mean = 0.0, 0.0
    for x, y in zip(jax.tree_util.tree_leaves(decompress_tree(a_storage)),
                    jax.tree_util.tree_leaves(decompress_tree(b_storage))):
        d = np.abs(np.asarray(x) - np.asarray(y))
        worst, mean = max(worst, float(d.max())), max(mean, float(d.mean()))
    if worst > max_tol or mean > mean_tol:
        raise AssertionError(f"trees differ: max {worst} (limit {max_tol}), "
                             f"mean {mean} (limit {mean_tol})")
    return dict(max_abs_diff=worst, max_leaf_mean_diff=mean)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def train_phase(cfg, *, fused: bool, clock: CompileClock, cohort: int = 4,
                local_steps: int = 2, batch: int = 8, frames: int = 256,
                rounds: int = 3, num_clients: int = 16, seed: int = 0):
    """Federated training through the cohort engine; returns
    ``(storage, history)`` and prints the run's numbers."""
    task = make_frame_task(d_in=cfg.d_in, n_classes=cfg.n_classes,
                           seq_len=frames, num_clients=num_clients, seed=seed)
    spec = engine.CohortSpec(CohortPlan(num_clients=num_clients,
                                        cohort_size=cohort))
    sim = simulate.SimConfig(local_steps=local_steps, client_lr=0.1)
    ends = []
    c0, t0 = clock.total(), time.perf_counter()
    # ``log`` runs after each round's loss is on the host, which waits for
    # the round program: consecutive stamps bound one round each.
    storage, hist = engine.run_training_vectorized(
        cf, cfg, OMC, sim, spec, lambda c, r, s: task.batch(c, r, s, batch),
        jax.random.PRNGKey(seed), num_rounds=rounds, eval_every=1,
        log=lambda _: ends.append(time.perf_counter()), fused_agg=fused,
    )
    jax.block_until_ready(storage)
    round_s = np.diff([t0] + ends).tolist()
    losses = [h["loss"] for h in hist]
    if not np.all(np.isfinite(losses)):
        raise FloatingPointError(f"non-finite losses {losses}")
    emit("train", fused=fused, params=cfg.param_count(), cohort=cohort,
         local_steps=local_steps, batch=batch, frames=frames,
         compile_s=clock.total() - c0, round_s=round_s,
         median_round_s=statistics.median(round_s[1:] or round_s),
         losses=losses, peak_bytes_in_use=peak_bytes(jax.devices()[0]),
         dispatch=ops.dispatch_counts())
    return storage, hist


def compare_fused(unfused, fused) -> dict:
    (u_storage, u_hist), (f_storage, f_hist) = unfused, fused
    for u, f in zip(u_hist, f_hist):
        for k in ("cohort", "dropped", "down_bytes", "up_bytes"):
            if u[k] != f[k]:
                raise AssertionError(f"round {u['round']} {k}: {u[k]} != {f[k]}")
        if abs(u["loss"] - f["loss"]) >= LOSS_TOL:
            raise AssertionError(f"round {u['round']} loss {u['loss']} vs "
                                 f"{f['loss']}")
    return assert_trees_close(u_storage, f_storage, FUSED_MAX, FUSED_MEAN)


def wire_phase(storage) -> dict:
    """Encode then decode a storage tree through the wire codec."""
    t0 = time.perf_counter()
    payload = codecs.encode_payload(storage)
    t1 = time.perf_counter()
    decoded, info = codecs.decode_payload(payload)
    jax.block_until_ready(decoded)
    t2 = time.perf_counter()
    report = codecs.payload_bytes_report(storage)
    want, got = codecs.tree_digest(storage), codecs.tree_digest(decoded)
    if got != want:
        raise AssertionError(f"wire digest {got:#x} != {want:#x}")
    if info.body_bytes != report["wire_bytes"]:
        raise AssertionError(f"body {info.body_bytes} B != wire_bytes "
                             f"{report['wire_bytes']} B")
    return dict(payload_bytes=len(payload), body_bytes=info.body_bytes,
                digest=f"{got:#010x}", encode_s=t1 - t0, decode_s=t2 - t1)


def serve_phase(arch, cfg, *, clock: CompileClock, batch: int = 4,
                prompt_len: int = 64, gen: int = 16, calls: int = 3,
                seed: int = 0) -> dict:
    """ServeSession from a payload: generate, hot_swap, generate."""
    family = get_family(arch.FAMILY)
    specs = family.param_specs(cfg)
    build = jax.jit(lambda k: compress_params(family.init(k, cfg), specs, OMC))

    def payload(i: int):
        # One storage on the device at a time: it is freed on return.
        storage = build(jax.random.PRNGKey(seed + i))
        return codecs.encode_payload(storage), codecs.tree_digest(storage)

    c0 = clock.total()
    # the served model and the one swapped in
    payloads, digests = zip(*(payload(i) for i in range(2)))
    sess = ServeSession.from_payload(family, cfg, payloads[0])
    if codecs.tree_digest(sess.storage) != digests[0]:
        raise AssertionError("served weights differ from the payload's")
    prompt = dict(tokens=jax.random.randint(jax.random.PRNGKey(seed + 2),
                                            (batch, prompt_len), 0, cfg.vocab))
    logits = sess.prefill(prompt, sess.init_cache(batch, prompt_len + gen))[1]
    if logits.shape != (batch, 1, cfg.vocab) or not bool(
            jnp.isfinite(logits).all()):
        raise FloatingPointError("prefill logits malformed or non-finite")
    del logits

    def generate() -> float:
        t = time.perf_counter()
        _, toks = sess.generate(prompt, sess.init_cache(batch, prompt_len + gen),
                                gen)
        toks = np.asarray(jax.block_until_ready(toks))
        dt = time.perf_counter() - t
        if toks.shape != (batch, gen) or toks.min() < 0 or toks.max() >= cfg.vocab:
            raise AssertionError(f"bad tokens {toks.shape} "
                                 f"[{toks.min()}, {toks.max()}]")
        return dt

    before = [generate() for _ in range(calls)]
    sess.hot_swap(payloads[1])
    if codecs.tree_digest(sess.storage) != digests[1]:
        raise AssertionError("hot-swapped weights differ from the payload's")
    after = [generate() for _ in range(calls)]
    return dict(arch=arch.ID, n_layers=cfg.n_layers, d_model=cfg.d_model,
                payload_bytes=len(payloads[0]), batch=batch,
                prompt_len=prompt_len, tokens_per_call=batch * gen,
                tokens_generated=batch * gen * 2 * calls,
                call_s_before_swap=before, call_s_after_swap=after,
                swap_stall_s=sess.swap_ms[-1] / 1e3,
                compile_s=clock.total() - c0,
                peak_bytes_in_use=peak_bytes(jax.devices()[0]))


def sharded_round(cfg, num_shards: int, *, num_clients: int = 8,
                  cohort: int = 8, capacity: int = 2, local_steps: int = 2,
                  batch: int = 8, frames: int = 256, seed: int = 0):
    """One ``run_round_sharded`` round whose population store (top-k error
    feedback rows) is placed over a ``num_shards``-device mesh.

    Returns ``(storage, metrics, store, placement)``; ``placement`` lists
    ``(shard, clients, device id)`` for each chunk program and the bytes in
    use on each device with the store rows placed.
    """
    mesh = make_population_mesh(num_shards)
    specs = cf.param_specs(cfg)
    key = jax.random.PRNGKey(seed)
    params = cf.init(key, cfg)
    store = PopulationStore(ShardLayout(num_clients, num_shards))
    store.init_ef(params, specs, OMC)
    rows = store.device_ef(mesh)  # noqa: F841 - held while the round runs
    storage = compress_params(params, specs, OMC)
    del params
    task = make_frame_task(d_in=cfg.d_in, n_classes=cfg.n_classes,
                           seq_len=frames, num_clients=num_clients, seed=seed)
    data_fn = lambda c, r, s: task.batch(c, r, s, batch)  # noqa: E731
    sim = simulate.SimConfig(local_steps=local_steps, client_lr=0.1)
    strategy = get_strategy("topk")
    chunk_fn = make_stream_fn(cf, cfg, specs, OMC, sim, data_fn, capacity,
                              strategy=strategy)
    devices_used, chunks = [], []

    def stream_fn(*args):
        out = chunk_fn(*args)
        leaf = jax.tree_util.tree_leaves(out)[0]
        devices_used.append(sorted(d.id for d in leaf.devices()))
        return out

    storage, metrics = run_round_sharded(
        cf, cfg, specs, OMC, sim, storage, data_fn,
        CohortPlan(num_clients=num_clients, cohort_size=cohort),
        store.layout, 0, jax.random.fold_in(key, 1), capacity=capacity,
        stream_fn=stream_fn, strategy=strategy, store=store,
        on_chunk=lambda shard, n, _: chunks.append(
            dict(shard=shard, clients=n, devices=devices_used[-1])),
    )
    jax.block_until_ready(storage)
    in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
              for d in mesh.devices.flat}
    return storage, metrics, store, dict(chunks=chunks, bytes_in_use=in_use)


def four_chip_phase(cfg, **sizes) -> dict:
    """The sharded round over four devices against the same round on one."""
    one = sharded_round(cfg, 1, **sizes)
    four = sharded_round(cfg, 4, **sizes)
    for k in ("cohort", "dropped"):
        if one[1][k] != four[1][k]:
            raise AssertionError(f"{k}: {one[1][k]} != {four[1][k]}")
    if abs(one[1]["loss"] - four[1]["loss"]) >= LOSS_TOL:
        raise AssertionError(f"loss {one[1]['loss']} vs {four[1]['loss']}")
    close = assert_trees_close(one[0], four[0], SHARD_MAX, SHARD_MEAN)
    ids = np.arange(one[2].layout.num_clients)
    ef_diff = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
                  for a, b in zip(one[2].gather_ef(ids).values(),
                                  four[2].gather_ef(ids).values()))
    return dict(losses=[one[1]["loss"], four[1]["loss"]], ef_max_diff=ef_diff,
                one_device=one[3], four_devices=four[3], **close)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded round on four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    clock = CompileClock()
    emit("device", platform=dev.platform, kind=dev.device_kind,
         count=len(devices), jax=jax.__version__)

    if args.four_chips:
        emit("four_chips", **four_chip_phase(conformer_s.config()))
    else:
        cfg = conformer_s.config()
        unfused = train_phase(cfg, fused=False, clock=clock)
        fused = train_phase(cfg, fused=True, clock=clock)
        emit("train_compare", **compare_fused(unfused, fused),
             dispatch=check_dispatch("fused_aggregate"))
        wire = wire_phase(fused[0])
        counts = check_dispatch("pack_bits")
        check_dispatch("unpack_bits")
        emit("wire", **wire, pack_bits_pallas=counts["pack_bits.pallas"],
             unpack_bits_pallas=counts["unpack_bits.pallas"])
        emit("serve", **serve_phase(qwen2_5_3b, qwen2_5_3b.config(),
                                    clock=clock),
             dispatch=check_dispatch("unpack_bits"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
