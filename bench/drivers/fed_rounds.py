"""Federated rounds through the cohort engine (``engine.run_round_vectorized``).

Set-up makes the weights from the seed on the device, compresses them with
the program, builds the round program once and runs the first
``checked_rounds`` rounds through the same call the window uses: those are
compared with the reference.  The window then runs whole rounds until its
seconds are spent; the rate is the client samples of those rounds over the
time from the first round's start to the last round's end.

Client data is drawn inside the round program: frames are standard normal,
labels uniform over the classes, and every (client, round, step) has its own
rows.  The seed enters through the weights, the cohort key and the first
round's index (a number below 2**20 drawn from the seed), never as a
constant of a compiled program, so every seed runs the same programs and
only the first run of a checkout compiles them.
"""

from __future__ import annotations

import functools
import importlib
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from harness import counters
from reference import fedavg, omc as ref_omc, weights

from . import common


DATA_KEY = 0x5EED


def first_round(seed: int) -> int:
    return zlib.crc32(str(int(seed)).encode()) % (1 << 20)


def make_data_fn(d_in: int, n_classes: int, frames: int, batch: int):
    def data_fn(client_id, round_index, step):
        k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(DATA_KEY), client_id), round_index), step)
        kx, ky = jax.random.split(k)
        return dict(
            frames=jax.random.normal(kx, (batch, frames, d_in), jnp.float32),
            labels=jax.random.randint(ky, (batch, frames), 0, n_classes, jnp.int32))
    return data_fn


class Driver:
    def __init__(self, cell, seed: int):
        self.cell, self.seed = cell, seed
        self.config, self.traffic = cell.config, cell.traffic
        self.reference = importlib.import_module(f"reference.{self.config['reference']}")
        self.layout = self.reference.layout(self.config)
        self.fmt = ref_omc.Fmt(self.config["omc"]["format"])
        key = weights.seed_key(seed)
        self.k_weights, self.k_cohort = (jax.random.fold_in(key, i) for i in range(2))
        self.round0 = first_round(seed)
        t = self.traffic
        self.data_fn = make_data_fn(self.config["d_in"],
                                    self.config["n_classes"], t["frames"], t["batch"])

    # -- the program -----------------------------------------------------

    def setup(self, prior=None):
        """Build the program and run the checked rounds; ``prior`` (a driver
        of the same cell set up before, in this process) lends its compiled
        round program."""
        from repro.core.omc import OMCConfig
        from repro.federated import engine, simulate
        from repro.federated.cohort import CohortPlan
        from repro.federated.state import compress_params

        t, o = self.traffic, self.config["omc"]
        self.engine = engine
        self.family, self.cfg = common.program_model(self.config)
        self.omc = OMCConfig.parse(o["format"], pvt=o["pvt"],
                                   quantize_fraction=o["quantize_fraction"],
                                   ppq_seed=o["ppq_seed"])
        self.specs = self.family.param_specs(self.cfg)
        self.spec = engine.CohortSpec(CohortPlan(num_clients=t["population"],
                                                 cohort_size=t["cohort"]))
        self.sim = simulate.SimConfig(local_steps=t["local_steps"],
                                      client_lr=t["client_lr"],
                                      server_lr=t["server_lr"])
        t0 = time.perf_counter()
        build = jax.jit(lambda k: compress_params(weights.init(k, self.layout),
                                                  self.specs, self.omc))
        self.storage = jax.block_until_ready(build(self.k_weights))
        self.sizes = self.selected_sizes()  # traces the init: not in the window
        t1 = time.perf_counter()
        self.round_fn = prior.round_fn if prior is not None else engine.make_round_fn(
            self.family, self.cfg, self.specs, self.omc, self.sim, self.spec,
            self.data_fn, fused_agg=t["fused_agg"])
        self.round = self.round0
        self.snapshots = [self.storage]
        self.losses = []
        for _ in range(t["checked_rounds"]):
            self.losses.append(self._round()["loss"])
            self.snapshots.append(self.storage)
        self.snapshots = [self.snapshots[0], self.snapshots[1], self.snapshots[-1]]
        self.phases = dict(weights=t1 - t0, checked_rounds=time.perf_counter() - t1)

    def _round(self):
        self.storage, m = self.engine.run_round_vectorized(
            self.family, self.cfg, self.specs, self.omc, self.sim, self.storage,
            self.data_fn, self.spec, self.round, self.k_cohort,
            round_fn=self.round_fn, fused_agg=self.traffic["fused_agg"])
        self.round += 1
        return m

    def window(self, seconds: float):
        t = self.traffic
        alive = rounds = 0
        start = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.round"):
                m = self._round()
            alive += m["cohort"]
            rounds += 1
            elapsed = time.perf_counter() - start
            if elapsed >= seconds:
                break
        samples = alive * t["local_steps"] * t["batch"]
        return dict(metrics=dict(train_samples_per_s=samples / elapsed),
                    attempted=rounds * t["cohort"], failed=rounds * t["cohort"] - alive,
                    counts=dict(rounds=rounds, samples=samples, seconds=elapsed,
                                cohort=t["cohort"], frames=t["frames"],
                                params=self.reference.param_count(self.config),
                                selected_sizes=self.sizes,
                                container_bytes=counters.container_bytes(self.fmt.bits)))

    def selected_sizes(self):
        tree = jax.eval_shape(functools.partial(weights.init, layout=self.layout),
                              self.k_weights)
        sel = set(fedavg.selection(tree, self.layout))
        return [int(np.prod(l.shape)) for p, l in
                zip(fedavg.paths(tree), jax.tree_util.tree_leaves(tree)) if p in sel]

    def release(self):
        self.prog = [self.decode(s) for s in self.snapshots]
        del self.storage, self.snapshots

    def decode(self, storage):
        """Program storage -> dequantized values, read with the reference decoder."""
        def f(leaf):
            if hasattr(leaf, "codes"):
                return ref_omc.decode_codes(leaf.codes, self.fmt) * leaf.s + leaf.b
            return leaf
        return jax.tree_util.tree_map(f, storage, is_leaf=lambda x: hasattr(x, "codes"))

    # -- the reference ---------------------------------------------------

    def reference_rounds(self, dtype=jnp.float32, half_batch: bool = False):
        """``(losses, [W0, W1, W_last])`` of the reference from the seed, its
        matmuls at the configuration's precision; ``half_batch`` plants a
        fault: the loss of half of each batch."""
        plain = dtype == jnp.float32 and not half_batch
        if plain and getattr(self, "_reference", None) is not None:
            return self._reference
        t, o = self.traffic, self.config["omc"]
        tree = weights.init(self.k_weights, self.layout)
        w = fedavg.store(tree, self.layout, self.fmt)
        loss = functools.partial(self.reference.loss, self.config)
        if half_batch:
            full = loss
            loss = lambda p, b: full(p, {k: v[: t["batch"] // 2] for k, v in b.items()})  # noqa: E731
        rnd = fedavg.Round(loss,
                           self.layout, w, fmt=self.fmt,
                           fraction=o["quantize_fraction"], ppq_seed=o["ppq_seed"],
                           local_steps=t["local_steps"], client_lr=t["client_lr"],
                           server_lr=t["server_lr"], data_fn=self.data_fn, dtype=dtype,
                           precision=self.config["matmul_precision"])
        states, losses = [w], []
        for r in range(self.round0, self.round0 + t["checked_rounds"]):
            ids = jax.random.permutation(jax.random.fold_in(self.k_cohort, r),
                                         t["population"])[: t["cohort"]]
            w, loss = rnd(w, ids, r)
            losses.append(loss)
            states.append(w)
        out = losses, [states[0], states[1], states[-1]]
        if plain:
            self._reference = out
        return out

    def compare(self, losses, states, ref_losses, ref_states):
        """The numbers ``correct`` may be decided on, side against reference.
        The round's loss (``loss_gap``: the largest relative gap over the
        checked rounds); per leaf, the gap of the update's norm after the
        first round (``first_update_gap``) and of the change after the
        checked rounds (``change_gap``), ``|‖side‖−‖ref‖|`` over the
        reference's norm or the median leaf's, if larger; and the same leaves'
        norm of the difference, ``‖side−ref‖`` over the same
        (``first_update_diff``, ``change_diff``), which sees the update's
        direction.  Each by the worst leaf, and with ``.median`` by the median
        leaf.  Every leaf's norms and the losses go to ``self.detail``."""
        first_ref = fedavg.diff(ref_states[1], ref_states[0])
        first = fedavg.diff(states[1], states[0])
        change_ref = fedavg.diff(ref_states[2], ref_states[0])
        change = fedavg.diff(states[2], states[0])
        keep = fedavg.moved(first_ref)
        per_leaf = dict(
            first_update_gap=fedavg.norm_gaps(first, first_ref, keep),
            change_gap=fedavg.norm_gaps(change, change_ref, keep),
            first_update_diff=fedavg.diff_norms(first, first_ref, keep),
            change_diff=fedavg.diff_norms(change, change_ref, keep))
        numbers = dict(loss_gap=max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)))
        for k, v in per_leaf.items():
            numbers[k] = float(v.max())
            numbers[k + ".median"] = float(np.median(v))
        kept = [p for p, k in zip(fedavg.paths(first_ref), keep) if k]
        self.detail = dict(leaves=fedavg.paths(first_ref), kept=keep.tolist(),
                           losses=list(losses), ref_losses=list(ref_losses),
                           worst={k: kept[int(np.argmax(v))] for k, v in per_leaf.items()},
                           first=fedavg.leaf_norms(first).tolist(),
                           ref_first=fedavg.leaf_norms(first_ref).tolist(),
                           change=fedavg.leaf_norms(change).tolist(),
                           ref_change=fedavg.leaf_norms(change_ref).tolist(),
                           **{k: v.tolist() for k, v in per_leaf.items()})
        return numbers

    def faults(self):
        """Readings of the faults a training cell can have, planted in the
        reference: half of each batch left out (a state returned unchanged
        reads 1 on both update gaps by their measure and needs no run)."""
        ref_losses, ref_states = self.reference_rounds()
        half_losses, half_states = self.reference_rounds(half_batch=True)
        out = dict(half_batch=self.compare(half_losses, half_states, ref_losses, ref_states))
        self.fault_detail = self.detail
        return out

    def check(self, control: bool = False):
        """Program against reference; with ``control``, also the reference
        with its clients in the configuration's control precision put in the
        program's place."""
        ref_losses, ref_states = self.reference_rounds()
        out = self.compare(self.losses, self.prog, ref_losses, ref_states)
        if control:
            detail = self.detail
            low_losses, low_states = self.reference_rounds(jnp.dtype(self.config["control"]))
            low = self.compare(low_losses, low_states, ref_losses, ref_states)
            self.detail = dict(program=detail, control=self.detail)
            return out, low
        return out
