"""Wire round trips of a whole storage tree through ``api.codecs``.

One round trip is ``encode_payload`` of the storage, then ``decode_payload``
back to device storage, ending when every decoded leaf is on the device.
The storage is the configuration's weights, drawn from the seed on the
device and compressed by the program.  The rate is payload megabytes over
the window.  The codec promises a bit-exact round trip, so every decoded
tree kept (a sample of the window's, drawn from the seed) is compared with
the storage it was encoded from, value for value.
"""

from __future__ import annotations

import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from reference import omc as ref_omc, weights

from . import common

KEEP = 3  # decoded trees kept for the comparison


class Driver:
    def __init__(self, cell, seed: int):
        self.cell, self.seed = cell, seed
        self.config = cell.config
        self.reference = importlib.import_module(f"reference.{self.config['reference']}")
        self.layout = self.reference.layout(self.config)
        self.fmt = ref_omc.Fmt(self.config["omc"]["format"])
        self.k_weights = jax.random.fold_in(weights.seed_key(seed), 0)
        self.rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 2])

    def setup(self, prior=None):
        from repro.api import codecs
        from repro.core.omc import OMCConfig
        from repro.federated.state import compress_params

        o = self.config["omc"]
        family, cfg = common.program_model(self.config)
        omc = OMCConfig.parse(o["format"], pvt=o["pvt"])
        specs = family.param_specs(cfg)
        t0 = time.perf_counter()
        build = jax.jit(lambda k: compress_params(weights.init(k, self.layout), specs, omc))
        self.codecs = codecs
        self.storage = jax.block_until_ready(build(self.k_weights))
        t1 = time.perf_counter()
        jax.block_until_ready(self._roundtrip()[0])
        self.phases = dict(weights=t1 - t0, warm_up=time.perf_counter() - t1)

    def _roundtrip(self):
        with jax.profiler.TraceAnnotation("bench.encode"):
            payload = self.codecs.encode_payload(self.storage)
        with jax.profiler.TraceAnnotation("bench.decode"):
            decoded, _ = self.codecs.decode_payload(payload)
            jax.block_until_ready(decoded)
        return decoded, len(payload)

    def window(self, seconds: float):
        self.kept, nbytes, trips = [], 0, 0
        start = time.perf_counter()
        while True:
            decoded, n = self._roundtrip()
            nbytes += n
            trips += 1
            # reservoir sample of the round trips, drawn from the seed
            if len(self.kept) < KEEP:
                self.kept.append(decoded)
            else:
                j = int(self.rng.integers(0, trips))
                if j < KEEP:
                    self.kept[j] = decoded
            del decoded
            elapsed = time.perf_counter() - start
            if elapsed >= seconds:
                break
        return dict(metrics=dict(wire_MB_per_s=nbytes / 1e6 / elapsed),
                    attempted=trips, failed=0,
                    counts=dict(trips=trips, bytes=nbytes, seconds=elapsed,
                                leaves=self.leaf_sizes()))

    def leaf_sizes(self):
        """``[(fields, bits)]`` of every compressed leaf: what one direction packs."""
        return [(int(np.prod(leaf.codes.shape)), self.fmt.bits)
                for leaf in jax.tree_util.tree_leaves(
                    self.storage, is_leaf=lambda x: hasattr(x, "codes"))
                if hasattr(leaf, "codes")]

    def release(self):
        # the encoded storage is what the decoded trees are compared with
        pass

    def _values(self, tree):
        """Every stored value as 32-bit patterns, with a flag for codes."""
        out = []
        for leaf in jax.tree_util.tree_leaves(tree, is_leaf=lambda x: hasattr(x, "codes")):
            parts = ((leaf.codes, True), (leaf.s, False), (leaf.b, False)) \
                if hasattr(leaf, "codes") else ((leaf, False),)
            for a, is_code in parts:
                a = jnp.asarray(a)
                bits = (a.astype(jnp.uint32) if a.dtype.itemsize < 4
                        else jax.lax.bitcast_convert_type(a, jnp.uint32))
                out.append((bits, is_code))
        return out

    def check(self, control: bool = False):
        """Values of the kept decoded trees that differ from the storage
        encoded; with ``control``, the same for the storage with the lowest
        mantissa bit of every code dropped (a format one bit narrower)."""
        want = self._values(self.storage)
        bad = sum(int(jnp.sum(a != b)) for tree in self.kept
                  for (a, _), (b, _) in zip(self._values(tree), want))
        out = dict(mismatched_values=float(bad))
        if not control:
            return out
        low = sum(int(jnp.sum((a & ~np.uint32(1)) != a)) for a, is_code in want if is_code)
        return out, dict(mismatched_values=float(low))
