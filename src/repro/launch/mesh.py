"""Production mesh definition (TPU v5e target).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before any jax init).

Besides the 2-D/3-D production meshes (DESIGN.md §4) this module owns the
1-D ``("clients",)`` population mesh that `repro.scale` shards per-client
state over (DESIGN.md §14).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small host mesh for tests (requires >= data*model local devices)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def make_population_mesh(num_shards=None):
    """1-D ``("clients",)`` mesh for sharded population state (DESIGN.md §14).

    Per-client server state (EF residuals, counters —
    :class:`repro.scale.store.PopulationStore`) partitions along one
    logical ``clients`` axis; this mesh maps that axis onto the local
    devices.  ``num_shards`` is clamped to the available device count —
    the *logical* shard count (``ShardLayout.num_shards``) may exceed it,
    in which case multiple logical shards share a device (the single-CPU
    test topology runs every shard on one device).
    """
    n = len(jax.devices())
    if num_shards is not None:
        n = max(1, min(int(num_shards), n))
    return jax.make_mesh((n,), ("clients",),
                         axis_types=(jax.sharding.AxisType.Auto,))


# TPU v5e hardware constants (per chip) — used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s per link
