"""launch/ mesh + sharding-spec unit tests (ISSUE 9 satellite).

The production mesh shapes (16x16, 2x16x16) exceed any test host, so
``make_production_mesh`` is tested by monkeypatching ``jax.make_mesh`` and
capturing the arguments; host- and population-mesh tests run for real on
the local devices.
"""

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch import mesh as mesh_lib
from repro.launch import specs as specs_lib


class _Capture:
    def __init__(self):
        self.calls = []

    def __call__(self, shape, axes, **kw):
        self.calls.append((tuple(shape), tuple(axes), dict(kw)))
        return ("mesh", tuple(shape), tuple(axes))


def test_meshes_have_auto_axis_types():
    """Every mesh this module builds marks each axis explicitly Auto."""
    auto = jax.sharding.AxisType.Auto
    assert mesh_lib.make_host_mesh().axis_types == (auto, auto)
    assert mesh_lib.make_population_mesh().axis_types == (auto,)


def test_make_production_mesh_shapes(monkeypatch):
    cap = _Capture()
    monkeypatch.setattr(jax, "make_mesh", cap)
    mesh_lib.make_production_mesh()
    mesh_lib.make_production_mesh(multi_pod=True)
    assert cap.calls[0][:2] == ((16, 16), ("data", "model"))
    assert cap.calls[1][:2] == ((2, 16, 16), ("pod", "data", "model"))


def test_make_host_mesh_real():
    m = mesh_lib.make_host_mesh()
    assert m.axis_names == ("data", "model")
    assert m.devices.shape == (1, 1)


def test_make_population_mesh_real():
    m = mesh_lib.make_population_mesh()
    assert m.axis_names == ("clients",)
    assert 1 <= m.devices.size <= len(jax.devices())
    # logical shard counts beyond the device count clamp, never raise
    m2 = mesh_lib.make_population_mesh(num_shards=10_000)
    assert m2.devices.size <= len(jax.devices())
    assert mesh_lib.make_population_mesh(num_shards=1).devices.size == 1


def test_population_sharding_fallbacks():
    """No 'clients' axis, a 1-wide axis, or a non-dividing leading dim all
    fall back to replication; a dividing leading dim partitions axis 0."""
    host = mesh_lib.make_host_mesh()
    assert specs_lib.population_sharding(host, 2, 8).spec == P()

    pop = mesh_lib.make_population_mesh()
    sh = specs_lib.population_sharding(pop, 3, 8)
    n = pop.devices.size
    assert isinstance(sh, NamedSharding)
    if n <= 1:  # single-device topology: replicate
        assert sh.spec == P()
    else:
        assert sh.spec == P("clients", None, None)
        # non-divisible leading dim replicates instead of raising
        assert specs_lib.population_sharding(pop, 3, n + 1).spec == P()


def test_annotate_population_places_tree():
    pop = mesh_lib.make_population_mesh()
    tree = dict(a=np.zeros((8, 3), np.float32), b=np.zeros((8,), np.float32))
    placed = specs_lib.annotate_population(tree, pop)
    for v in placed.values():
        assert isinstance(v.sharding, NamedSharding)
        assert v.sharding.mesh.axis_names == ("clients",)


def test_population_mesh_hosts_store_rows():
    """End to end: PopulationStore.device_ef places rows via the spec."""
    from repro.core.omc import OMCConfig
    from repro.models import conformer as cf
    from repro.scale import PopulationStore, ShardLayout

    cfg = cf.ConformerConfig(n_layers=1, d_model=16, n_heads=2, d_ff=32,
                             n_classes=8, d_in=4)
    store = PopulationStore(ShardLayout(4, 2))
    params = cf.init(jax.random.PRNGKey(0), cfg)
    store.init_ef(params, cf.param_specs(cfg), OMCConfig.parse("S1E3M7"))
    mesh = mesh_lib.make_population_mesh(num_shards=2)
    rows = store.device_ef(mesh)
    assert rows and all(v.shape[0] == 4 for v in rows.values())


def test_compile_cache_env_var_wins(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []  # JAX reads the variable itself


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    from pathlib import Path

    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    root = Path(__file__).resolve().parents[1]
    assert Path(path) == root / ".jax_cache"
    assert calls == [("jax_compilation_cache_dir", path)]
    assert ".jax_cache/" in (root / ".gitignore").read_text().splitlines()
