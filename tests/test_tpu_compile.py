"""Main-path Pallas kernels compile for a described TPU v5e.

Nothing runs: each test lowers a kernel at a real conformer_s width for one
chip of a ``v5e:2x2`` topology described (not attached) on this host, and
asserts the compiled program holds the Mosaic kernel (``tpu_custom_call``).
This catches what interpret mode cannot see: block shapes off the (8, 128)
tiling, casts and primitives Mosaic cannot lower.

The topology is described only inside the module fixture, never at import,
so every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler.  The kernel modules are compiled directly:
``kernels.ops`` would pick the jnp oracles on this CPU host.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.formats import FloatFormat
from repro.kernels import agg, bitpack
from repro.kernels import quantize as qk

LEAF = (17, 512, 2048)  # conformer_s FFN weight, 17 layers stacked
N = 17 * 512 * 2048
COHORT = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("width", [11, 2])
def test_pack_unpack_compile(spec, width):
    _assert_kernel(lambda c: bitpack.pack(c, width), spec(LEAF, jnp.uint32))
    words = -(-N * width // 32)
    _assert_kernel(lambda w: bitpack.unpack(w, width, N),
                   spec((words,), jnp.uint32))


@pytest.mark.parametrize("name", ["S1E3M7", "S1E4M3"])
def test_fused_aggregate_compiles_on_stacked_leaf(spec, name):
    fmt = FloatFormat.parse(name)
    cd, stack = fmt.container_dtype, LEAF[:1]
    args = (spec(LEAF, cd), spec(stack, jnp.float32), spec(stack, jnp.float32),
            spec((COHORT,) + LEAF, cd), spec((COHORT,) + stack, jnp.float32),
            spec((COHORT,) + stack, jnp.float32), spec((COHORT,), jnp.float32))
    _assert_kernel(
        lambda *a: agg.fused_aggregate(*a, 0.5, fmt, batch_axes=1), *args)


def test_dequantize_compiles(spec):
    fmt = FloatFormat.parse("S1E3M7")
    _assert_kernel(lambda c: qk.dequantize(c, fmt, 1.0, 0.0),
                   spec(LEAF[1:], fmt.container_dtype))


def test_quantize_and_stats_compile(spec):
    fmt = FloatFormat.parse("S1E3M7")
    x = spec(LEAF[1:], jnp.float32)
    _assert_kernel(lambda v: qk.quantize(v, fmt), x)
    _assert_kernel(lambda v: qk.quantize_stats(v, fmt), x)
