"""Record the small TPU trace that ``test_trace.py`` reads.

    python bench/tests/record_trace.py <out dir>

On a TPU: three rounds of a matmul program, a named Pallas kernel and a
5 ms host sleep, each in a ``bench.*`` annotation, all inside the
``bench.window`` annotation the benchmark's reduction looks for.
"""

import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _double(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    x = jnp.ones((1024, 1024), jnp.float32)
    matmul = jax.jit(lambda a: (a @ a).sum())
    double = jax.jit(lambda a: pl.pallas_call(
        _double, out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype), name="double_kernel")(a))
    jax.block_until_ready((matmul(x), double(x)))
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.matmul"):
                matmul(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.kernel"):
                double(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.host_work"):
                time.sleep(0.005)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
