#!/usr/bin/env python3
"""Record the small TPU trace the xplane tests read.

    python3 benchmarks/chip/tests/record_trace.py <out_dir>

Traces three executions of a jitted bf16 matmul step (``jit_small_step``)
and one call of a small Pallas kernel (``small_kernel``), each between
host spans named ``bench.*``, and writes the ``.xplane.pb`` under
``<out_dir>``. Needs a TPU.
"""
import sys

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def small_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def main(out_dir: str) -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: needs a TPU")

    @jax.jit
    def small_step(a, b):
        return (a @ b).astype(jnp.bfloat16)

    kernel = jax.jit(lambda x: pl.pallas_call(
        small_kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x))
    a = jnp.ones((1024, 1024), jnp.bfloat16)
    x = jnp.ones((256, 256), jnp.float32)
    small_step(a, a).block_until_ready()
    kernel(x).block_until_ready()
    jax.profiler.start_trace(out_dir)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            small_step(a, a).block_until_ready()
    with jax.profiler.TraceAnnotation("bench.kernel"):
        kernel(x).block_until_ready()
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
