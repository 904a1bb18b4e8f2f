#!/usr/bin/env python3
"""Record the small TPU trace the scope tests read.

    python3 benchmarks/chip/tests/record_scoped_trace.py <out_dir>

Traces three executions of a jitted step (``jit_scoped_step``) whose
stages run under ``jax.named_scope``: a bf16 matmul under ``attention``, a
small Pallas kernel under ``moe.exchange/moe.expert``, an add under
``moe.exchange`` and a tanh in no scope. Each execution runs inside the
program's own host spans (``repro.obs.trace``): ``repro.frontend.step``
around ``repro.engine.inputs`` (the dispatch), ``repro.engine.sync`` (the
wait) and ``repro.engine.bookkeep`` (2 ms of host work, a gap on the
device). Writes the ``.xplane.pb`` under ``<out_dir>``. Needs a TPU.
"""
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[3] / "src"))
from repro.obs.trace import span  # noqa: E402

HOST_WORK_S = 0.002


def scoped_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


@jax.jit
def scoped_step(a, b, x):
    with jax.named_scope("attention"):
        h = (a @ b).astype(jnp.bfloat16)
    with jax.named_scope("moe.exchange"):
        with jax.named_scope("moe.expert"):
            y = pl.pallas_call(scoped_kernel, out_shape=jax.ShapeDtypeStruct(
                x.shape, x.dtype))(x)
        y = y + 1.0
    return h, y, jnp.tanh(y)


def main(out_dir: str) -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_scoped_trace: needs a TPU")
    a = jnp.ones((1024, 1024), jnp.bfloat16)
    x = jnp.ones((256, 256), jnp.float32)
    jax.block_until_ready(scoped_step(a, a, x))
    jax.profiler.start_trace(out_dir)
    for _ in range(3):
        with span("frontend.step"):
            with span("engine.inputs"):
                out = scoped_step(a, a, x)
            with span("engine.sync"):
                jax.block_until_ready(out)
            with span("engine.bookkeep"):
                time.sleep(HOST_WORK_S)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
