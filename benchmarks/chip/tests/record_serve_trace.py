#!/usr/bin/env python3
"""Record the small TPU trace of the program's serve step that the reader
tests read.

    python3 benchmarks/chip/tests/record_serve_trace.py <out_dir>

Builds ``CONF`` (Mixtral-shaped, bf16, through the ``mixtral`` adapter) as
a cell would, then traces three executions of its jitted serve step
(``jit_serve_step``: every stage scope, three ``gmm`` calls a layer), each
inside a ``bench.step`` host span, and writes the ``.xplane.pb`` under
``<out_dir>``. Needs a TPU.
"""
import pathlib
import sys

CHIP = pathlib.Path(__file__).resolve().parents[1]

#: small widths the TPU's kernels take (lanes of 128), 2 layers
CONF = {
    "name": "serve-trace", "adapter": "mixtral",
    "model": {"hidden_size": 256, "intermediate_size": 512,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 64, "num_hidden_layers": 2,
              "num_local_experts": 8, "num_experts_per_tok": 2,
              "vocab_size": 1024, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
              "sliding_window": None, "hidden_act": "silu",
              "tie_word_embeddings": False},
    "deployment": {"ep_ranks": 16, "slots_per_rank": 1, "ranks_per_host": 2,
                   "hosts_per_switch": 2, "dispatch": "ragged",
                   "dtype": "bfloat16", "max_batch": 16, "max_len": 64,
                   "kv_block_size": 16},
}
#: the position each row feeds in every traced step
POSITION = 10


def main(out_dir: str) -> None:
    sys.path.insert(0, str(CHIP.parents[1] / "src"))
    sys.path.insert(0, str(CHIP))
    import jax
    import jax.numpy as jnp

    import bench
    from repro.launch.steps import make_serve_step

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_serve_trace: needs a TPU")
    adapter = bench.load_adapter(CHIP, CONF)
    cfg = bench.arch_config(CONF, adapter)
    rt, eng, _ = adapter.build(cfg, CONF, 0)
    step = jax.jit(make_serve_step(cfg, rt.dpl), donate_argnums=(1,))
    B = CONF["deployment"]["max_batch"]
    tokens = jnp.arange(1, B + 1, dtype=jnp.int32)[:, None]
    pos = jnp.full((B,), POSITION, jnp.int32)
    caches = eng.caches
    nxt, _, caches = step(rt.params, caches, rt.membership, tokens, pos)
    jax.block_until_ready(nxt)
    jax.profiler.start_trace(out_dir)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            nxt, _, caches = step(rt.params, caches, rt.membership, tokens,
                                  pos)
            jax.block_until_ready(nxt)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
