#!/usr/bin/env python3
"""Compile a configuration's serving programs for a described TPU v5e, with
no chip attached, and print what the compiler reports of their memory.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/compile_check.py <config>

``<config>`` names a file under ``configs/``. Compiled at the deployment's
batch and cache length, in its dtype and dispatch, on one chip of a
described ``v5e:2x2``: the serve step (the ``gmm`` kernel as on a TPU),
the parameter draw, and the donor-row gather over the KV cache. A compile
that passes is not a run. Prints one JSON object per program.
"""
from __future__ import annotations

import json
import os
import pathlib
import sys

CHIP_DIR = pathlib.Path(__file__).resolve().parent
HBM_BYTES = 15.75e9        # v5e: 16 GiB less the runtime's reservation


def main(name: str) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(CHIP_DIR.parents[1] / "src"))
    sys.path.insert(0, str(CHIP_DIR))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import bench
    from repro.launch.steps import make_serve_step
    from repro.models import moe as moe_mod
    from repro.models.model import init_params

    jax.config.update("jax_enable_compilation_cache", False)
    conf = json.loads((CHIP_DIR / "configs" / f"{name}.json").read_text())
    dep = conf["deployment"]
    adapter = bench.load_adapter(CHIP_DIR, conf)
    cfg = bench.arch_config(conf, adapter)
    dtype = jnp.dtype(dep["dtype"])
    B, S = dep["max_batch"], dep["max_len"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    # the kernel choice the model makes on a TPU
    moe_mod._interpret_kernels = lambda: False

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            tree)

    parts = adapter.compile_parts(cfg, conf)
    table, dpl = parts["table"], parts["deployment"]
    params, caches = placed(parts["params"]), placed(parts["caches"])
    programs = {
        "serve_step": jax.jit(make_serve_step(cfg, dpl),
                              donate_argnums=(1,)).lower(
            params, caches, placed(table.to_device()),
            jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=chip),
            jax.ShapeDtypeStruct((B,), jnp.int32, sharding=chip)),
        "init_params": jax.jit(lambda k: init_params(
            cfg, k, dtype, table.slot_to_expert, table.num_slots)).lower(
            jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                 sharding=chip)),
        "donor_gather": jax.jit(lambda c, src: jax.tree_util.tree_map(
            lambda leaf: jnp.take(leaf, src, axis=1), c),
            donate_argnums=(0,)).lower(
            caches, jax.ShapeDtypeStruct((B,), jnp.int32, sharding=chip)),
    }
    for prog, lowered in programs.items():
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(json.dumps({
            "config": name, "program": prog, "batch": B, "max_len": S,
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes, "total_bytes": total,
            "fits": total < HBM_BYTES,
            "tpu_custom_call": "tpu_custom_call" in compiled.as_text()}),
            flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
