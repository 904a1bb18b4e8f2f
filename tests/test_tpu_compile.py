"""Compile rehearsal for TPU v5e: the main path at Mixtral-8x22B's published
widths, compiled by the TPU compiler for a DESCRIBED v5e:2x2 topology (no
chip attached, nothing runs). The compiler refuses what the chip would
refuse: kernels over the scoped VMEM budget, programs over HBM, collectives
it cannot lower.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. Code that asks ``jax.default_backend()`` still sees the CPU here,
so the tests steer the kernel choice with monkeypatch.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import make_initial_membership
from repro.core.elastic_moe import EPContext
from repro.kernels.moe_gmm import gmm
from repro.launch.steps import make_serve_step
from repro.models import moe as moe_mod
from repro.models.model import Deployment, init_caches, init_params, param_shapes

HBM_BYTES = 15.75e9          # v5e: 16 GiB, less the runtime's reservation
ARCH = "mixtral-8x22b"
BATCH, MAX_LEN = 8, 40       # chip_smoke's decode batch and cache length


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executable is written to the persistent cache but
    # cannot be read back without one: keep the cache off for this file.
    # The CPU suite's fp32 matmul precision is not what the chip runs with
    # (Mosaic refuses an fp32-precision dot of bf16 operands): compile
    # with the default precision, as the chip does.
    enabled = jax.config.jax_enable_compilation_cache
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    jax.config.update("jax_default_matmul_precision", precision)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_kernels(monkeypatch):
    """The model's kernel choice as it is made on a TPU (native Pallas)."""
    monkeypatch.setattr(moe_mod, "_interpret_kernels", lambda: False)


def _placed(tree, sharding):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _one_layer():
    return dataclasses.replace(get_config(ARCH), num_layers=1)


# ---------------------------------------------------------------------------
# gmm at both expert projections
# ---------------------------------------------------------------------------


# decode: 8 tokens x top-2; 128 rows; the batch cell's 128 rows x top-2,
# whose x fills the resident schedule's VMEM at w_out; a receive buffer too
# large to keep resident
@pytest.mark.parametrize("rows", [2 * BATCH, 128, 256, 8192])
@pytest.mark.parametrize("proj", ["up", "down"])
def test_gmm_compiles_at_mixtral_widths(one_chip, rows, proj):
    cfg = get_config(ARCH)
    d, de, slots = cfg.d_model, cfg.moe.d_expert, 16
    din, dout = (d, de) if proj == "up" else (de, d)
    x = jax.ShapeDtypeStruct((rows, din), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((slots, din, dout), jnp.bfloat16,
                             sharding=one_chip)
    gs = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(gmm).lower(x, w, gs).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    kernel = "gmm_tiled" if rows == 8192 else "gmm_resident"
    assert len(calls) == 1 and f"%{kernel}" in calls[0]


# ---------------------------------------------------------------------------
# the 1-layer serve step and its params on one chip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dispatch", ["dense", "ragged"])
def test_serve_step_fits_one_chip(one_chip, tpu_kernels, dispatch):
    cfg = _one_layer()
    table = make_initial_membership(16, cfg.moe.num_experts, 1)
    dpl = Deployment(moe=moe_mod.local_deployment(
        table.num_slots, cfg.capacity_factor, dispatch=dispatch))
    params = _placed(param_shapes(cfg, jnp.bfloat16, table.slot_to_expert,
                                  table.num_slots), one_chip)
    caches = _placed(jax.eval_shape(
        lambda: init_caches(cfg, BATCH, MAX_LEN, jnp.bfloat16)), one_chip)
    ms = _placed(table.to_device(), one_chip)
    tokens = jax.ShapeDtypeStruct((BATCH, 1), jnp.int32, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((BATCH,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(make_serve_step(cfg, dpl), donate_argnums=(1,)).lower(
        params, caches, ms, tokens, lengths).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 10e9           # the full-width layer
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    assert ("tpu_custom_call" in compiled.as_text()) == (dispatch == "ragged")


def test_init_params_fits_one_chip(one_chip):
    cfg = _one_layer()
    table = make_initial_membership(16, cfg.moe.num_experts, 1)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one_chip)
    compiled = jax.jit(lambda k: init_params(
        cfg, k, jnp.bfloat16, table.slot_to_expert, table.num_slots)
    ).lower(key).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes > 10e9
    assert mem.output_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


# ---------------------------------------------------------------------------
# the EP path across a v5e:2x2 mesh
# ---------------------------------------------------------------------------


def test_ragged_moe_apply_compiles_on_2x2_mesh(topo, tpu_kernels):
    cfg = get_config(ARCH)
    world, spr = 4, 4
    mesh = Mesh(np.asarray(topo.devices).reshape(world, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    table = make_initial_membership(world, cfg.moe.num_experts, spr)
    dep = moe_mod.MoEDeployment(
        ep=EPContext(axis_names=("data",), world=world, slots_per_rank=spr,
                     capacity_factor=8.0),
        tp_axes=("model",), mesh=mesh, dispatch="ragged")
    shapes = jax.eval_shape(lambda: moe_mod.moe_layer_init(
        jax.random.key(0), cfg, world * spr, table.slot_to_expert,
        jnp.bfloat16))
    specs = moe_mod.moe_param_specs(dep, shapes)
    p = jax.tree_util.tree_map(
        lambda s, spec: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
        shapes, specs, is_leaf=lambda x: isinstance(x, P))
    x = jax.ShapeDtypeStruct((64, cfg.d_model), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data", None)))
    ms = _placed(table.to_device(), NamedSharding(mesh, P()))
    compiled = jax.jit(lambda x, p, m: moe_mod.moe_apply(cfg, p, x, m, dep)
                       ).lower(x, p, ms).compile()
    hlo = compiled.as_text()
    assert "ragged-all-to-all" in hlo
    assert "tpu_custom_call" in hlo
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
