"""Multi-device integration (8 fake CPU devices via a subprocess, so the
main test process keeps its single-device world):

  * distributed shard_map MoE dispatch == local reference
  * elastic masking under a failure: distributed == local, and a2a over the
    EP axis present in the compiled HLO
  * local ragged (dropless) dispatch == distributed dense dispatch
  * sequence-sharded distributed decode (LSE merge) == plain decode

XLA:CPU cannot run ``ragged-all-to-all``, so the DISTRIBUTED ragged path is
checked on TPU instead: compiled for a described v5e:2x2 mesh in
tests/test_tpu_compile.py and run on four chips by ``chip_smoke.py --chips 4``.
"""
import os
import subprocess
import sys

import pytest

# multi-device dry-run: spawns a subprocess with 8 fake CPU devices and
# recompiles everything — minutes of wall time, so nightly CI only
pytestmark = pytest.mark.slow

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp, dataclasses
jax.config.update("jax_default_matmul_precision", "float32")
from jax.sharding import PartitionSpec as P
from repro.configs import get_config
from repro.core import make_initial_membership, EPContext
from repro.models.moe import moe_apply, moe_layer_init, MoEDeployment, local_deployment
from repro.models import attention as attn

from jax.sharding import AxisType
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)

cfg = get_config("mixtral-8x22b").reduced()
world, spr = 4, 2
table = make_initial_membership(world, cfg.moe.num_experts, spr)
p = moe_layer_init(jax.random.key(0), cfg, world * spr,
                   table.slot_to_expert, jnp.float32)
T, d = 64, cfg.d_model
x = jax.random.normal(jax.random.key(1), (T, d), jnp.float32)

dep_d = MoEDeployment(
    ep=EPContext(axis_names=("data",), world=world, slots_per_rank=spr,
                 capacity_factor=8.0),
    tp_axes=("model",), mesh=mesh)
dep_l = local_deployment(world * spr, capacity_factor=8.0)

# --- healthy: distributed == local -------------------------------------
ms = table.to_device()
yd, _ = jax.jit(lambda x, p, m: moe_apply(cfg, p, x, m, dep_d))(x, p, ms)
yl, _ = jax.jit(lambda x, p, m: moe_apply(cfg, p, x, m, dep_l))(x, p, ms)
err = float(jnp.abs(yd - yl).max())
assert err < 1e-4, f"healthy mismatch {err}"
print("healthy dist==local OK", err)

# --- degraded: fail rank 2, EPLB repair, same compiled fn ---------------
from repro.core import eplb_place
table.deactivate(2)
res = eplb_place(cfg.moe.num_experts, world, spr, table.active_mask,
                 prev_slot_to_expert=table.slot_to_expert)
assert not res.infeasible
table.set_placement(res.slot_to_expert)
ms2 = table.to_device()
fn = jax.jit(lambda x, p, m: moe_apply(cfg, p, x, m, dep_d))
yd2, _ = fn(x, p, ms2)
yl2, _ = jax.jit(lambda x, p, m: moe_apply(cfg, p, x, m, dep_l))(x, p, ms2)
err2 = float(jnp.abs(yd2 - yl2).max())
assert err2 < 1e-4, f"degraded mismatch {err2}"
# routing never targets rank 2's slots
from repro.core import elastic_route
logits = jnp.einsum("td,de->te", x, p["router"])
_, _, slots = elastic_route(logits, ms2, cfg.moe.top_k, jnp.arange(T))
assert not np.isin(np.asarray(slots) // spr, [2]).any()
print("degraded dist==local OK", err2)

# --- a2a over the EP axis exists in the compiled module -----------------
txt = fn.lower(x, p, ms2).compile().as_text()
assert "all-to-all" in txt, "expected all-to-all over the EP axis"
print("a2a present OK")

# --- ragged (dropless) local dispatch == distributed dense ---------------
dep_rl = dataclasses.replace(dep_l, dispatch="ragged")
for label, table_ms in (("healthy", ms), ("degraded", ms2)):
    yrl, _ = jax.jit(lambda x, p, m: moe_apply(cfg, p, x, m, dep_rl))(x, p, table_ms)
    ydd, aux = jax.jit(lambda x, p, m: moe_apply(cfg, p, x, m, dep_d))(x, p, table_ms)
    assert float(aux["dropped_fraction"]) == 0.0
    e_dd = float(jnp.abs(yrl - ydd).max())
    assert e_dd < 1e-4, f"ragged local {label} vs dense dist mismatch {e_dd}"
    print(f"ragged local {label} == dense dist OK", e_dd)

# --- seq-sharded LSE-merged decode == plain decode ------------------------
acfg = dataclasses.replace(get_config("jamba-v0.1-52b").reduced(),
                           attention="gqa", attn_layer_period=1,
                           attn_layer_offset=0)
ap = attn.gqa_init(jax.random.key(2), acfg, jnp.float32)
B, W = 2, 32
cache = {"k": jax.random.normal(jax.random.key(3), (B, W, acfg.num_kv_heads, acfg.head_dim)),
         "v": jax.random.normal(jax.random.key(4), (B, W, acfg.num_kv_heads, acfg.head_dim)),
         "pos": jnp.tile(jnp.arange(W)[None], (B, 1)).astype(jnp.int32)}
lengths = jnp.array([20, 31], jnp.int32)
xq = jax.random.normal(jax.random.key(5), (B, 1, acfg.d_model))
y_ref, _ = attn.gqa_decode(acfg, ap, xq, lengths, cache)
fn2 = jax.shard_map(
    lambda p_, x_, l_, c_: attn.gqa_decode_seqsharded(acfg, p_, x_, l_, c_,
                                                      axis="data"),
    mesh=mesh,
    in_specs=(jax.tree_util.tree_map(lambda _: P(), ap), P(), P(),
              {"k": P(None, "data"), "v": P(None, "data"),
               "pos": P(None, "data")}),
    out_specs=(P(), {"k": P(None, "data"), "v": P(None, "data"),
                     "pos": P(None, "data")}),
    check_vma=False)
y_ss, _ = jax.jit(fn2)(ap, xq, lengths, cache)
err3 = float(jnp.abs(y_ss - y_ref).max())
assert err3 < 1e-4, f"seq-sharded decode mismatch {err3}"
print("seq-sharded decode OK", err3)
print("ALL MULTIDEVICE OK")
"""


def test_multidevice_subprocess(tmp_path):
    script = tmp_path / "md.py"
    script.write_text(SCRIPT)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, str(script)],
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        env=env, capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    assert "ALL MULTIDEVICE OK" in res.stdout
