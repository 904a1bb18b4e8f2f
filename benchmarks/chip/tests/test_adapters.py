"""A configuration's adapter (``adapters/<name>.py``): the ``mixtral``
adapter builds what the harness built before adapters; the harness refuses
a missing adapter, an ArchConfig whose widths differ from the file, and a
deployment the adapter cannot hold, before any step runs; and a second
configuration, under DeepSeek-V3's key names, enters the harness as new
files alone."""
import dataclasses
import filecmp
import json
import types

import pytest

import bench
import flops
from helpers import CHIP, TINY_CLOSED, tiny_conf, tiny_tree

SEED = 2**31 + 11
M22B = json.loads((CHIP / "configs" / "mixtral-8x22b.json").read_text())


def _arch_config_before_adapters(conf: dict):
    """``bench.arch_config`` as it stood before adapters, the reference the
    ``mixtral`` adapter is held to."""
    from repro.configs.base import ArchConfig, MoEArchConfig

    m, dep = conf["model"], conf["deployment"]
    window = m.get("sliding_window") or 0
    return ArchConfig(
        name=conf["name"], family="moe",
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"],
        head_dim=m.get("head_dim") or 0, d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"],
        attention="swa" if window else "gqa", window=window,
        activation="swiglu", rope_theta=float(m["rope_theta"]),
        moe=MoEArchConfig(num_experts=m["num_local_experts"],
                          top_k=m["num_experts_per_tok"],
                          d_expert=m["intermediate_size"]),
        slots_per_rank=dep["slots_per_rank"],
        ranks_per_host=dep["ranks_per_host"],
        hosts_per_switch=dep["hosts_per_switch"],
        dispatch_mode=dep["dispatch"], kv_block_size=dep["kv_block_size"])


def _mixtral():
    return bench.load_adapter(CHIP, M22B)


@pytest.mark.parametrize("conf", [M22B, tiny_conf()],
                         ids=["mixtral-8x22b", "tiny"])
def test_mixtral_adapter_builds_the_config_as_before(conf):
    cfg = bench.arch_config(conf, _mixtral())
    want = _arch_config_before_adapters(conf)
    for f in dataclasses.fields(cfg):
        assert getattr(cfg, f.name) == getattr(want, f.name), f.name


def _widened(key):
    """An adapter that builds the file's ArchConfig with one width off."""
    def arch_config(conf):
        cfg = _mixtral().arch_config(conf)
        moe = cfg.moe
        change = {
            "intermediate_size": dict(moe=dataclasses.replace(
                moe, d_expert=moe.d_expert * 2)),
            "num_local_experts": dict(moe=dataclasses.replace(
                moe, num_experts=moe.num_experts * 2)),
            "vocab_size": dict(vocab_size=cfg.vocab_size + 128)}[key]
        return dataclasses.replace(cfg, **change)
    return types.SimpleNamespace(arch_config=arch_config)


@pytest.mark.parametrize("key", ["intermediate_size", "num_local_experts",
                                 "vocab_size"])
def test_width_guard_names_the_key(key):
    with pytest.raises(bench.Refused, match=f"built {key}="):
        bench.arch_config(M22B, _widened(key))


def test_missing_adapter_is_refused_naming_the_file():
    with pytest.raises(bench.Refused, match=r"adapters/nosuch\.py"):
        bench.load_adapter(CHIP, dict(M22B, adapter="nosuch"))
    with pytest.raises(bench.Refused, match="no adapter"):
        bench.load_adapter(CHIP, {k: v for k, v in M22B.items()
                                  if k != "adapter"})


def test_mixtral_adapter_refuses_a_share_of_the_ranks():
    conf = dict(M22B, deployment=dict(M22B["deployment"], ranks_here=8))
    with pytest.raises(bench.Refused, match="ranks_here=8"):
        bench.arch_config(conf, _mixtral())


#: an adapter that runs twice the file's expert width
WIDE_ADAPTER = '''
import dataclasses
import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "mixtral_adapter", pathlib.Path(__file__).parent / "mixtral.py")
_mixtral = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mixtral)
build = _mixtral.build


def arch_config(conf):
    cfg = _mixtral.arch_config(conf)
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, d_expert=2 * cfg.moe.d_expert))
'''


@pytest.mark.parametrize("fault", ["no_adapter", "widths"])
def test_cell_refused_before_any_step(tmp_path, fault):
    """Through ``CellRun.build``: the refusal comes before the instance is
    built, so no step runs."""
    conf = tiny_conf()
    adapter = None
    if fault == "no_adapter":
        conf["adapter"] = "tiny-missing"
    else:
        conf["adapter"] = "tiny-wide"
        adapter = WIDE_ADAPTER
    spec, chip, root = tiny_tree(tmp_path, TINY_CLOSED, conf=conf,
                                 adapter=adapter)
    cell = bench.CellRun(spec, "tiny", SEED, 1.0, False, chip_dir=chip,
                         root=root)
    match = "no adapter" if fault == "no_adapter" else \
        "built intermediate_size=192"
    with pytest.raises(bench.Refused, match=match):
        cell.build()
    assert not hasattr(cell, "rt") and cell.run.steps == []


# -- a second configuration as new files --------------------------------------

#: DeepSeek-V3's key names at toy widths: MLA, one shared expert, one
#: leading dense layer, routed experts of ``moe_intermediate_size``
MLA_MODEL = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, n_routed_experts=8, n_shared_experts=1,
    num_experts_per_tok=2, first_k_dense_replace=1, num_hidden_layers=3,
    vocab_size=256, rope_theta=10000, rms_norm_eps=1e-6, hidden_act="silu",
    tie_word_embeddings=False)

MLA_ADAPTER = '''"""A stand-in for the benchmark's tests, not DeepSeek support: DeepSeek-V3's
Hugging Face keys onto the program's MLA attention, shared experts and
leading dense layers, with the program's softmax routing in place of
DeepSeek-V3's grouped sigmoid routing."""


def arch_config(conf):
    from repro.configs.base import ArchConfig, MLAConfig, MoEArchConfig

    m, dep = conf["model"], conf["deployment"]
    de = m["moe_intermediate_size"]
    return ArchConfig(
        name=conf["name"], family="moe",
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        attention="mla", activation="swiglu",
        rope_theta=float(m["rope_theta"]),
        mla=MLAConfig(q_lora_rank=m["q_lora_rank"],
                      kv_lora_rank=m["kv_lora_rank"],
                      qk_nope_head_dim=m["qk_nope_head_dim"],
                      qk_rope_head_dim=m["qk_rope_head_dim"],
                      v_head_dim=m["v_head_dim"]),
        moe=MoEArchConfig(num_experts=m["n_routed_experts"],
                          top_k=m["num_experts_per_tok"], d_expert=de,
                          num_shared_experts=m["n_shared_experts"],
                          d_shared_expert=de,
                          first_dense_layers=m["first_k_dense_replace"]),
        slots_per_rank=dep["slots_per_rank"],
        ranks_per_host=dep["ranks_per_host"],
        hosts_per_switch=dep["hosts_per_switch"],
        dispatch_mode=dep["dispatch"], kv_block_size=dep["kv_block_size"])


def build(cfg, conf, seed):
    import jax.numpy as jnp

    from repro.core.topology import FaultDomainTree
    from repro.launch.serve import build_frontend

    dep = conf["deployment"]
    world = dep["ep_ranks"]
    return build_frontend(
        cfg, world=world, slots_per_rank=dep["slots_per_rank"],
        topology=FaultDomainTree(world, ranks_per_host=dep["ranks_per_host"],
                                 hosts_per_switch=dep["hosts_per_switch"]),
        seed=seed, dtype=jnp.dtype(dep["dtype"]), dispatch=dep["dispatch"],
        max_batch=dep["max_batch"], max_len=dep["max_len"])
'''


def _mla_conf() -> dict:
    conf = tiny_conf()
    conf.update(name="tiny-mla", adapter="tiny-mla", model=MLA_MODEL)
    return conf


def _tree_files(chip):
    return {p.relative_to(chip) for p in chip.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_second_configuration_enters_as_new_files(tmp_path):
    conf = _mla_conf()
    spec, chip, root = tiny_tree(tmp_path, TINY_CLOSED, name="tiny-mla",
                                 conf=conf, adapter=MLA_ADAPTER)
    # the copy gains a config file, its adapter and a mix file; every
    # copied file is as it was
    copied = {p for p in _tree_files(CHIP) if p.parts[0] != "tests"}
    added = _tree_files(chip) - copied
    assert {str(p) for p in added} == {"configs/tiny-mla.json",
                                       "adapters/tiny-mla.py",
                                       "traffic/tiny-mla.json"}
    _, mismatch, errors = filecmp.cmpfiles(CHIP, chip, sorted(copied),
                                           shallow=False)
    assert mismatch == [] and errors == []

    cell = bench.CellRun(spec, "tiny", SEED, 1.0, False, chip_dir=chip,
                         root=root)
    cell.measure()
    cfg, run = cell.cfg, cell.run
    assert cfg.attention == "mla" and cfg.moe.num_shared_experts == 1
    assert cfg.moe.first_dense_layers == 1 and cfg.moe.d_expert == 32
    batch = conf["deployment"]["max_batch"]
    assert run.steps and all(fed == batch for _, _, fed, _ in run.steps)
    assert run.compiles_in_window == 0
    done = run.window_requests()
    assert done and all(len(r.tokens) == r.max_new for r in done)

    # flops counts its work: 1 dense layer and 2 MoE layers, absorbed MLA
    d, H, V = 64, 4, 256
    attn = (d * 48 + 48 * H * 24 + d * (32 + 8) + H * 16 * 32 + H * 32 * 16
            + H * 16 * d)
    per_position = H * (2 * 32 + 8)
    moe = d * 8 + 1 * 3 * d * 32 + 2 * 3 * d * 32
    for ctx in (0, 7):
        assert flops.token_flops(conf["model"], ctx,
                                 conf["deployment"]) == 2 * (
            3 * (attn + per_position * ctx) + 2 * moe + 3 * d * 128 + d * V)


def test_width_guard_names_the_deepseek_keys():
    conf = _mla_conf()
    ns = {}
    exec(MLA_ADAPTER, ns)
    cfg = ns["arch_config"](conf)
    assert bench.arch_config(conf, types.SimpleNamespace(
        arch_config=lambda c: cfg)) is cfg
    for key, change in (
            ("moe_intermediate_size", dict(d_expert=64)),
            ("n_routed_experts", dict(num_experts=16))):
        wrong = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, **change))
        with pytest.raises(bench.Refused, match=f"built {key}="):
            bench.arch_config(conf, types.SimpleNamespace(
                arch_config=lambda c: wrong))
