"""Mixtral-family configurations onto the program (Hugging Face
``MixtralForCausalLM`` keys): every layer is attention (full, or sliding
where ``sliding_window`` is set) and a routed MoE FFN of
``intermediate_size``, with no shared or dense FFN.

One serving instance holds every EP rank's slots on this chip, so the
deployment's ``ranks_here`` has to be ``ep_ranks``.
"""

#: configuration keys the program fixes and cannot be told otherwise
PROGRAM_FIXED = {"rms_norm_eps": 1e-6, "hidden_act": "silu",
                 "tie_word_embeddings": False}


def arch_config(conf: dict):
    """The program's ArchConfig for a configuration file; raises
    ValueError for a file the program cannot run as it says."""
    from repro.configs.base import ArchConfig, MoEArchConfig

    m, dep = conf["model"], conf["deployment"]
    for key, value in PROGRAM_FIXED.items():
        if m.get(key) != value:
            raise ValueError(f"{key}={m.get(key)!r}, but the program runs "
                             f"{value!r}")
    if dep.get("ranks_here", dep.get("ep_ranks")) != dep.get("ep_ranks"):
        raise ValueError(f"ranks_here={dep['ranks_here']}, but the program "
                         f"holds all {dep['ep_ranks']} ranks' slots")
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


def build(cfg, conf: dict, seed: int):
    """(runtime, engine, frontend): the serving instance, every rank's
    slots on this chip, weights drawn on the device from ``seed``."""
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


def compile_parts(cfg, conf: dict) -> dict:
    """What a compile for a described chip needs: the membership ``table``,
    the ``deployment``, and the ``params`` and ``caches`` as shapes."""
    import jax
    import jax.numpy as jnp

    from repro.core import make_initial_membership
    from repro.models import moe as moe_mod
    from repro.models.model import Deployment, init_caches, param_shapes

    dep = conf["deployment"]
    dtype = jnp.dtype(dep["dtype"])
    table = make_initial_membership(dep["ep_ranks"], cfg.moe.num_experts,
                                    dep["slots_per_rank"])
    return {
        "table": table,
        "deployment": Deployment(moe=moe_mod.local_deployment(
            table.num_slots, cfg.capacity_factor, dispatch=dep["dispatch"])),
        "params": param_shapes(cfg, dtype, table.slot_to_expert,
                               table.num_slots),
        "caches": jax.eval_shape(lambda: init_caches(
            cfg, dep["max_batch"], dep["max_len"], dtype)),
    }
