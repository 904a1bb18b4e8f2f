"""MoE layer: elastic (membership-table-driven) and fixed-membership variants.

Parameters are stored per PHYSICAL SLOT ([num_slots, ...]), not per logical
expert — the slot axis is what EP-shards, and what the three-tier repair
executor rewrites. Replicas of one logical expert hold identical weights
(enforced at init; preserved by repair).

The distributed path is a shard_map island inside the jitted step: tokens
sharded over the EP axes, slot weights sharded over the slot axis, membership
arrays replicated. Expert-internal tensor parallelism (mixtral/jamba) shards
the expert hidden dim over ``tp_axes`` with a psum after the down-projection
(baseline; §Perf iterates on reduce-scatter variants).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.elastic_moe import (
    EPContext,
    dispatch_combine_dense,
    dispatch_combine_ragged,
    elastic_route,
    expert_load_from_route,
    fixed_route,
)
from repro.core.membership import MembershipState
from repro.kernels.moe_gmm import fused_moe_ffn, gmm
from repro.models.layers import activation_fn, is_gated


def _interpret_kernels() -> bool:
    """Pallas kernels run in interpret mode off-TPU (CPU CI / smoke tests)."""
    return jax.default_backend() != "tpu"


@dataclass(frozen=True)
class MoEDeployment:
    """Compile-time MoE parallelism geometry."""

    ep: EPContext
    tp_axes: tuple[str, ...] = ()     # expert-internal TP axes
    mesh: object = None               # jax Mesh; None -> local path
    # Beyond-paper (EXPERIMENTS SSPerf P1): reduce the expert-TP partial sums
    # AFTER the combine all_to_all, on [T_local, d] tokens, instead of inside
    # the expert on the k*cf-padded [spr, world*cap, d] capacity buffers —
    # the psum volume drops by the top_k * capacity_factor padding factor.
    # False = paper-faithful baseline (DeepEP-style reduce-then-combine).
    defer_tp_reduce: bool = True
    # Dispatch layout (ISSUE 2 tentpole): "dense" = capacity-padded buffers
    # (predictable bytes, drops over capacity); "ragged" = dropless
    # size-exchange dispatch riding the gmm grouped-matmul kernel.
    dispatch: str = "dense"
    # Dense-path expert compute through the fused Pallas FFN kernel instead
    # of the unfused einsum chain (interpret mode off-TPU).
    use_fused_ffn: bool = False
    # Ragged-path grouped matmul: True = gmm Pallas kernel, False = pure-jnp
    # grouped einsum, None = auto (kernel on TPU; the jnp form on CPU, where
    # interpret-mode Pallas is orders of magnitude slower than XLA and would
    # dominate simulation wall time).
    use_pallas_gmm: Optional[bool] = None
    # Row block of gmm's tiled schedule (calls whose rows do not fit VMEM).
    gmm_block_t: int = 128

    @property
    def distributed(self) -> bool:
        return self.mesh is not None and bool(self.ep.axis_names)


def local_deployment(num_slots: int, capacity_factor: float = 2.0,
                     dispatch: str = "dense", **kw) -> MoEDeployment:
    return MoEDeployment(
        ep=EPContext(axis_names=(), world=1, slots_per_rank=num_slots,
                     capacity_factor=capacity_factor),
        dispatch=dispatch, **kw)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def moe_layer_init(key, cfg: ArchConfig, num_slots: int,
                   slot_to_expert: np.ndarray, dtype,
                   expert_dtype: str = ""):
    """Router + slot-stacked expert weights with replica-consistent contents.
    ``expert_dtype``: optional narrower storage for routed expert weights
    (SSPerf P2: fp8 weight streaming on the memory-bound decode path)."""
    m = cfg.moe
    d, de, E = cfg.d_model, m.d_expert, m.num_experts
    e_dtype = jnp.dtype(expert_dtype) if expert_dtype else dtype
    ks = jax.random.split(key, 5)
    s_in, s_out = 1.0 / np.sqrt(d), 1.0 / np.sqrt(de)
    idx = jnp.asarray(np.clip(np.asarray(slot_to_expert), 0, E - 1))

    def logical(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    def per_slot(k, shape, scale):
        # every slot draws from its logical expert's key: replicas hold
        # identical weights, written straight into the [S, ...] layout
        return jax.vmap(lambda e: logical(jax.random.fold_in(k, e), shape,
                                          scale))(idx).astype(e_dtype)

    p = {
        "router": logical(ks[2], (d, E), s_in),
        "w_in": per_slot(ks[0], (d, de), s_in),
        "w_out": per_slot(ks[1], (de, d), s_out),
    }
    if is_gated(cfg.activation):
        p["w_gate"] = per_slot(ks[3], (d, de), s_in)
    if m.num_shared_experts:
        dse = m.d_shared_expert * m.num_shared_experts
        p["shared"] = {
            "w_in": logical(ks[4], (d, dse), s_in),
            "w_out": logical(jax.random.fold_in(ks[4], 1), (dse, d),
                             1.0 / np.sqrt(dse)),
        }
        if is_gated(cfg.activation):
            p["shared"]["w_gate"] = logical(jax.random.fold_in(ks[4], 2),
                                            (d, dse), s_in)
    return p


def slot_weight_keys(p) -> list[str]:
    return [k for k in ("w_in", "w_gate", "w_out") if k in p]


# ---------------------------------------------------------------------------
# Expert compute (per local slots)
# ---------------------------------------------------------------------------


@jax.named_scope("moe.expert")
def _expert_ffn(recv, w_in, w_gate, w_out, activation, tp_axes,
                use_fused: bool = False):
    """recv: [spr, R, d]; w_*: [spr, d, de_local] / [spr, de_local, d].
    Weights may be stored narrower (fp8) and upcast at use (the HBM read is
    the narrow dtype; the MXU computes in the activation dtype)."""
    w_in = w_in.astype(recv.dtype)
    w_out = w_out.astype(recv.dtype)
    w_gate = w_gate.astype(recv.dtype) if w_gate is not None else None
    if use_fused:
        # fused Pallas kernel: the [R, de] expert-hidden activation never
        # leaves VMEM (two HBM round trips saved vs the einsum chain)
        y = fused_moe_ffn(recv, w_in, w_out, w_gate, activation=activation,
                          interpret=_interpret_kernels())
    else:
        act = activation_fn(activation)
        h = jnp.einsum("srd,sde->sre", recv, w_in)
        if w_gate is not None:
            g = jnp.einsum("srd,sde->sre", recv, w_gate)
            h = act(g) * h
        else:
            h = act(h)
        y = jnp.einsum("sre,sed->srd", h, w_out)
    if tp_axes:
        y = jax.lax.psum(y, tp_axes)   # reduce the de-sharded partial sums
        # (baseline path; the deferred variant reduces after combine instead)
    return y


def _grouped_matmul(x, w, group_sizes, dep: MoEDeployment):
    """Ragged-path building block: x [R, d] group-sorted, w [G, d_in, d_out].
    Dispatches to the gmm Pallas kernel (TPU, or explicitly requested) or a
    pure-jnp grouped einsum with identical semantics (CPU default —
    interpret-mode Pallas inside the serve step would dominate sim time)."""
    use = dep.use_pallas_gmm
    if use is None:
        use = not _interpret_kernels()
    if use:
        return gmm(x, w, group_sizes, block_t=dep.gmm_block_t,
                   interpret=_interpret_kernels())
    starts = jnp.cumsum(group_sizes) - group_sizes
    gid = jnp.clip(jnp.searchsorted(starts, jnp.arange(x.shape[0]),
                                    side="right") - 1, 0, w.shape[0] - 1)
    return jnp.einsum("td,tdf->tf", x, w[gid],
                      preferred_element_type=jnp.float32).astype(x.dtype)


@jax.named_scope("moe.expert")
def _expert_ffn_grouped(xg, group_sizes, w_in, w_gate, w_out, activation,
                        tp_axes, dep: MoEDeployment):
    """Ragged-path expert compute: xg [R, d] sorted by local slot with
    contiguous per-slot groups (sizes in group_sizes [spr]); the three
    projections each run as one grouped matmul over the real tokens only —
    no capacity padding anywhere."""
    act = activation_fn(activation)
    w_in = w_in.astype(xg.dtype)
    w_out = w_out.astype(xg.dtype)
    h = _grouped_matmul(xg, w_in, group_sizes, dep)
    if w_gate is not None:
        g = _grouped_matmul(xg, w_gate.astype(xg.dtype), group_sizes, dep)
        h = act(g) * h
    else:
        h = act(h)
    y = _grouped_matmul(h.astype(xg.dtype), w_out, group_sizes, dep)
    if tp_axes:
        y = jax.lax.psum(y, tp_axes)
    return y


def _shared_ffn(p, x, activation):
    act = activation_fn(activation)
    h = jnp.einsum("td,df->tf", x, p["w_in"])
    if "w_gate" in p:
        h = act(jnp.einsum("td,df->tf", x, p["w_gate"])) * h
    else:
        h = act(h)
    return jnp.einsum("tf,fd->td", h, p["w_out"])


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _moe_island(x, router, w_in, w_gate, w_out, shared, membership,
                *, cfg: ArchConfig, dep: MoEDeployment, fixed_s2e,
                x_axes: tuple = ()):
    """Per-EP-rank body (runs under shard_map when distributed).
    x: [T_local, d]. ``x_axes``: mesh axes the token dim is sharded over
    (pod + EP axes); pods run independent EP instances — the all_to_all only
    spans ``ep.axis_names``."""
    ep = dep.ep
    m = cfg.moe
    T = x.shape[0]
    if x_axes:
        rank = jax.lax.axis_index(x_axes)
        token_ids = rank * T + jnp.arange(T, dtype=jnp.int32)
    else:
        token_ids = jnp.arange(T, dtype=jnp.int32)

    # device-trace scopes: moe.route (router, replica choice through the
    # membership), moe.exchange (dispatch and combine), and inside it
    # moe.expert (the expert FFN)
    with jax.named_scope("moe.route"):
        logits = jnp.einsum("td,de->te", x, router) * m.router_scale
        if fixed_s2e is not None:
            experts, weights, slots = fixed_route(
                logits, fixed_s2e, m.top_k, m.normalize_router_weights)
        else:
            experts, weights, slots = elastic_route(
                logits, membership, m.top_k, token_ids,
                m.normalize_router_weights)

    inner_tp = () if (dep.defer_tp_reduce and dep.tp_axes) else dep.tp_axes
    with jax.named_scope("moe.exchange"):
        if dep.dispatch == "ragged":
            grouped_fn = partial(_expert_ffn_grouped, w_in=w_in,
                                 w_gate=w_gate, w_out=w_out,
                                 activation=cfg.activation, tp_axes=inner_tp,
                                 dep=dep)
            y, aux = dispatch_combine_ragged(x, slots, weights, grouped_fn,
                                             ep)
        else:
            expert_fn = partial(_expert_ffn, w_in=w_in, w_gate=w_gate,
                                w_out=w_out, activation=cfg.activation,
                                tp_axes=inner_tp, use_fused=dep.use_fused_ffn)
            y, aux = dispatch_combine_dense(x, slots, weights,
                                            lambda r: expert_fn(r), ep)
    if dep.defer_tp_reduce and dep.tp_axes:
        # SSPerf P1: TP partial sums ride the combine a2a and reduce here on
        # [T_local, d] — k*cf-times less psum volume than inside the expert
        y = jax.lax.psum(y, dep.tp_axes)
    if shared is not None:
        ys = _shared_ffn(shared, x, cfg.activation)
        if dep.tp_axes:
            ys = jax.lax.psum(ys, dep.tp_axes)
        y = y + ys
    load = expert_load_from_route(experts, weights, m.num_experts)
    if x_axes:
        load = jax.lax.psum(load, x_axes)
        aux["dropped_fraction"] = jax.lax.pmean(
            aux["dropped_fraction"], x_axes)
    return y, load, aux["dropped_fraction"]


def moe_param_specs(dep: MoEDeployment, p) -> dict:
    """PartitionSpecs of one MoE layer's params ``p`` on ``dep.mesh``: slot
    weights sharded over the EP axes (slot dim) and the expert-TP axes
    (expert hidden dim), router replicated. The shard_map island's
    in_specs, and the shardings to draw the weights into."""
    ep_axes = tuple(dep.ep.axis_names)
    tp = tuple(dep.tp_axes)
    tp_spec = tp[0] if len(tp) == 1 else (tp if tp else None)
    ep_spec = ep_axes[0] if len(ep_axes) == 1 else ep_axes
    specs = dict(router=P(None, None),
                 w_in=P(ep_spec, None, tp_spec),
                 w_out=P(ep_spec, tp_spec, None))
    if "w_gate" in p:
        specs["w_gate"] = P(ep_spec, None, tp_spec)
    if "shared" in p:
        specs["shared"] = {k: (P(tp_spec, None) if k == "w_out"
                               else P(None, tp_spec)) for k in p["shared"]}
    return specs


def moe_apply(cfg: ArchConfig, p, x, membership: MembershipState,
              dep: MoEDeployment, fixed_s2e: Optional[np.ndarray] = None):
    """x: [T, d] tokens (global view). Returns (y [T, d], aux dict).

    The token dim shards over (pod +) EP axes; pods run independent EP
    instances. T is padded up to that divisor — pad tokens carry zero combine
    weight (they consume dispatch capacity: the honest cost of wide-EP decode
    at small global batches)."""
    shared = p.get("shared")
    w_gate = p.get("w_gate")

    if not dep.distributed:
        body = partial(_moe_island, cfg=cfg, dep=dep, fixed_s2e=fixed_s2e)
        y, load, dropped = body(x, p["router"], p["w_in"], w_gate,
                                p["w_out"], shared, membership)
        return y, {"expert_load": load, "dropped_fraction": dropped}

    mesh = dep.mesh
    ep_axes = tuple(dep.ep.axis_names)
    x_axes = (("pod",) if "pod" in mesh.axis_names else ()) + ep_axes
    denom = int(np.prod([mesh.shape[a] for a in x_axes]))
    T = x.shape[0]
    T_pad = -(-T // denom) * denom
    if T_pad != T:
        x = jnp.pad(x, ((0, T_pad - T), (0, 0)))

    body = partial(_moe_island, cfg=cfg, dep=dep, fixed_s2e=fixed_s2e,
                   x_axes=x_axes)
    x_spec = x_axes[0] if len(x_axes) == 1 else x_axes
    specs = moe_param_specs(dep, p)
    specs["x"] = P(x_spec, None)
    specs["membership"] = jax.tree_util.tree_map(lambda _: P(), membership)
    out_specs = (P(x_spec, None), P(), P())
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(specs["x"], specs["router"], specs["w_in"],
                  specs.get("w_gate"), specs["w_out"], specs.get("shared"),
                  specs["membership"]),
        out_specs=out_specs,
        check_vma=False,
    )
    y, load, dropped = fn(x, p["router"], p["w_in"], w_gate, p["w_out"],
                          shared, membership)
    if T_pad != T:
        y = y[:T]
    return y, {"expert_load": load, "dropped_fraction": dropped}
