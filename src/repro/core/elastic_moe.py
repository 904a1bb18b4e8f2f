"""Membership-elastic MoE dispatch/combine (paper §3.4, §4.1) in JAX.

The paper's GPU-driven path — kernels read a device-resident peer table,
issue transfers to active peers only, and skip failed ranks by testing one
active bit — becomes, on TPU:

  * routing consults the mutable ``MembershipState`` arrays (graph-visible,
    content-mutable) to map logical experts to physical slots on ACTIVE ranks;
  * dispatch is a capacity-based ``all_to_all`` over the EP mesh axes inside
    ``shard_map`` (GShard/DeepEP-style); a failed rank's slots simply receive
    zero traffic because no routing-table entry points at them;
  * combine returns expert outputs with the same collective and applies the
    renormalized top-k weights in fp32.

One compiled executable covers steady state, degraded execution, and the
restored configuration — membership changes update table *contents* only.

Two dispatch layouts:
  dense  — fixed-capacity buffers [world, spr, cap, d]; predictable collective
           bytes (used by the dry-run/roofline), tokens over capacity dropped.
  ragged — dropless size-exchange dispatch (the DeepEP analogue): (token,
           choice) pairs are sorted by destination slot, per-destination
           counts are exchanged first, and only REAL tokens move (via
           ``ragged_all_to_all`` where jax provides it, a tight dense
           exchange otherwise); expert compute runs on group-sorted tokens
           through the ``gmm`` grouped-matmul kernel and the combine applies
           the inverse permutation with fp32 weights. Elastic semantics are
           identical: failed ranks receive zero traffic because no table
           entry points at them, and membership changes never recompile.

Invariants BOTH layouts must uphold (asserted by tests/test_dispatch_modes
and the registry-wide scenario tests; see docs/recovery-lifecycle.md and
docs/dispatch-modes.md):

  * **validity** — routing consults only the published membership arrays:
    a slot whose rank's active bit is clear can never be a destination, so
    a stale-in-flight table is impossible by construction;
  * **zero recompilation** — membership arrays are traced *arguments* with
    fixed shapes; fail/repair/rejoin rewrite contents only, so the
    compiled dispatch/combine (and its collectives) survive every
    transition — the paper's CUDA-graph-stability analogue;
  * **coverage** — the routing tables are derived from a placement that
    the EPLB guarantees covers every expert on active ranks; dispatch
    never has to handle an unhosted expert (the runtime raises
    CoverageLossError upstream instead);
  * ragged additionally guarantees **dropless**: dropped_fraction == 0 on
    any routing, enforced as a hard CI gate (never a trend).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.membership import MembershipState, REPLICA_HASH_PRIME


@dataclass(frozen=True)
class EPContext:
    """Static EP deployment geometry (compile-time)."""

    axis_names: tuple[str, ...] = ()   # mesh axes spanning the EP world
    world: int = 1
    slots_per_rank: int = 1
    capacity_factor: float = 2.0
    min_capacity: int = 8

    @property
    def num_slots(self) -> int:
        return self.world * self.slots_per_rank

    def capacity(self, tokens_per_rank: int, top_k: int) -> int:
        """Per-(dst-slot) capacity of the dense dispatch buffers."""
        expected = tokens_per_rank * top_k / max(self.num_slots, 1)
        cap = int(math.ceil(expected * self.capacity_factor))
        cap = max(cap, self.min_capacity)
        return int(-(-cap // 8) * 8)  # round up to multiple of 8 (lane-friendly)


# ---------------------------------------------------------------------------
# Elastic routing: logical expert -> (replica) physical slot, active ranks only
# ---------------------------------------------------------------------------


def elastic_route(
    logits: jax.Array,            # [T, E] router logits
    membership: MembershipState,
    top_k: int,
    token_ids: jax.Array,         # [T] global ids (replica hash)
    normalize: bool = True,
):
    """Top-k over *reachable* experts + replica selection from the mutable
    expert_to_slot table. Returns (experts[T,k], weights[T,k] f32, slots[T,k]).

    Experts whose replica_count is 0 are masked out — after a repaired
    placement this never triggers (coverage validity), but during the bounded
    window between failure detection and repair publication it is exactly the
    paper's 'route tokens only to valid experts on active ranks'.
    """
    valid = membership.replica_count > 0                     # [E]
    neg = jnp.finfo(jnp.float32).min
    masked = jnp.where(valid[None, :], logits.astype(jnp.float32), neg)
    probs = jax.nn.softmax(masked, axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)           # [T, k]
    if normalize:
        weights = weights / jnp.maximum(
            jnp.sum(weights, axis=-1, keepdims=True), 1e-9)
    # deterministic replica choice: spread tokens across replicas
    rc = jnp.maximum(membership.replica_count[experts], 1)   # [T, k]
    r = (token_ids[:, None] * REPLICA_HASH_PRIME + experts) % rc
    slots = jnp.take_along_axis(
        membership.expert_to_slot[experts.reshape(-1)],      # [T*k, MAX_R]
        r.reshape(-1, 1).astype(jnp.int32), axis=1,
    ).reshape(experts.shape)
    return experts, weights, slots


# ---------------------------------------------------------------------------
# Dense capacity-based dispatch/combine
# ---------------------------------------------------------------------------


def _bucket_positions_onehot(flat_slot: jax.Array, num_slots: int) -> jax.Array:
    """Reference formulation of ``_bucket_positions``: one-hot cumsum.
    Materializes an [N, num_slots] int32 intermediate — O(N*S) memory, which
    dominates the dispatch prologue at wide-EP slot counts. Kept as the
    correctness oracle for the sort-based version below."""
    onehot = jax.nn.one_hot(flat_slot, num_slots, dtype=jnp.int32)  # [N, S]
    pos = jnp.cumsum(onehot, axis=0) - 1                            # [N, S]
    return jnp.take_along_axis(pos, flat_slot[:, None], axis=1)[:, 0]


def _bucket_positions(flat_slot: jax.Array, num_slots: int) -> jax.Array:
    """Position of each (token, choice) entry within its destination-slot
    bucket. Sort-based: a stable argsort groups equal slots into runs, a
    running maximum over run-start indices yields each entry's offset within
    its run — O(N log N) and O(N) memory (vs the one-hot cumsum's O(N*S)).
    flat_slot: int32[N] in [0, num_slots). Returns int32[N]."""
    n = flat_slot.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.int32)
    order = jnp.argsort(flat_slot, stable=True)                     # [N]
    sorted_slot = flat_slot[order]
    idx = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_slot[1:] != sorted_slot[:-1]])
    run_start = jax.lax.cummax(jnp.where(is_start, idx, 0))
    pos_sorted = idx - run_start                                    # [N]
    return jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted)


def dispatch_combine_dense(
    x: jax.Array,                    # [T, d] LOCAL tokens (inside shard_map)
    slots: jax.Array,                # [T, k] destination physical slots
    weights: jax.Array,              # [T, k] fp32 combine weights
    expert_fn: Callable,             # ([S_local, R, d], slot_base) -> [S_local, R, d]
    ep: EPContext,
):
    """Capacity-based dispatch -> expert compute -> combine.

    Dense buffers are laid out [world, spr, cap, d]: dim0 is the all_to_all
    split axis (destination rank), dim1 the local slot on that rank. Sender
    computes positions within each destination-slot bucket; entries over
    capacity are dropped and their combine weight zeroed (GShard semantics;
    capacity_factor 2.0 makes drops statistically negligible — the drop rate
    is reported by the aux output and asserted small in tests).
    """
    T, d = x.shape
    k = slots.shape[1]
    spr = ep.slots_per_rank
    world = ep.world
    cap = ep.capacity(T, k)
    nbuf = world * spr * cap

    flat_slot = slots.reshape(-1).astype(jnp.int32)            # [N]
    pos = _bucket_positions(flat_slot, ep.num_slots)           # [N]
    ok = pos < cap                                             # capacity check
    # flat destination offset; invalid entries pushed out of bounds (dropped
    # by scatter mode=drop)
    f = flat_slot * cap + pos
    f = jnp.where(ok, f, nbuf)

    send = jnp.zeros((nbuf, d), x.dtype)
    send = send.at[f].set(jnp.repeat(x, k, axis=0), mode="drop")
    send = send.reshape(world, spr, cap, d)

    if ep.axis_names:
        recv = jax.lax.all_to_all(send, ep.axis_names, split_axis=0,
                                  concat_axis=0, tiled=False)
    else:
        recv = send                                             # world == 1
    # recv: [world_src, spr, cap, d] — tokens for MY spr local slots
    recv = recv.transpose(1, 0, 2, 3).reshape(spr, world * cap, d)

    y = expert_fn(recv)                                         # [spr, world*cap, d]

    y = y.reshape(spr, world, cap, d).transpose(1, 0, 2, 3)
    if ep.axis_names:
        back = jax.lax.all_to_all(y, ep.axis_names, split_axis=0,
                                  concat_axis=0, tiled=False)
    else:
        back = y
    back = back.reshape(nbuf, d)

    # gather each token's k contributions; dropped entries contribute zero
    gathered = jnp.take(back, jnp.where(ok, f, 0), axis=0)      # [N, d]
    w = (weights.reshape(-1) * ok.astype(weights.dtype))[:, None]
    out = jnp.sum((gathered.astype(jnp.float32) * w).reshape(T, k, d), axis=1)

    aux = {
        "dropped_fraction": 1.0 - jnp.mean(ok.astype(jnp.float32)),
        "capacity": cap,
    }
    return out.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Ragged (dropless, size-exchange) dispatch/combine — the DeepEP analogue
# ---------------------------------------------------------------------------


def _inverse_permutation(order: jax.Array) -> jax.Array:
    n = order.shape[0]
    return jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))


def _ragged_exchange(rows, send_sizes, recv_sizes, axis_names, *,
                     world: int, out_rows: int):
    """Move variable-size row chunks over the EP axes with
    ``lax.ragged_all_to_all``: the wire carries only real rows.

    rows is sorted by destination rank (the chunk for rank w starts at
    ``exclusive_cumsum(send_sizes)[w]``); ``recv_sizes`` is the other half
    of the size exchange. Returns [out_rows, d], source-major and
    compacted; rows past ``sum(recv_sizes)`` are unspecified."""
    send_off = jnp.cumsum(send_sizes) - send_sizes
    recv_off = jnp.cumsum(recv_sizes) - recv_sizes
    # output_offsets[w] = where MY chunk lands in w's source-major buffer =
    # recv_off[me] as computed BY w; one tiny all_to_all hands every source
    # its own column of the offset matrix
    out_off = jax.lax.all_to_all(recv_off.reshape(world, 1), axis_names,
                                 split_axis=0, concat_axis=0,
                                 tiled=False).reshape(world)
    out_buf = jnp.zeros((out_rows, rows.shape[1]), rows.dtype)
    return jax.lax.ragged_all_to_all(
        rows, out_buf, send_off.astype(jnp.int32),
        send_sizes.astype(jnp.int32), out_off.astype(jnp.int32),
        recv_sizes.astype(jnp.int32), axis_name=axis_names)


def dispatch_combine_ragged(
    x: jax.Array,                    # [T, d] LOCAL tokens (inside shard_map)
    slots: jax.Array,                # [T, k] destination physical slots
    weights: jax.Array,              # [T, k] fp32 combine weights
    grouped_expert_fn: Callable,     # ([R, d] group-sorted, group_sizes[spr])
                                     #   -> [R, d]
    ep: EPContext,
):
    """Dropless dispatch: sort (token, choice) pairs by destination slot,
    exchange per-destination counts, move only real tokens, run expert
    compute on group-sorted tokens (``gmm``-shaped: contiguous per-local-slot
    groups + group_sizes), combine via the inverse permutation in fp32.

    No capacity, no drops: every routed pair is served regardless of load
    skew (``aux["dropped_fraction"]`` is identically 0). The receive buffer
    uses the exact worst case (world × local pairs), so correctness never
    depends on a tuning factor; balanced load fills ~1/world of it and the
    wire carries only real rows (see ``dispatch_bytes_model``).

    Elastic semantics match the dense path: slots only ever point at ACTIVE
    ranks (elastic_route consults the mutable table), so failed ranks get
    zero traffic, and a membership patch changes only array contents.
    """
    T, d = x.shape
    k = slots.shape[1]
    n_pairs = T * k
    spr = ep.slots_per_rank
    world = ep.world

    flat_slot = slots.reshape(-1).astype(jnp.int32)            # [N]
    order = jnp.argsort(flat_slot, stable=True)                # dst-sorted
    inv = _inverse_permutation(order)
    xs = jnp.repeat(x, k, axis=0)[order]                       # [N, d]
    counts = jnp.bincount(flat_slot, length=ep.num_slots).astype(jnp.int32)

    aux = {"dropped_fraction": jnp.asarray(0.0, jnp.float32),
           "pairs": n_pairs}

    if not ep.axis_names or world == 1:
        # local: every slot is resident; the sort IS the dispatch
        y_sorted = grouped_expert_fn(xs, counts)
        y = y_sorted[inv]                                      # per pair
    else:
        cmat = counts.reshape(world, spr)                      # send counts
        send_sizes = cmat.sum(axis=1)                          # [world]
        # ---- size exchange: who sends how much to whom ----
        recv_cmat = jax.lax.all_to_all(cmat, ep.axis_names, split_axis=0,
                                       concat_axis=0, tiled=False)
        recv_sizes = recv_cmat.sum(axis=1)                     # [world] by src
        r_buf = n_pairs * world                                # exact bound
        xr = _ragged_exchange(xs, send_sizes, recv_sizes, ep.axis_names,
                              world=world, out_rows=r_buf)
        # received rows are source-major; within one source chunk they are
        # local-slot-sorted (the sender sorted by global slot id). Recover
        # each row's local slot from the count matrix, then group-sort.
        roff = jnp.cumsum(recv_sizes) - recv_sizes
        ridx = jnp.arange(r_buf)
        src = jnp.clip(jnp.searchsorted(roff, ridx, side="right") - 1,
                       0, world - 1)
        pos = ridx - roff[src]
        cum_ls = jnp.cumsum(recv_cmat, axis=1)                 # [world, spr]
        ls = (pos[:, None] >= cum_ls[src]).sum(axis=1)         # [r_buf]
        ls = jnp.where(ridx < recv_sizes.sum(), ls, spr)       # slack -> end
        order2 = jnp.argsort(ls, stable=True)
        inv2 = _inverse_permutation(order2)
        group_sizes = recv_cmat.sum(axis=0).astype(jnp.int32)  # [spr]
        yg = grouped_expert_fn(xr[order2], group_sizes)
        # back to source-major, then the mirror exchange returns each pair's
        # output to its sender in the original dst-sorted order (each
        # destination gets back exactly what it sent: n_pairs rows)
        y_back = _ragged_exchange(yg[inv2], recv_sizes, send_sizes,
                                  ep.axis_names, world=world,
                                  out_rows=n_pairs)
        y = y_back[inv]

    w = weights.reshape(-1).astype(jnp.float32)[:, None]
    out = jnp.sum((y.astype(jnp.float32) * w).reshape(T, k, d), axis=1)
    return out.astype(x.dtype), aux


def dispatch_bytes_model(ep: EPContext, tokens_per_rank: int, top_k: int,
                         d_model: int, itemsize: int = 2) -> dict:
    """Per-device on-wire bytes of one dispatch+combine round trip, both
    layouts (analytic: the ragged exchange's HLO buffers are sized for the
    worst case, while the wire carries only the real rows).

    dense:  both all_to_alls carry the full capacity-padded buffer
            [world, spr, cap, d] regardless of how many slots are real.
    ragged: both exchanges carry only the T*k real (token, choice) pairs
            (balanced load; skew moves the same global total), plus the
            int32 count exchange. At the default top_k=2 / cf=2.0 geometry
            dense pads by ~capacity_factor (and the lane/min-capacity
            round-up), so ragged moves >= 2x fewer bytes.
    """
    cap = ep.capacity(tokens_per_rank, top_k)
    n_pairs = tokens_per_rank * top_k
    dense = 2 * ep.world * ep.slots_per_rank * cap * d_model * itemsize
    size_exchange = 2 * ep.world * ep.slots_per_rank * 4
    ragged = 2 * n_pairs * d_model * itemsize + size_exchange
    return {
        "capacity": int(cap),
        "pairs_per_rank": int(n_pairs),
        "dense_bytes": int(dense),
        "ragged_bytes": int(ragged),
        "dense_over_ragged": float(dense / max(ragged, 1)),
    }


def expert_load_from_route(experts: jax.Array, weights: jax.Array,
                           num_experts: int) -> jax.Array:
    """Per-logical-expert token load of this batch (EPLB telemetry)."""
    onehot = jax.nn.one_hot(experts.reshape(-1), num_experts, dtype=jnp.float32)
    return jnp.sum(onehot, axis=0)


# ---------------------------------------------------------------------------
# Fixed-membership baseline (the DeepEP analogue for Fig. 9)
# ---------------------------------------------------------------------------


def fixed_route(
    logits: jax.Array,            # [T, E]
    slot_of_expert: np.ndarray,   # STATIC int32[E] — baked at trace time
    top_k: int,
    normalize: bool = True,
):
    """Fixed-membership routing: the expert->slot map is a compile-time
    constant (the analogue of DeepEP's preconfigured EP group). Same math as
    ``elastic_route`` minus the mutable-table consults."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    if normalize:
        weights = weights / jnp.maximum(
            jnp.sum(weights, axis=-1, keepdims=True), 1e-9)
    table = jnp.asarray(slot_of_expert, jnp.int32)
    slots = table[experts]
    return experts, weights, slots
