"""Three-tier expert-coverage repair (paper §3.5, §5.1).

After the elasticity-aware EPLB computes a covering placement over survivors,
the repair path satisfies it through the bandwidth-aware hierarchy:

  Tier 1 — local reuse:        slot already holds the expert -> metadata only
  Tier 2 — GPU-to-GPU reloc:   a surviving replica exists -> one *batched*
                               gather over the slot axis (on a sharded array
                               this lowers to EP-axis collectives: the paper's
                               'batched transfer schedule')
  Tier 3 — DRAM-backed reload: all live copies died -> fetch from the backup
                               service into device memory

The planner consults the active bitmap atomically per transfer (paper §5.1):
if a chosen Tier-2 source died between planning and execution, the expert is
re-planned to Tier 3.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import jax
import numpy as np

from repro.core.backup import BackupStore


@dataclass
class RepairPlan:
    num_slots: int
    tier1: list[int] = field(default_factory=list)            # dst slots reused
    tier2: list[tuple[int, int]] = field(default_factory=list)  # (dst, src)
    tier3: list[tuple[int, int]] = field(default_factory=list)  # (dst, expert)
    cleared: list[int] = field(default_factory=list)           # slots emptied
    unrecoverable: list[int] = field(default_factory=list)     # experts lost
    bytes_per_slot: int = 0

    @property
    def tier2_bytes(self) -> int:
        return len(self.tier2) * self.bytes_per_slot

    @property
    def tier3_bytes(self) -> int:
        return len(self.tier3) * self.bytes_per_slot

    def source_mix(self) -> dict[str, int]:
        """Repair-source mix (paper Fig. 10 middle)."""
        return {"local_reuse": len(self.tier1),
                "gpu_relocation": len(self.tier2),
                "dram_reload": len(self.tier3)}


def plan_repair(
    old_slot_to_expert: np.ndarray,       # placement before the failure
    new_slot_to_expert: np.ndarray,       # EPLB output over survivors
    active: np.ndarray,                    # bool[world] CURRENT active bitmap
    slots_per_rank: int,
    backup: Optional[BackupStore] = None,
    bytes_per_slot: int = 0,
    source_active: Optional[np.ndarray] = None,
    topology=None,
    load: Optional[np.ndarray] = None,     # float[E] expert load (EMA);
                                           # orders transfers hot-first
) -> RepairPlan:
    """``active`` gates transfer *destinations*; ``source_active`` (defaults
    to ``active``) gates Tier-2 *sources*. A planned drain passes the
    pre-transition mask as ``source_active`` so the departing rank — still
    alive during the transfer window, unlike a fault casualty — hands its
    uniquely-hosted experts over GPU-to-GPU instead of forcing Tier-3 DRAM
    reloads.

    ``topology`` (a ``FaultDomainTree``) makes Tier-2 source selection
    bandwidth-aware: among the live replicas of an expert, a source on the
    destination's own host (ICI) beats one under the same switch (host
    NIC), which beats a cross-switch copy (spine) — the paper's transfer
    hierarchy applied to source *choice*, with round-robin load-spreading
    inside the winning proximity class.

    ``load`` (per-expert routing mass, any positive scale) orders the
    Tier-2/Tier-3 transfer list by urgency: transfers that restore
    *coverage* (the expert has no Tier-1 slot left, so it serves nothing
    until a copy lands) come first, hottest expert first, then the
    remaining rebalancing transfers hottest-first. The ``tier2``/``tier3``
    lists are emitted in execution order, so the first entry is the first
    transfer on the wire — the skew tests assert a hot expert that lost
    every replica is the very first Tier-2 gather."""
    num_slots = len(new_slot_to_expert)
    active = np.asarray(active, bool)
    source_active = active if source_active is None \
        else np.asarray(source_active, bool)

    def rank_of(slot: int) -> int:
        return slot // slots_per_rank

    # Where does each expert still live, on *source-live* ranks, under the
    # OLD map?
    live_sources: dict[int, list[int]] = {}
    for s, e in enumerate(old_slot_to_expert):
        e = int(e)
        if e >= 0 and source_active[rank_of(s)]:
            live_sources.setdefault(e, []).append(s)

    plan = RepairPlan(num_slots=num_slots, bytes_per_slot=bytes_per_slot)

    # Pass 1: classify destinations. Tier-1 slots cost nothing, so they are
    # recorded immediately; actual transfers are collected and ordered below.
    transfers: list[tuple[int, int]] = []   # (dst slot, expert)
    tier1_experts: set[int] = set()
    for s in range(num_slots):
        if not active[rank_of(s)]:
            if old_slot_to_expert[s] >= 0:
                plan.cleared.append(s)
            continue
        e = int(new_slot_to_expert[s])
        if e < 0:
            continue
        if int(old_slot_to_expert[s]) == e:
            plan.tier1.append(s)                              # Tier 1
            tier1_experts.add(e)
            continue
        transfers.append((s, e))

    # Pass 2: order transfers by urgency — coverage-restoring copies (the
    # expert serves NOTHING until one lands) before rebalancing copies,
    # hottest expert first inside each class, destination slot as the
    # deterministic tie-break.
    if load is not None:
        w = np.maximum(np.asarray(load, np.float64), 0.0)

        def hot(e: int) -> float:
            return float(w[e]) if e < len(w) else 0.0
    else:
        def hot(e: int) -> float:
            return 0.0
    transfers.sort(key=lambda de: (de[1] in tier1_experts, -hot(de[1]), de[0]))

    rr: dict[int, int] = {}  # round-robin cursor per expert over its sources
    for s, e in transfers:
        srcs = [x for x in live_sources.get(e, ())
                if source_active[rank_of(x)]]                 # atomic re-check
        if srcs:
            if topology is not None:
                # keep only the closest proximity class to the destination
                prox = {x: topology.proximity(rank_of(s), rank_of(x))
                        for x in srcs}
                best = min(prox.values())
                srcs = [x for x in srcs if prox[x] == best]
            i = rr.get(e, 0)
            src = srcs[i % len(srcs)]
            rr[e] = i + 1
            plan.tier2.append((s, src))                       # Tier 2
        elif backup is not None and backup.has(e):
            plan.tier3.append((s, e))                         # Tier 3
        else:
            plan.unrecoverable.append(e)
    return plan


def revalidate_plan(
    plan: RepairPlan,
    new_slot_to_expert: np.ndarray,
    active: np.ndarray,
    slots_per_rank: int,
    backup: Optional[BackupStore] = None,
) -> RepairPlan:
    """Atomic bitmap consult at execution time (paper §5.1), generalized to
    overlapping failures: when a second failure lands between planning and
    execution, every transfer is re-checked against the CURRENT active bitmap.

      * a Tier-2 transfer whose source rank died is re-sourced from another
        surviving replica of the same expert when one exists, else escalated
        to Tier-3 (DRAM reload), else recorded unrecoverable,
      * any transfer whose destination rank died is dropped (the slot is
        cleared; the follow-up repair round will re-cover the expert).

    Returns a plan safe to execute against the current membership; identical
    to the input when nothing changed since planning.
    """
    active = np.asarray(active, bool)

    def rank_of(slot: int) -> int:
        return slot // slots_per_rank

    # surviving slots that (will) hold each expert and can serve as an
    # alternate gather source: Tier-1 slots already hold the expert, and a
    # live Tier-2 *source* holds it under the old placement
    alt_source: dict[int, int] = {}
    for s in plan.tier1:
        if active[rank_of(s)]:
            alt_source.setdefault(int(new_slot_to_expert[s]), s)
    for d2, s2 in plan.tier2:
        if active[rank_of(s2)]:
            alt_source.setdefault(int(new_slot_to_expert[d2]), s2)

    out = RepairPlan(num_slots=plan.num_slots,
                     bytes_per_slot=plan.bytes_per_slot,
                     cleared=list(plan.cleared),
                     unrecoverable=list(plan.unrecoverable))
    for s in plan.tier1:
        if active[rank_of(s)]:
            out.tier1.append(s)
        else:
            out.cleared.append(s)
    for dst, src in plan.tier2:
        if not active[rank_of(dst)]:
            out.cleared.append(dst)
            continue
        if active[rank_of(src)]:
            out.tier2.append((dst, src))
            continue
        e = int(new_slot_to_expert[dst])
        if e in alt_source:
            out.tier2.append((dst, alt_source[e]))        # re-source Tier 2
        elif backup is not None and e >= 0 and backup.has(e):
            out.tier3.append((dst, e))                    # escalate to Tier 3
        else:
            out.unrecoverable.append(e)
    for dst, e in plan.tier3:
        if active[rank_of(dst)]:
            out.tier3.append((dst, e))
        else:
            out.cleared.append(dst)
    return out


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------

@dataclass
class SlotMoves:
    """The weight movement of one or more composed repair plans, staged as
    what each MOVED slot will hold: a live source slot (Tier 2, an index)
    or a host copy from the backup service (Tier 3, arrays [L, ...]).
    Unlisted slots keep their contents. Nothing touches the device until
    :func:`apply_slot_moves`, so staging never holds a second copy of the
    expert weights and discarding it needs no undo."""

    src: dict[int, int] = field(default_factory=dict)     # dst -> live slot
    host: dict[int, object] = field(default_factory=dict)  # dst -> pytree

    def __bool__(self) -> bool:
        return bool(self.src or self.host)

    def then(self, plan: RepairPlan,
             backup: Optional[BackupStore] = None) -> "SlotMoves":
        """Compose ``plan`` on top of these moves. The plan was made against
        the STAGED placement, so a Tier-2 source reads what earlier plans
        put in that slot; all Tier-2 reads see the pre-plan state (one
        simultaneous gather), then Tier-3 reloads land."""
        src, host = dict(self.src), dict(self.host)
        for dst, s in plan.tier2:
            src.pop(dst, None)
            host.pop(dst, None)
            if s in self.host:
                host[dst] = self.host[s]
            elif self.src.get(s, s) != dst:
                src[dst] = self.src.get(s, s)
        if plan.tier3:
            assert backup is not None, "Tier-3 repairs need a backup store"
        for dst, e in plan.tier3:
            src.pop(dst, None)
            host[dst] = backup.fetch(e)
        return SlotMoves(src, host)


@partial(jax.jit, donate_argnums=(0,))
def _write_slots(slot_weights, dst2, src2, dst3, rows3):
    def write(a, rows):
        if dst2.shape[0]:
            a = a.at[:, dst2].set(a[:, src2])
        if rows is not None:
            a = a.at[:, dst3].set(rows.astype(a.dtype))
        return a
    if rows3 is None:
        return jax.tree_util.tree_map(lambda a: write(a, None), slot_weights)
    return jax.tree_util.tree_map(write, slot_weights, rows3)


def apply_slot_moves(slot_weights, moves: SlotMoves):
    """Write the moved slots into the slot-stacked leaves (slot axis 1 of
    every [L, S, ...] leaf) as ONE jitted update that DONATES the input
    leaves: Tier-2 moves are one batched gather over the slot axis (under
    EP sharding, the batched EP-axis transfer schedule), Tier-3 rows are
    one scatter of the host copies. The update is in place, so a repair
    never holds two copies of the expert weights; the input leaves are
    consumed."""
    if not moves:
        return slot_weights
    dst2 = sorted(moves.src)
    dst3 = sorted(moves.host)
    rows3 = (jax.tree_util.tree_map(lambda *xs: np.stack(xs, axis=1),
                                    *[moves.host[d] for d in dst3])
             if dst3 else None)
    return _write_slots(slot_weights, np.asarray(dst2, np.int32),
                        np.asarray([moves.src[d] for d in dst2], np.int32),
                        np.asarray(dst3, np.int32), rows3)


def apply_repair(slot_weights, plan: RepairPlan,
                 backup: Optional[BackupStore] = None):
    """Full repair of one plan: Tier-2 batched relocation, then Tier-3
    reloads (consumes ``slot_weights``, see :func:`apply_slot_moves`).
    Tier 1 requires no data movement (metadata was already updated by the
    placement publish)."""
    return apply_slot_moves(slot_weights, SlotMoves().then(plan, backup))


# ---------------------------------------------------------------------------
# Recovery-time cost model (drives the Fig. 1/10/11 simulations)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RecoveryCostModel:
    """Bandwidths/latencies for the simulated cluster. Defaults approximate
    the paper's testbed scaled to the TPU fabric model in DESIGN.md."""

    ici_gbps: float = 50.0          # per-link GB/s (Tier-2 relocation)
    host_gbps: float = 12.0         # host->device GB/s (Tier-3 reload)
    detect_s: float = 1.0           # timeout window (paper: 1 s)
    coordinate_s: float = 0.8       # EPLB + metadata broadcast + publish
    drain_s: float = 0.5            # in-flight requests failed & drained
    join_patch_s: float = 0.4       # peer-table refresh + placement broadcast

    def recovery_seconds(self, plan: RepairPlan, world: int,
                         slots_per_rank: int) -> dict[str, float]:
        """Phase breakdown, parallelized over ranks: each rank moves the bytes
        destined to its own slots; the wall time is the max over ranks."""
        per_rank_t2 = np.zeros(world)
        per_rank_t3 = np.zeros(world)
        for dst, _ in plan.tier2:
            per_rank_t2[dst // slots_per_rank] += plan.bytes_per_slot
        for dst, _ in plan.tier3:
            per_rank_t3[dst // slots_per_rank] += plan.bytes_per_slot
        t2 = float(per_rank_t2.max(initial=0.0)) / (self.ici_gbps * 1e9)
        t3 = float(per_rank_t3.max(initial=0.0)) / (self.host_gbps * 1e9)
        return {
            "detect": self.detect_s,
            "drain": self.drain_s,
            "coordinate": self.coordinate_s,
            "weight_transfer": t2 + t3,
            "total": self.detect_s + self.drain_s + self.coordinate_s + t2 + t3,
        }
