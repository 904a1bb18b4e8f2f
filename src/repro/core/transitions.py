"""Transactional membership control plane: propose -> plan -> validate ->
commit (ISSUE 4).

The paper's thesis is that partial-failure tolerance falls out of treating
EP membership as explicit, mutable runtime state. This module makes the
*mutation* itself first-class: every change to membership, placement,
slot-stacked params and the device-published :class:`MembershipState` —
whether triggered by a fault, a deferred join, a straggler re-place, or a
*planned* drain/scale operation — flows through one
:class:`MembershipTransaction`. The transaction stages all mutations on a
cloned :class:`~repro.core.membership.PeerTable` plus the staged moves of
the MoE slots (:class:`~repro.core.repair.SlotMoves`: source indices and
host copies of the moved slots only), and only
:meth:`MembershipTransaction.commit` applies them to the live runtime, so
the core invariants are enforced structurally instead of re-asserted in
every handler:

  * **epoch** — each commit bumps the host's monotonically increasing
    epoch and publishes it as ``MembershipState.version`` (subsuming the
    old ad-hoc ``PeerTable.version`` bumps): the device tables always
    carry the exact commit they came from;
  * **validity** — ``repro.core.validity.check`` runs against the staged
    state *before* publication; an invalid transition aborts with
    :class:`TransitionAborted` and the live table/params/membership are
    left byte-identical (nothing was mutated in place);
  * **zero recompilation** — commits only rewrite array contents through
    the existing content-patch publish path, never shapes.

On top of the transaction sit the :class:`TransitionPolicy` implementations
(:class:`ElasticPolicy` for the paper's EEP runtime,
:class:`FullRestartPolicy` for the fixed-membership baseline — previously
an attribute-monkeypatch the serving engine performed on the runtime) and
the :class:`ControlPlane` facade exposing *planned* operations: ``drain``,
``undrain``, ``scale_down``, ``scale_up``, ``rebalance``. A drain is a
replan + transfer with no detect/drain pause (the departing rank is still
alive, so it even serves as a Tier-2 source); a scale-up rides the
deferred-join warmup path; a rebalance re-places replicas against the
tracked per-expert routing mass without touching membership at all.
Lazarus/ReviveMoE-style planned elasticity and crash recovery are
the same substrate — this module is where that substrate lives.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import TYPE_CHECKING, Optional, Protocol, runtime_checkable

import numpy as np

from repro.core.placement import PlacementResult, eplb_place
from repro.core.repair import RepairPlan, SlotMoves, apply_slot_moves, \
    plan_repair, revalidate_plan
from repro.core.validity import check as validity_check

if TYPE_CHECKING:  # pragma: no cover - import cycle guard (runtime hosts us)
    from repro.runtime.elastic import ElasticEPRuntime

#: Every way membership can change. "bootstrap" is the initial publish.
TRANSITION_KINDS = ("bootstrap", "fault", "join", "straggler", "drain",
                    "undrain", "scale_down", "scale_up", "restart", "heal",
                    "rebalance")


class TransitionAborted(RuntimeError):
    """A membership transaction could not commit: the planned placement is
    infeasible, a repair is unrecoverable, or the staged state failed the
    validity check. The live table/params/membership are untouched."""

    def __init__(self, message: str, **detail):
        super().__init__(message)
        self.detail = detail
        self.recorded = False      # set once a transition_abort/coverage_loss
                                   # event has been emitted for this abort


# ---------------------------------------------------------------------------
# Slot-leaf helpers (the MoE expert weights a repair plan moves around)
# ---------------------------------------------------------------------------

def moe_slot_leaves(cfg, params) -> dict:
    """The slot-stacked expert weights: {path: leaf [n_periods, S, ...]}."""
    out = {}
    for gname, group in params.get("groups", {}).items():
        for lname, layer in group.items():
            moe = layer.get("moe")
            if moe is None:
                continue
            for wname in ("w_in", "w_gate", "w_out"):
                if wname in moe:
                    out[(gname, lname, wname)] = moe[wname]
    return out


def set_moe_slot_leaves(params, leaves: dict):
    """Swap MoE slot leaves into a params tree via a *targeted* nested-dict
    copy: only the dict spine along each (group, layer, "moe", weight) path
    is rebuilt; every untouched subtree (attention, norms, other layers) is
    shared with the input. A table patch swaps a few MoE leaves — walking
    and re-wrapping the entire param tree for that is pure overhead."""
    if not leaves:
        return params
    out = dict(params)
    groups = out["groups"] = dict(params["groups"])
    copied_groups: set = set()
    copied_layers: set = set()
    for (gname, lname, wname), leaf in leaves.items():
        if gname not in copied_groups:
            groups[gname] = dict(groups[gname])
            copied_groups.add(gname)
        if (gname, lname) not in copied_layers:
            layer = dict(groups[gname][lname])
            layer["moe"] = dict(layer["moe"])
            groups[gname][lname] = layer
            copied_layers.add((gname, lname))
        groups[gname][lname]["moe"][wname] = leaf
    return out


def slot_bytes(leaves: dict) -> int:
    """Bytes per slot across all stacked leaves (drives transfer timing and
    the tier2/tier3 byte telemetry)."""
    return int(sum(np.prod(l.shape[2:]) * l.dtype.itemsize * l.shape[0]
                   for l in leaves.values()))


# ---------------------------------------------------------------------------
# The transaction
# ---------------------------------------------------------------------------

_PROPOSED, _COMMITTED, _ABORTED = "proposed", "committed", "aborted"


@dataclass(frozen=True)
class KVPageManifest:
    """KV pages a planned drain must ship off the departing ranks.

    Produced by the serving engine (the only component that knows the live
    block tables) when the runtime opens a drain window, and attached to
    the drain's :class:`MembershipTransaction` as ``kv_manifest``: the
    page transfer is sequenced INSIDE the transaction — after the weight
    repair-transfer, before ``commit()`` publishes the shrunk table — so
    by the time the table patch lands every surviving rank already holds
    the KV it needs and re-admission replays nothing.
    """
    pages_total: int      # PHYSICAL pages held by all in-flight requests
    pages_moved: int      # the departing ranks' share (what actually ships)
    bytes_moved: int      # pages_moved * page_bytes (Tier-2 transfer timing)
    requests: int         # live requests whose KV the manifest covers
    page_bytes: int       # modeled bytes per page (block_size x token KV)
    # prefix-sharing dedup: block-table references vs physical pages. A
    # page shared by N requests appears N times in the logical count but
    # ships once — pages_deduped is the transfer the prefix cache saved.
    pages_logical: int = 0
    pages_deduped: int = 0


class MembershipTransaction:
    """One atomic membership transition: propose -> plan -> validate ->
    commit.

    The host is any object exposing the runtime surface (``cfg``,
    ``params``, ``table``, ``membership``, ``backup``, ``detector``,
    ``expert_load``, ``epoch``, ``record()``) — in practice an
    :class:`~repro.runtime.elastic.ElasticEPRuntime`. All mutations land on
    a cloned table and staged slot moves; nothing touches the host until
    :meth:`commit`, which (in order) re-runs the validity check against the
    staged state, bumps the host epoch, stamps it into
    ``MembershipState.version``, writes the moved slots into the live
    expert weights (one donating update), publishes the device arrays and
    swaps table/params/membership in one step. Any failure before the
    write leaves the host byte-identical.

    Cascade composition: :meth:`plan` may be called repeatedly (each call
    replans from the *staged* placement), :meth:`revalidate` re-checks an
    in-flight plan against the staged active bitmap after further
    deactivations, and :meth:`apply` folds a plan's weight movement into
    the staged slot moves — exactly the loop ``handle_failure`` drives when
    failures land mid-recovery.
    """

    def __init__(self, host, kind: str, *, incident: int = -1):
        assert kind in TRANSITION_KINDS, kind
        self.host = host
        self.kind = kind
        self.incident = incident
        self.state = _PROPOSED
        self.table = host.table.clone()          # staged control-plane state
        self.placement: Optional[PlacementResult] = None
        self.repair_plan: Optional[RepairPlan] = None
        self.plans: list[RepairPlan] = []        # every applied plan, in order
        self.rank_capacity: Optional[np.ndarray] = None
        self._moves = SlotMoves()               # staged weight movement
        self.epoch: Optional[int] = None         # set on commit
        # planned drains: the KV pages shipped off the departing ranks
        # inside this transaction's window (set by the runtime between the
        # weight transfer and commit; None when nothing was resident)
        self.kv_manifest: Optional[KVPageManifest] = None

    # -- guards -------------------------------------------------------------
    def _live(self) -> None:
        if self.state != _PROPOSED:
            raise RuntimeError(
                f"transaction is {self.state}; no further operations allowed")

    def _fail(self, message: str, **detail) -> "TransitionAborted":
        self.state = _ABORTED
        raise TransitionAborted(message, **detail)

    # -- propose-stage mutations (staged table only) -------------------------
    def deactivate(self, ranks, *, drained: bool = False) -> None:
        """Stage the removal of ``ranks`` (fault casualty or planned
        drain/scale-down — ``drained`` marks a deliberate departure so the
        relaunch controller leaves the rank alone)."""
        self._live()
        for r in ranks:
            if self.table.entries[r].active:
                self.table.deactivate(r, drained=drained)

    def activate(self, ranks) -> None:
        """Stage the (re)admission of ``ranks`` (join, undrain, scale-up,
        baseline restart refresh)."""
        self._live()
        for r in ranks:
            self.table.reactivate(r)

    def set_rank_capacity(self, capacity: np.ndarray) -> None:
        """Stage straggler de-weighting: capacity weights for the next
        :meth:`plan` (1.0 = full speed; no membership change)."""
        self._live()
        self.rank_capacity = np.asarray(capacity, np.float64)

    def is_active(self, rank: int) -> bool:
        return bool(self.table.entries[rank].active)

    @property
    def active_mask(self) -> np.ndarray:
        return self.table.active_mask

    # -- plan ----------------------------------------------------------------
    def bytes_per_slot(self) -> int:
        return slot_bytes(moe_slot_leaves(self.host.cfg, self.host.params))

    def plan(self, *, source_active: Optional[np.ndarray] = None
             ) -> Optional[RepairPlan]:
        """EPLB over the staged active set + 3-tier repair plan from the
        staged placement. Returns ``None`` for non-MoE archs (membership
        substrate only). ``source_active`` lets planned drains keep the
        departing (still-alive) ranks as Tier-2 sources. Raises
        :class:`TransitionAborted` when coverage is infeasible."""
        self._live()
        host = self.host
        if not host.cfg.is_moe:
            self.placement = None
            self.repair_plan = None
            return None
        old_s2e = self.table.slot_to_expert.copy()
        res = eplb_place(
            host.cfg.moe.num_experts, self.table.world,
            self.table.slots_per_rank, self.table.active_mask,
            load=host.expert_load, prev_slot_to_expert=old_s2e,
            max_replicas=self.table.max_replicas,
            rank_capacity=self.rank_capacity,
            topology=self.table.topology)
        if res.infeasible:
            self._fail(res.reason, reason=res.reason)
        self.placement = res
        self.repair_plan = plan_repair(
            old_s2e, res.slot_to_expert, self.table.active_mask,
            self.table.slots_per_rank, host.backup,
            bytes_per_slot=self.bytes_per_slot(),
            source_active=source_active,
            topology=self.table.topology,
            load=host.expert_load)
        return self.repair_plan

    def revalidate(self) -> RepairPlan:
        """Atomic bitmap consult at execution time: re-check the in-flight
        plan against the staged active set (which may have shrunk since
        :meth:`plan` — a Tier-2 source that died escalates to Tier-3)."""
        self._live()
        assert self.repair_plan is not None and self.placement is not None
        self.repair_plan = revalidate_plan(
            self.repair_plan, self.placement.slot_to_expert,
            self.table.active_mask, self.table.slots_per_rank,
            self.host.backup)
        return self.repair_plan

    def apply(self) -> None:
        """Fold the current plan's weight movement into the staged slot
        moves and stage the new placement. Aborts if the plan lost
        experts."""
        self._live()
        plan = self.repair_plan
        if plan is None:                    # non-MoE: nothing to move
            return
        if plan.unrecoverable:
            lost = sorted(plan.unrecoverable)
            self._fail(f"experts {lost} lost every live replica and backup "
                       f"copy", experts=lost)
        self._moves = self._moves.then(plan, self.host.backup)
        self.table.set_placement(self.placement.slot_to_expert)
        self.plans.append(plan)
        self.repair_plan = None

    # -- validate / commit ---------------------------------------------------
    def validate(self):
        """Dry-run the validity contract against the staged state (what
        :meth:`commit` enforces before publishing)."""
        self._live()
        return validity_check(self.table, self.table.to_device(),
                              reachable=self.host.detector.known_reachable())

    def commit(self, *, enforce_validity: bool = True):
        """Validate, bump the epoch, publish, swap. The ONLY path by which
        ``host.table`` / ``host.params`` / ``host.membership`` ever change.
        Returns the published :class:`MembershipState`.

        ``enforce_validity=False`` is reserved for recording *facts about a
        wreck*: when a fault's recovery aborts on coverage loss, the deaths
        are still real and the published peer set must stop claiming the
        dead ranks are active — even though the resulting (stopped)
        instance is formally invalid. Planned transitions never use it."""
        self._live()
        host = self.host
        if self.repair_plan is not None:    # planned but never applied
            self.apply()
        epoch = host.epoch + 1
        self.table.version = epoch          # device version IS the epoch
        staged = self.table.to_device()
        if enforce_validity:
            rep = validity_check(self.table, staged,
                                 reachable=host.detector.known_reachable())
            if not rep.valid:
                self._fail(f"validity check failed: {rep.violations[:3]}",
                           violations=rep.violations)
        # the moved slots land in place: the update donates the live slot
        # leaves, so the old params tree is consumed here
        new_params = host.params
        if self._moves:
            new_params = set_moe_slot_leaves(host.params, apply_slot_moves(
                moe_slot_leaves(host.cfg, host.params), self._moves))
        # the swap: atomic from the serving loop's point of view (between
        # forward passes; nothing below can raise)
        host.table = self.table
        host.params = new_params
        host.membership = staged
        host.epoch = epoch
        self.epoch = epoch
        self.state = _COMMITTED
        host.record("membership_commit", _incident=self.incident,
                    transition=self.kind, epoch=epoch,
                    active=int(self.table.active_mask.sum()),
                    **({} if enforce_validity else {"degraded": True}))
        return staged

    def abort(self) -> None:
        """Explicitly discard the staged state."""
        if self.state == _PROPOSED:
            self.state = _ABORTED


# ---------------------------------------------------------------------------
# Baseline cost model (lived in serving/engine.py before the redesign)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class FullRestartCostModel:
    """Fixed-membership baseline: the whole instance rebuilds (paper: 348 s).
    Phases follow the paper's description of the initialization path."""

    environment_setup_s: float = 40.0
    model_load_s: float = 180.0
    jit_warmup_s: float = 80.0
    graph_capture_s: float = 48.0

    @property
    def total_s(self) -> float:
        return (self.environment_setup_s + self.model_load_s
                + self.jit_warmup_s + self.graph_capture_s)


# ---------------------------------------------------------------------------
# Transition policies
# ---------------------------------------------------------------------------


@runtime_checkable
class TransitionPolicy(Protocol):
    """How a runtime answers membership-transition triggers. Selected at
    engine construction (replacing the old ``runtime.failure_policy``
    bound-method monkeypatch). Handlers return a dict whose ``"mode"`` key
    tells the control pump what actually happened (``"elastic"`` in-place
    transition vs ``"restart"`` full-instance bounce)."""

    name: str
    mutates_membership: bool

    def on_failure(self, rt: "ElasticEPRuntime", failed: list[int]) -> dict: ...
    def on_join_ready(self, rt: "ElasticEPRuntime", ranks: list[int]) -> dict: ...
    def on_drain(self, rt: "ElasticEPRuntime", ranks: list[int]) -> dict: ...
    def on_undrain(self, rt: "ElasticEPRuntime", ranks: list[int]) -> dict: ...
    def on_scale_down(self, rt: "ElasticEPRuntime", ranks: list[int]) -> dict: ...
    def on_scale_up(self, rt: "ElasticEPRuntime", ranks: list[int]) -> dict: ...
    def on_rebalance(self, rt: "ElasticEPRuntime", ranks: list[int]) -> dict: ...


class ElasticPolicy:
    """The paper's EEP behavior: every transition is an in-place
    transactional patch on the live instance."""

    name = "elastic"
    mutates_membership = True

    def on_failure(self, rt, failed):
        return {"mode": "elastic", "phases": rt.handle_failure(failed)}

    def on_join_ready(self, rt, ranks):
        rt._join_batch(ranks)
        return {"mode": "elastic"}

    def on_drain(self, rt, ranks):
        return {"mode": "elastic", **rt.drain_ranks(ranks, kind="drain")}

    def on_scale_down(self, rt, ranks):
        return {"mode": "elastic",
                **rt.drain_ranks(ranks, kind="scale_down")}

    def on_undrain(self, rt, ranks):
        return {"mode": "elastic", **rt.undrain_ranks(ranks)}

    def on_scale_up(self, rt, ranks):
        return {"mode": "elastic", **rt.scale_up_ranks(ranks)}

    def on_rebalance(self, rt, ranks):
        return {"mode": "elastic", **rt.rebalance_placement()}


class FullRestartPolicy:
    """Fixed-membership baseline: the only transition a static stack can
    express is rebuilding the whole instance — for faults AND for planned
    maintenance (which is exactly why the paper's mutable membership
    matters). Telemetry-wise every answer is a single ``full-restart``
    span; there are no phases to break down, which is the point."""

    name = "full-restart"
    mutates_membership = False

    def __init__(self, restart_model: Optional[FullRestartCostModel] = None):
        self.restart_model = restart_model or FullRestartCostModel()

    def _restart(self, rt, ranks) -> dict:
        incident = rt.obs.incident("full-restart", ranks=ranks)
        rt.record("full_restart_begin", _incident=incident, ranks=list(ranks))
        txn = rt.begin("restart", incident=incident)
        with rt.obs.span("full-restart", incident, ranks=list(ranks)):
            # the rebuilt instance comes back whole: every rank (restarted
            # casualties AND the survivors that just sat through the
            # outage) resumes heartbeating at the same instant; injector
            # events due inside the outage fire at their scheduled times
            rt._advance(self.restart_model.total_s)
            for r in ranks:
                rt.detector.mark_reachable(r)
            txn.activate(ranks)
            txn.commit()
        rt.record("full_restart_done", _incident=incident,
                  seconds=self.restart_model.total_s)
        return {"mode": "restart", "seconds": self.restart_model.total_s}

    def on_failure(self, rt, failed):
        return self._restart(rt, failed)

    # planned transitions: a static stack answers them the only way it can
    on_drain = _restart
    on_scale_down = _restart

    def on_join_ready(self, rt, ranks):        # never relaunches -> no joins
        return {"mode": "restart"}

    def on_undrain(self, rt, ranks):           # nothing ever drained
        return {"mode": "restart"}

    def on_scale_up(self, rt, ranks):
        return {"mode": "restart"}

    def on_rebalance(self, rt, ranks):
        # A static placement cannot move replicas toward the hot experts;
        # a 348 s rebuild would come back with the same table, so the only
        # honest answer is "can't" — which is exactly the contrast the
        # skew scenarios measure.
        return {"mode": "restart"}


# ---------------------------------------------------------------------------
# ControlPlane facade: planned operations
# ---------------------------------------------------------------------------

#: Control-event kinds the planned operations enqueue (handled by
#: ``ElasticEPRuntime.pump_control`` between forward passes).
PLANNED_OPS = ("drain", "undrain", "scale_down", "scale_up", "rebalance")


def _flatten(ranks) -> list[int]:
    out: list[int] = []
    for r in ranks:
        if isinstance(r, (list, tuple, set, np.ndarray)):
            out.extend(int(x) for x in r)
        else:
            out.append(int(r))
    return out


class ControlPlane:
    """Planned-operations facade over the transition machinery.

    ``drain``/``undrain``/``scale_down``/``scale_up``/``rebalance``
    dispatch through the
    runtime's :class:`TransitionPolicy` immediately (returning the handled
    ranks and the outcome mode); the ``request*`` variants enqueue a
    control event so the transition lands at the next serving-step
    boundary, where the engine can observe it (requeue semantics) via the
    pump's :class:`~repro.runtime.elastic.ControlSummary`.
    """

    def __init__(self, runtime):
        self.rt = runtime

    # -- eligibility: which of the requested ranks the op applies to --------
    def _eligible(self, op: str, ranks) -> list[int]:
        rt = self.rt
        entries = rt.table.entries
        ranks = _flatten(ranks)
        # split-brain fence: a partitioned rank is unreachable from the
        # majority side — no planned op may target it until the partition
        # heals (its state will be reconciled by the heal transaction)
        part = getattr(rt.detector, "is_partitioned", None)
        if part is not None:
            ranks = [r for r in ranks if not part(r)]
        if op in ("drain", "scale_down"):
            return [r for r in ranks if entries[r].active]
        if op == "undrain":
            # is_recovering guard: a cold undrain already relaunching must
            # not be restarted from scratch by an idempotent re-request
            return [r for r in ranks
                    if not entries[r].active and entries[r].drained
                    and not rt.controller.is_recovering(r)]
        if op == "scale_up":
            return [r for r in ranks if not entries[r].active
                    and not rt.controller.is_recovering(r)]
        if op == "rebalance":
            # rank-less: the op targets the whole active set (any requested
            # ranks are ignored); "handled" is the set whose replicas may
            # move, so the pump sees a non-empty result when serving ranks
            # exist at all
            return [r for r in range(rt.table.world) if entries[r].active]
        raise ValueError(f"unknown planned op {op!r}")

    def dispatch(self, op: str, ranks) -> tuple[list[int], Optional[str]]:
        """Run one planned op through the policy. Returns (handled ranks,
        outcome mode) — ``([], None)`` when no rank was eligible, mode
        ``"aborted"`` when the transaction rolled back."""
        handled = self._eligible(op, ranks)
        if not handled:
            return [], None
        handler = getattr(self.rt.policy, f"on_{op}")
        try:
            out = handler(self.rt, handled) or {}
        except TransitionAborted as e:
            # state is untouched; make sure the abort left telemetry even
            # when the handler raised before recording (e.g. an undrain
            # whose join patch failed validation)
            if not e.recorded:
                self.rt.record("transition_abort", op=op,
                               ranks=list(handled), **e.detail)
            return handled, "aborted"
        return handled, out.get("mode", "elastic")

    # -- immediate operations ------------------------------------------------
    def drain(self, *ranks):
        """Planned maintenance drain: replan + transfer, no detect pause.
        Sequencing inside the window: weight repair-transfer, then the
        departing ranks' KV pages ship to the survivors (the transaction's
        ``kv_manifest``, the ``kv-migrate`` phase), and only then does the
        table patch publish the shrunk membership — transfer before
        table-patch, so re-admitted requests find their pages intact."""
        return self.dispatch("drain", ranks)

    def undrain(self, *ranks):
        """Bring a drained (still-warm) rank back: one batched table patch."""
        return self.dispatch("undrain", ranks)

    def scale_down(self, *ranks):
        """Elastic shrink: like a drain, but the ranks are decommissioned."""
        return self.dispatch("scale_down", ranks)

    def scale_up(self, *ranks):
        """Elastic regrow: rides the deferred-join warmup path."""
        return self.dispatch("scale_up", ranks)

    def rebalance(self):
        """Popularity-driven re-place: EPLB over the *current* active set
        against the tracked per-expert routing mass, committed through the
        same transaction path as a drain (epoch bump, byte-identical
        abort) — but with membership untouched, so there is no detect, no
        warmup, and no rank leaves. Rank-less by construction."""
        return self.dispatch("rebalance", ())

    # -- deferred (step-boundary) request ------------------------------------
    def request(self, op: str, ranks) -> None:
        """Enqueue a planned op; it commits at the next control pump, where
        the serving engine observes it (drain requeue semantics)."""
        if op not in PLANNED_OPS:
            raise ValueError(f"unknown planned op {op!r}")
        self.rt._enqueue(op, _flatten(ranks))
