"""ElasticEPRuntime — the live EP instance (paper Fig. 5/6 end to end).

Couples the core substrate (membership, EPLB, 3-tier repair, backup,
detector, deferred-join controller) with the compiled serving step.

Every membership mutation — fault shrink, deferred-join batch, straggler
re-place, planned drain/undrain, elastic scale — is staged and published
through ONE path: ``repro.core.transitions.MembershipTransaction``
(propose -> plan -> validate -> commit). Each commit bumps the runtime's
monotonic ``epoch`` (mirrored into the device-published
``MembershipState.version``) and re-runs the validity check against the
staged state before publication, so the invariants below are enforced
structurally rather than re-asserted per handler:

  * **validity** — after every committed transition the peer set, expert
    placement and graph-visible routing tables satisfy
    ``repro.core.validity.check``: no routing entry targets an inactive
    rank, and the published device tables mirror the host `PeerTable`;
  * **zero recompilation** — the compiled executable is built ONCE;
    commits only rewrite membership array *contents* and slot-weight
    *contents*, never shapes, so healthy ranks never recompile (the
    paper's no-CUDA-graph-recapture property; tests assert the jit cache
    size stays at 1 across runs mixing faults, drains and scale-ups);
  * **coverage** — every logical expert keeps >= 1 active replica, or the
    runtime records an explicit ``coverage_loss`` event and raises
    ``CoverageLossError`` instead of serving unhosted experts. A *planned*
    transition that would lose coverage simply aborts
    (``TransitionAborted``) and leaves the instance untouched — unlike a
    fault, nothing has actually broken yet.

How the runtime reacts to transitions is a pluggable
``TransitionPolicy`` (``ElasticPolicy`` = the paper's EEP behavior;
``FullRestartPolicy`` = the fixed-membership baseline), selected at
serving-engine construction. Planned operations are issued through
``self.control`` (``repro.core.transitions.ControlPlane``): ``drain`` /
``undrain`` / ``scale_down`` / ``scale_up`` / ``rebalance``.

Telemetry: every transition is recorded through ``self.obs``
(``repro.obs.phases.PhaseClock``) as phase-tagged spans/events using the
canonical phase vocabulary (detect, replan, repair-transfer, warmup,
table-patch, rejoin, plus the planned-transition phases drain and
scale-down — defined in docs/recovery-lifecycle.md). The flat
``timeline`` list is kept in lockstep for backward compatibility; both are
fed by the single ``record()`` path.

On this CPU container the EP world is *simulated*: the slot axis lives on
one device and a deterministic SimClock + RecoveryCostModel supply the
timing the paper measures on real hardware (recovery phases, reintegration
pauses, throughput traces). On a real mesh the same runtime drives the
shard_map deployment — only `deployment` changes.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from repro.configs.base import ArchConfig
from repro.core.backup import BackupStore
from repro.core.failure import (
    CoverageLossError,
    FailureDetector,
    FailureInjector,
    RankState,
    SimClock,
)
from repro.core.membership import MembershipState, PeerTable
from repro.core.reintegration import ReintegrationController, WarmupCostModel
from repro.core.topology import FaultDomainTree, flat_topology
from repro.core.straggler import StragglerMonitor
from repro.core.repair import RecoveryCostModel
from repro.core.transitions import (
    PLANNED_OPS,
    ControlPlane,
    ElasticPolicy,
    MembershipTransaction,
    TransitionAborted,
    TransitionPolicy,
    moe_slot_leaves,
    set_moe_slot_leaves,
)
from repro.models.model import Deployment
from repro.obs.phases import PhaseClock

__all__ = [
    "ControlEvent", "ControlSummary", "ElasticEPRuntime", "TimelineEvent",
    "moe_slot_leaves", "set_moe_slot_leaves",
]


@dataclass
class TimelineEvent:
    t: float
    kind: str            # "failure" | "recovery_done" | "join" | ...
    detail: dict = field(default_factory=dict)


@dataclass
class ControlEvent:
    """One pending control-plane transition awaiting its handler."""
    kind: str                    # "failure_detected" | "join_ready" |
                                 # "drain" | "undrain" | "scale_down" |
                                 # "scale_up"
    ranks: tuple[int, ...] = ()


@dataclass
class ControlSummary:
    """What one control pump did — consumed by the serving engine to decide
    requeue/trace actions without re-deriving runtime state. Planned
    transitions report separately from faults because their serving
    semantics differ (graceful preemption vs failed-and-retried)."""
    failures_handled: list[int] = field(default_factory=list)
    joined: list[int] = field(default_factory=list)
    warmups_aborted: list[int] = field(default_factory=list)
    drained: list[int] = field(default_factory=list)
    undrained: list[int] = field(default_factory=list)
    scaled_down: list[int] = field(default_factory=list)
    scaled_up: list[int] = field(default_factory=list)
    rebalanced: list[int] = field(default_factory=list)  # ranks whose
                                   # replicas a popularity rebalance may move
                                   # (no rank leaves; nothing to evict)
    restarts: list[int] = field(default_factory=list)   # baseline bounces


class ElasticEPRuntime:
    """One live EP instance with explicit mutable membership."""

    def __init__(self, cfg: ArchConfig, params, table: PeerTable, *,
                 deployment: Optional[Deployment] = None,
                 backup_nodes: int = 2,
                 cost_model: Optional[RecoveryCostModel] = None,
                 warmup_model: Optional[WarmupCostModel] = None,
                 expert_load_ema: float = 0.9,
                 base_throughput: float = 7200.0,
                 dispatch: Optional[str] = None,
                 policy: Optional[TransitionPolicy] = None,
                 popularity_aware: bool = True):
        self.cfg = cfg
        self.params = params
        self.table = table
        # fault-domain layout: a table built without an explicit topology
        # (degenerate flat tree) adopts the config's host/switch geometry,
        # so correlated-failure planning and domain anti-affinity see the
        # same rank -> host -> switch map the scenario/launcher declared
        if table.topology == flat_topology(table.world):
            table.topology = FaultDomainTree(
                table.world,
                ranks_per_host=getattr(cfg, "ranks_per_host", 1),
                hosts_per_switch=getattr(cfg, "hosts_per_switch", 1))
        if deployment is None:
            from repro.models.moe import local_deployment
            deployment = Deployment(
                moe=local_deployment(table.num_slots, cfg.capacity_factor,
                                     dispatch=dispatch or cfg.dispatch_mode))
        elif dispatch is not None and dispatch != deployment.moe.dispatch:
            raise ValueError(
                f"dispatch={dispatch!r} conflicts with the provided "
                f"deployment's mode {deployment.moe.dispatch!r}")
        self.dpl = deployment
        self.dispatch = deployment.moe.dispatch
        self.clock = SimClock()
        # phase-aware telemetry: every record()/span rides this one recorder
        # (scenario name is stamped by the scenario runner)
        self.obs = PhaseClock(self.clock.now, dispatch=self.dispatch,
                              sample_active=self.active_fraction)
        self.detector = FailureDetector(table.world, self.clock)
        self.injector = FailureInjector(self.detector)
        self.controller = ReintegrationController(self.clock, warmup_model)
        self.cost_model = cost_model or RecoveryCostModel()
        self.base_throughput = base_throughput
        self.expert_load = np.ones(
            (cfg.moe.num_experts,), np.float64) if cfg.is_moe else None
        self.load_ema = expert_load_ema
        #: when False the runtime is deliberately popularity-BLIND: the EMA
        #: never learns the router distribution, so every planner input
        #: stays uniform — the contrast arm of the skew regression tests.
        self.popularity_aware = popularity_aware
        #: ground-truth router distribution the simulated traffic follows
        #: (set by the scenario `skew` op; None/uniform = no skew). This is
        #: what the *world* does; ``expert_load`` is what the runtime has
        #: *learned* about it.
        self.router_skew: Optional[np.ndarray] = None

        # DRAM-backed backup service (paper SS5.2)
        self.backup = BackupStore(num_nodes=backup_nodes)
        slots = moe_slot_leaves(cfg, params)
        if slots:
            self.backup.build_from_slots(slots, table.slot_to_expert)

        self.straggler = StragglerMonitor(table.world)
        self.rank_slowdown = np.ones(table.world)   # sim: injected slowness
        self.timeline: list[TimelineEvent] = []
        # fence log: every epoch-invalidation of a suspected/partitioned
        # rank (admin surface + scenario harvesting)
        self.fence_events: list[dict] = []
        #: injector events fired inside an ``_advance`` pause, awaiting the
        #: next ``_poll_transitions`` (which records/enqueues them)
        self._fired_backlog: list = []
        self.record("start")
        self.events_log: list[str] = []
        self.recompile_count = 0        # must stay 0 across fail/rejoin
        self._repair_jit_cache = {}

        # control-event queue: detections/join-readiness/planned operations
        # become events drained FIFO by pump_control() — polling is decoupled
        # from handling so every event source (detector, join controller,
        # the ControlPlane facade) shares one dispatch path. Cascades
        # detected *mid*-recovery are composed inside handle_failure itself,
        # not re-queued.
        self.control_queue: deque[ControlEvent] = deque()
        # pluggable transition policy (replaces the old failure_policy
        # bound-method monkeypatch): the serving engine selects the
        # full-restart baseline policy at construction.
        self.policy: TransitionPolicy = policy or ElasticPolicy()
        # planned-operations facade: drain/undrain/scale_down/scale_up
        self.control = ControlPlane(self)
        # KV-migration hook: the serving engine (when its pool can pin and
        # move pages) registers a callback returning a KVPageManifest for
        # a set of departing ranks; drain_ranks sequences the page
        # transfer inside the drain window, before the table patch.
        self.kv_migration_source = None

        # bootstrap commit: the initial device publication is itself a
        # transaction, so `epoch`, `MembershipState.version` and the
        # validity check are in force from the very first step.
        self.epoch = table.version
        self.membership: MembershipState = self.begin("bootstrap").commit()

    # ------------------------------------------------------------------
    # Transactions
    # ------------------------------------------------------------------
    def begin(self, kind: str, incident: int = -1) -> MembershipTransaction:
        """Open a membership transaction (propose -> plan -> validate ->
        commit). The ONLY way membership/placement/params/device state
        change on this runtime."""
        return MembershipTransaction(self, kind, incident=incident)

    def set_policy(self, policy: TransitionPolicy) -> None:
        """(Re)bind the transition policy — one engine drives a runtime at
        a time, so the most recently constructed engine's policy wins."""
        self.policy = policy

    # ------------------------------------------------------------------
    # Telemetry
    # ------------------------------------------------------------------
    def record(self, _kind: str, _incident: Optional[int] = None, **detail):
        """Single emission path: the enriched event (incident/phase/step/
        active-fraction tags) goes to ``self.obs``; the flat ``timeline``
        keeps the legacy shape for existing consumers. ``_incident`` tags
        events emitted outside any phase span. The event kind is
        underscored so ``detail`` may itself carry a ``kind`` key."""
        ev = self.obs.emit(_kind, _incident=_incident, **detail)
        self.timeline.append(TimelineEvent(ev.t, _kind, detail))

    def active_fraction(self) -> float:
        return float(self.table.active_mask.mean())

    def throughput_now(self) -> float:
        """Modeled serving throughput of the current configuration: wide-EP
        decoding is bandwidth/compute-proportional to the live rank count."""
        return self.base_throughput * self.active_fraction()

    def update_expert_load(self, load) -> None:
        """Fold one step's per-expert routing mass into the EMA the
        planners read, and mirror the normalized distribution into the
        peer table so every commit publishes it
        (``MembershipState.expert_load``). A popularity-blind runtime
        (``popularity_aware=False``) discards the observation — its
        planners keep seeing the uniform prior, which is exactly the
        contrast the skew gates measure."""
        if self.expert_load is None or not self.popularity_aware:
            return
        load = np.asarray(load, np.float64)
        if load.sum() > 0:
            self.expert_load = (self.load_ema * self.expert_load
                                + (1 - self.load_ema) * load)
            self.table.expert_load = (
                self.expert_load / self.expert_load.sum()).astype(np.float32)

    # -- router skew (simulated traffic popularity) ------------------------
    def set_router_skew(self, weights) -> None:
        """Set the ground-truth router distribution the simulated traffic
        follows (scenario ``skew`` op). ``None`` resets to uniform."""
        if self.expert_load is None:
            return
        if weights is None:
            self.router_skew = None
            return
        w = np.maximum(np.asarray(weights, np.float64), 0.0)
        if w.shape != self.expert_load.shape or w.sum() <= 0:
            raise ValueError(f"skew weights must be positive with shape "
                             f"{self.expert_load.shape}, got {w!r}")
        self.router_skew = w / w.sum()

    def router_distribution(self) -> Optional[np.ndarray]:
        """The true per-expert routing mass of current traffic (uniform
        unless a skew was injected); None for non-MoE archs."""
        if self.expert_load is None:
            return None
        if self.router_skew is not None:
            return self.router_skew
        e = len(self.expert_load)
        return np.full((e,), 1.0 / e)

    def expert_replica_counts(self) -> dict[int, int]:
        """Active replicas per logical expert under the current placement."""
        if self.expert_load is None:
            return {}
        return {e: len(slots)
                for e, slots in self.table.expert_to_slots().items()}

    def load_imbalance(self) -> float:
        """max/mean per-rank load of the CURRENT placement serving the TRUE
        router distribution (each expert's mass splits evenly over its
        active replicas). 1.0 = perfectly balanced; the serving engine
        divides modeled throughput by this, so a hot expert crammed onto
        too few replicas costs real (simulated) tokens — which is what the
        skew scenarios' throughput-restore gates measure."""
        dist = self.router_distribution()
        if dist is None:
            return 1.0
        e2s = self.table.expert_to_slots()
        spr = self.table.slots_per_rank
        rank_load = np.zeros((self.table.world,), np.float64)
        for e, slots in e2s.items():
            if not slots:
                continue
            share = dist[e] / len(slots)
            for s in slots:
                rank_load[s // spr] += share
        act = self.table.active_mask
        if not act.any() or rank_load[act].sum() <= 0:
            return 1.0
        mean = rank_load[act].mean()
        return float(rank_load[act].max() / mean) if mean > 0 else 1.0

    # ------------------------------------------------------------------
    # The failure -> shrink -> repair path (paper SS3.4/3.5), generalized to
    # overlapping failures: recovery is a phased state machine that re-polls
    # the detector at phase boundaries and composes a fresh repair round when
    # another rank dies mid-recovery (cascade), instead of a one-shot
    # transition that assumes the failure set is frozen. The whole composed
    # recovery is ONE transaction: rounds replan/revalidate on the staged
    # state, and a single commit publishes the final configuration.
    # ------------------------------------------------------------------
    def poll_failures(self) -> list[int]:
        fresh, _ = self._poll_transitions()
        return fresh

    def _poll_transitions(self) -> tuple[list[int], list[int]]:
        """Fire due injector events, convert re-failures of mid-warmup ranks
        into warmup aborts, and return (newly detected failures, aborted
        warmups). The single poll sequence behind poll_failures, the
        mid-recovery phase boundaries, and pump_control."""
        fired = self._fired_backlog + self.injector.step()
        self._fired_backlog = []
        aborted = self._restart_refailed_warmups(fired)
        for ev in fired:
            if ev.kind == "partition" and ev.ranks:
                # the cut itself is observable only as silence — record the
                # split so traces can tell a partition from a crash
                self.record("partition", ranks=sorted(ev.ranks),
                            minority=len(ev.ranks),
                            majority=self.table.world - len(ev.ranks))
            elif ev.kind == "heal" and ev.ranks:
                self.record("partition_healed", ranks=sorted(ev.ranks))
                self._enqueue("partition_heal", sorted(ev.ranks))
        return self.detector.poll(), aborted

    def _restart_refailed_warmups(self, fired) -> list[int]:
        """An injected failure that targets a rank currently mid-warmup is a
        warmup abort (the relaunched process died again), not a fresh
        detection: the detector already reported it, so the only action is
        restarting its local warmup. Returns the aborted ranks. Only real
        process deaths count — a suspicion, partition or heal event against
        a warming rank is not a relaunch failure."""
        aborted = []
        for ev in fired:
            if ev.kind not in ("sigkill", "hang"):
                continue
            for r in ev.ranks:
                if self.controller.is_recovering(r):
                    self.controller.restart_warmup(r)
                    # telemetry: the in-flight warmup span ends aborted and a
                    # fresh one opens under the SAME incident (same saga)
                    self.obs.close_span(("warmup", r), aborted=True)
                    self.obs.open_span(("warmup", r), "warmup",
                                       incident=self.obs.incident_of(r),
                                       rank=r, restarted=True)
                    self.record("warmup_abort",
                                _incident=self.obs.incident_of(r), rank=r)
                    aborted.append(r)
        return aborted

    def _poll_mid_recovery(self, txn: MembershipTransaction) -> list[int]:
        """Phase-boundary poll during an in-flight recovery: fire any
        injected events whose time has come and report newly detected
        failures (judged against the TRANSACTION's staged membership — the
        live table is untouched until commit) so the current repair round
        can be restarted."""
        fresh, _ = self._poll_transitions()
        return [r for r in fresh if txn.is_active(r)]

    def handle_failure(self, failed: list[int]) -> dict:
        """Restore live-EP validity on the surviving ranks; composes follow-on
        failures detected while the repair is in flight. Returns the
        accumulated phase breakdown (paper Fig. 10 left)."""
        incident = self.obs.incident("failure", ranks=failed)
        self.record("failure", _incident=incident, ranks=list(failed))
        txn = self.begin("fault", incident=incident)
        pending = [r for r in failed if txn.is_active(r)]
        # Measured detection latency: the detect span reaches BACK to the
        # casualties' oldest heartbeat — detection is imperfect and its
        # latency depends on HOW the rank failed (a sigkill confirms at
        # timeout_s, a hang/partition only after the suspicion grace
        # window) — instead of charging a configured constant. Only the
        # drain advances the clock here: the detection window already
        # elapsed in wall time before the verdict fired. A direct
        # handle_failure call without a detector verdict (unit tests,
        # baseline bounce) falls back to the modeled constant.
        ages = [self.detector.heartbeat_age(r) for r in failed
                if r in self.detector.reported]
        detect_s = max(ages) if ages else self.cost_model.detect_s
        phases = {"detect": detect_s,
                  "drain": self.cost_model.drain_s,
                  "coordinate": 0.0, "weight_transfer": 0.0}
        with self.obs.span("detect", incident,
                           t_start=self.clock.now() - detect_s,
                           ranks=sorted(failed), drain_s=phases["drain"],
                           measured=bool(ages)):
            self._advance(phases["drain"])

        casualties: set[int] = set()
        rounds = 0
        try:
            while True:
                rounds += 1
                txn.deactivate(pending)    # peer-set repair (staged)
                casualties.update(pending)
                for r in pending:
                    self.obs.bind_rank(r, incident)  # cascade casualties
                pending = []

                if not self.cfg.is_moe:
                    # dense arch: membership substrate only (no experts)
                    with self.obs.span("replan", incident, round=rounds):
                        self._advance(self.cost_model.coordinate_s)
                    phases["coordinate"] += self.cost_model.coordinate_s
                    pending = self._poll_mid_recovery(txn)
                    if pending:
                        self.record("recovery_restart", _incident=incident,
                                    ranks=sorted(pending), round=rounds)
                        continue
                    txn.commit()    # publish the shrunken peer set
                    break

                # expert-coverage repair: EPLB over survivors + 3-tier plan
                # (an infeasible shrink aborts the transaction -> converted
                # to CoverageLossError below)
                plan = txn.plan()

                # coordination phase (EPLB + metadata broadcast); a failure
                # that lands here invalidates the plan -> restart the round
                with self.obs.span("replan", incident, round=rounds,
                                   tier2=len(plan.tier2),
                                   tier3=len(plan.tier3)):
                    self._advance(self.cost_model.coordinate_s)
                phases["coordinate"] += self.cost_model.coordinate_s
                pending = self._poll_mid_recovery(txn)
                if pending:
                    self.record("recovery_restart", _incident=incident,
                                ranks=sorted(pending), round=rounds)
                    continue

                # execution: the transfers are in flight for the window the
                # cost model predicts; a rank can die INSIDE that window, so
                # poll once it elapses and re-check every transfer against
                # the staged bitmap (paper §5.1's atomic consult): transfers
                # sourced from a casualty escalate to Tier-3 DRAM reloads
                # before execution, and a follow-up round re-covers whatever
                # the casualty hosted.
                ph = self.cost_model.recovery_seconds(
                    plan, self.table.world, self.table.slots_per_rank)
                with self.obs.span("repair-transfer", incident,
                                   round=rounds) as xfer_span:
                    self._advance(ph["weight_transfer"])
                    phases["weight_transfer"] += ph["weight_transfer"]
                    pending = self._poll_mid_recovery(txn)
                    if pending:
                        txn.deactivate(pending)
                        self.record("recovery_restart", _incident=incident,
                                    ranks=sorted(pending), round=rounds)
                        n_t3 = len(plan.tier3)
                        plan = txn.revalidate()
                        if len(plan.tier3) > n_t3:
                            self.record("transfer_escalation",
                                        _incident=incident,
                                        escalated=len(plan.tier3) - n_t3)
                            extra = self.cost_model.recovery_seconds(
                                plan, self.table.world,
                                self.table.slots_per_rank)["weight_transfer"] \
                                - ph["weight_transfer"]
                            if extra > 0:
                                self._advance(extra)
                                phases["weight_transfer"] += extra
                    # transfer order (experts, wire order): the plan emits
                    # tier2/tier3 hot-coverage-first, and the skew tests
                    # assert the hottest uncovered expert ships first
                    s2e = txn.placement.slot_to_expert
                    xfer_span.meta.update(
                        tier2_bytes=plan.tier2_bytes,
                        tier3_bytes=plan.tier3_bytes,
                        tier2_experts=[int(s2e[d]) for d, _ in plan.tier2],
                        tier3_experts=[int(e) for _, e in plan.tier3])
                    txn.apply()     # aborts if the plan lost experts
                    if not pending:
                        # graph-visible routing repair: validate + publish
                        # the staged configuration (content patch; bumps
                        # the epoch). The slot moves land on the device
                        # here, so the span's wall time is their real work
                        # (the SimClock charged the model's)
                        txn.commit()
                if pending:
                    continue
                break

            # split-brain fencing: for casualties that may in fact still be
            # alive (false suspicion, network partition) the commit's epoch
            # bump IS the fence — any write they attempt against the old
            # epoch is rejected by the scheduler's epoch check. Record the
            # fence so the admin surface and scenarios can see it.
            for r in sorted(casualties):
                k = self.detector.kind_of.get(r)
                if k not in ("suspect", "partition"):
                    continue
                inc_r = self.obs.incident_of(r, incident)
                self.obs.mark("fence", inc_r, rank=r, kind=k,
                              epoch=self.epoch)
                self.record("fence", _incident=inc_r, rank=r, kind=k,
                            epoch=self.epoch)
                self.fence_events.append({
                    "t": self.clock.now(), "rank": r, "kind": k,
                    "epoch": self.epoch, "incident": inc_r})
        except TransitionAborted as e:
            if "violations" in e.detail:
                # a validity failure at commit is NOT coverage loss — it is
                # an invariant regression and must fail loudly (the
                # pre-transactional code asserted here), never be absorbed
                # by an expect_coverage_loss scenario
                raise
            # the recovery failed, but the deaths are still facts: publish
            # the staged deactivations (and nothing else) so the peer set
            # stops claiming the dead ranks are active. The instance is
            # formally invalid either way — serving stops on the raise —
            # so this degraded commit skips the validity gate.
            dead = [r for r in range(self.table.world)
                    if not txn.table.entries[r].active
                    and self.table.entries[r].active]
            if dead:
                wreck = self.begin("fault", incident=incident)
                wreck.deactivate(dead)
                wreck.commit(enforce_validity=False)
            detail = dict(e.detail)
            self.record("coverage_loss", _incident=incident, **detail)
            msg = str(e) if "experts" in detail else f"cannot shrink: {e}"
            raise CoverageLossError(msg) from None

        last = txn.plans[-1] if txn.plans else None
        phases["total"] = sum(phases.values())
        phases["rounds"] = rounds
        self.record("recovery_done", _incident=incident, phases=phases,
                    epoch=self.epoch,
                    mix=last.source_mix() if last else {},
                    tier2_bytes=last.tier2_bytes if last else 0,
                    tier3_bytes=last.tier3_bytes if last else 0)
        # relaunch every rank that is now inactive asynchronously (deferred
        # join) — including casualties of mid-recovery cascades, but NOT
        # deliberately drained/decommissioned ranks, and NOT partitioned
        # ranks: their processes are alive on the minority side, so they
        # rejoin warm when the partition heals instead of relaunching
        for r in range(self.table.world):
            entry = self.table.entries[r]
            if (not entry.active and not entry.drained
                    and not self.controller.is_recovering(r)
                    and not self.detector.is_partitioned(r)):
                self.controller.schedule_relaunch(r)
                self.obs.open_span(("warmup", r), "warmup",
                                   incident=self.obs.incident_of(r, incident),
                                   rank=r)
        return phases

    # ------------------------------------------------------------------
    # Event-queue control pump: one call per serving step enqueues newly
    # polled transitions and drains the queue FIFO (observation order).
    # Planned operations (drain/undrain/scale) requested through the
    # ControlPlane facade ride the same queue and dispatch through the
    # same policy.
    # ------------------------------------------------------------------
    def pump_control(self) -> ControlSummary:
        summary = ControlSummary()
        fresh, aborted = self._poll_transitions()
        summary.warmups_aborted += aborted
        if fresh:
            self._enqueue("failure_detected", fresh)
        ready = self.controller.poll_join_ready()
        if ready:
            self._enqueue("join_ready", ready)
        while self.control_queue:
            ev = self.control_queue.popleft()
            if ev.kind == "failure_detected":
                ranks = [r for r in ev.ranks if self.table.entries[r].active]
                if ranks:
                    out = self.policy.on_failure(self, ranks) or {}
                    summary.failures_handled += ranks
                    if out.get("mode") == "restart":
                        summary.restarts += ranks
            elif ev.kind == "join_ready":
                ranks = [r for r in ev.ranks
                         if self.controller.state_of(r) == RankState.JOIN_READY]
                if ranks:
                    self.policy.on_join_ready(self, ranks)
                    summary.joined += ranks
            elif ev.kind == "partition_heal":
                # the healed minority rejoins WARM (its processes never
                # died): one batched table patch, composed into the same
                # incident the partition opened. Ranks never fenced (the
                # cut healed before detection) are still active — nothing
                # to do for them.
                ranks = [r for r in ev.ranks
                         if not self.table.entries[r].active
                         and not self.table.entries[r].drained
                         and not self.controller.is_recovering(r)]
                if ranks and self.policy.mutates_membership:
                    self._rejoin_batch(ranks, kind="heal")
                    summary.joined += ranks
            elif ev.kind in PLANNED_OPS:
                handled, mode = self.control.dispatch(ev.kind, ev.ranks)
                if not handled or mode == "aborted":
                    continue
                if ev.kind == "rebalance":
                    # a fixed placement cannot move replicas: the baseline
                    # policy's answer is a genuine no-op, not a bounce
                    if mode != "restart":
                        summary.rebalanced += handled
                elif mode == "restart":
                    summary.restarts += handled
                elif ev.kind == "drain":
                    summary.drained += handled
                elif ev.kind == "undrain":
                    # only ranks the commit actually re-activated: a cold
                    # rank (died while drained) merely began relaunching —
                    # serving was never paused, and it will surface in
                    # `joined` when its deferred join lands
                    summary.undrained += [
                        r for r in handled if self.table.entries[r].active]
                elif ev.kind == "scale_down":
                    summary.scaled_down += handled
                elif ev.kind == "scale_up":
                    summary.scaled_up += handled
        return summary

    def _enqueue(self, kind: str, ranks) -> None:
        self.control_queue.append(ControlEvent(kind, tuple(ranks)))

    # ------------------------------------------------------------------
    # Reintegration (paper SS3.6/4.2), generalized to join storms: every
    # rank that is JOIN_READY at the same poll is incorporated with ONE
    # EPLB pass and ONE table patch, so a storm of N rejoiners costs the
    # healthy ranks a single join pause instead of N. Undrains ride the
    # same batched-patch path (kind="undrain").
    # ------------------------------------------------------------------
    def poll_reintegration(self) -> list[int]:
        """Between forward passes, healthy ranks poll for join-ready peers
        and incorporate them with an in-place table patch."""
        ready = self.controller.poll_join_ready()
        if ready:
            self._join_batch(ready)
        return ready

    def _join_batch(self, ranks: list[int]) -> None:
        self._rejoin_batch(ranks, kind="join")

    def _rejoin_batch(self, ranks: list[int], *, kind: str = "join") -> None:
        """ONE batched table patch incorporating ranks ready to serve:
        deferred-join completions ("join") and planned undrains
        ("undrain") share this path."""
        # telemetry: each rejoiner's background warmup span ends now (no-op
        # for undrained ranks, which never warmed up — they stayed hot)
        for rank in ranks:
            self.obs.close_span(("warmup", rank))
        incident = self.obs.incident_of(ranks[0], -1)
        txn = self.begin(kind, incident=incident)
        with self.obs.span("table-patch", incident, ranks=sorted(ranks),
                           kind=kind):
            for rank in ranks:
                self.detector.mark_reachable(rank)
            txn.activate(ranks)      # refresh entries (endpoint epoch)
            txn.plan()               # EPLB over the extended active set
            txn.commit()             # apply + validate + publish
            self._advance(self.cost_model.join_patch_s)
        for rank in ranks:
            self.controller.complete_join(rank)
            self.record(kind, _incident=self.obs.incident_of(rank, incident),
                        rank=rank, epoch=self.epoch)
            self.obs.mark("rejoin", self.obs.incident_of(rank, incident),
                          rank=rank)
        if len(ranks) > 1:
            self.record(f"{kind}_batch", _incident=incident,
                        ranks=sorted(ranks),
                        patch_s=self.cost_model.join_patch_s)

    # ------------------------------------------------------------------
    # Planned transitions (beyond the paper's unplanned faults): the same
    # transaction machinery serves deliberate elasticity — maintenance
    # drains, elastic shrink/regrow. A drain is a replan + transfer with
    # NO detect/drain pause, and the departing rank (still alive) serves
    # as a Tier-2 source for its uniquely-hosted experts; a scale-up rides
    # the deferred-join warmup path.
    # ------------------------------------------------------------------
    def drain_ranks(self, ranks: list[int], *, kind: str = "drain") -> dict:
        """Planned removal of ``ranks`` (maintenance drain or elastic
        scale-down). Raises ``TransitionAborted`` — leaving the instance
        untouched — when the remaining ranks cannot cover every expert."""
        assert kind in ("drain", "scale_down")
        phase = "drain" if kind == "drain" else "scale-down"
        incident = self.obs.incident(kind, ranks=ranks)
        txn = self.begin(kind, incident=incident)
        t0 = self.clock.now()
        try:
            with self.obs.span(phase, incident, ranks=sorted(ranks)):
                # the departing ranks stay live through the transfer window:
                # they are Tier-2 sources under the PRE-transition mask
                source = self.table.active_mask
                txn.deactivate(ranks, drained=True)
                plan = txn.plan(source_active=source)
                self._advance(self.cost_model.coordinate_s)
                if plan is not None:
                    xfer = self.cost_model.recovery_seconds(
                        plan, self.table.world,
                        self.table.slots_per_rank)["weight_transfer"]
                    if xfer > 0:
                        self._advance(xfer)
                # transfer-before-table-patch: the departing ranks' KV
                # pages ship to the survivors over the same Tier-2 window
                # the weights just used, so re-admitted requests find
                # their pages intact and replay NOTHING. The serving
                # engine owns the block tables; it registered the
                # manifest source at construction (paged pool only).
                manifest = (self.kv_migration_source(sorted(ranks))
                            if self.kv_migration_source is not None else None)
                if manifest is not None and manifest.pages_moved > 0:
                    with self.obs.span("kv-migrate", incident,
                                       pages=manifest.pages_moved,
                                       bytes=manifest.bytes_moved,
                                       requests=manifest.requests):
                        self._advance(
                            manifest.bytes_moved
                            / (self.cost_model.ici_gbps * 1e9))
                    txn.kv_manifest = manifest
                txn.commit()
        except TransitionAborted as e:
            self.record("transition_abort", _incident=incident, op=kind,
                        ranks=list(ranks), **e.detail)
            e.recorded = True
            raise
        # (obs.incident() above already bound every rank to this incident,
        # so later undrain/scale-up rejoins compose into the same saga)
        pause = self.clock.now() - t0
        last = txn.plans[-1] if txn.plans else None
        self.record(kind, _incident=incident, ranks=list(ranks),
                    pause_s=round(pause, 6), epoch=self.epoch,
                    mix=last.source_mix() if last else {},
                    tier2_bytes=last.tier2_bytes if last else 0,
                    tier3_bytes=last.tier3_bytes if last else 0,
                    kv_pages_moved=(txn.kv_manifest.pages_moved
                                    if txn.kv_manifest else 0),
                    kv_bytes_moved=(txn.kv_manifest.bytes_moved
                                    if txn.kv_manifest else 0),
                    kv_pages_deduped=(txn.kv_manifest.pages_deduped
                                      if txn.kv_manifest else 0))
        return {"pause_s": pause, "epoch": self.epoch}

    def rebalance_placement(self) -> dict:
        """Popularity-driven re-place: EPLB over the CURRENT active set
        against the tracked per-expert load EMA, committed through the
        standard transaction (epoch bump; byte-identical abort). No rank
        joins or leaves, so there is no detect window, no warmup and
        nothing to evict — the extra replica copies stream in the
        background (the non-critical ``rebalance`` span) and only the
        final table patch, reported as ``pause_s``, pauses serving."""
        incident = self.obs.incident("rebalance")
        txn = self.begin("rebalance", incident=incident)
        before = self.expert_replica_counts()
        try:
            with self.obs.span("rebalance", incident) as sp:
                plan = txn.plan()
                self._advance(self.cost_model.coordinate_s)
                if plan is not None:
                    xfer = self.cost_model.recovery_seconds(
                        plan, self.table.world,
                        self.table.slots_per_rank)["weight_transfer"]
                    if xfer > 0:
                        self._advance(xfer)
                    sp.meta.update(tier2_bytes=plan.tier2_bytes,
                                   tier3_bytes=plan.tier3_bytes,
                                   moved=len(plan.tier2) + len(plan.tier3))
                txn.commit()
                self._advance(self.cost_model.join_patch_s)
        except TransitionAborted as e:
            self.record("transition_abort", _incident=incident,
                        op="rebalance", ranks=[], **e.detail)
            e.recorded = True
            raise
        pause = self.cost_model.join_patch_s   # only the table patch pauses
        last = txn.plans[-1] if txn.plans else None
        self.record("rebalance", _incident=incident,
                    pause_s=round(pause, 6), epoch=self.epoch,
                    mix=last.source_mix() if last else {},
                    tier2_bytes=last.tier2_bytes if last else 0,
                    tier3_bytes=last.tier3_bytes if last else 0,
                    replicas_before={int(k): int(v)
                                     for k, v in before.items()},
                    replicas_after={int(k): int(v) for k, v in
                                    self.expert_replica_counts().items()},
                    imbalance=round(self.load_imbalance(), 4))
        return {"pause_s": pause, "epoch": self.epoch}

    def undrain_ranks(self, ranks: list[int]) -> dict:
        """Bring drained ranks back. A rank whose process is still up
        rejoins immediately via one batched table patch (it never went
        cold); one that died while drained rides the relaunch/warmup path
        like a scale-up."""
        warm = [r for r in ranks if self.detector.reachable[r]]
        cold = [r for r in ranks if not self.detector.reachable[r]]
        # warm patch first: if its transaction aborts, the exception leaves
        # the whole operation genuinely untouched (no cold relaunch has
        # been issued yet)
        if warm:
            self._rejoin_batch(warm, kind="undrain")
        if cold:
            self._relaunch_for_join(cold, kind="undrain_relaunch")
        return {"epoch": self.epoch, "warm": warm, "cold": cold}

    def scale_up_ranks(self, ranks: list[int]) -> dict:
        """Elastic regrow: the new ranks' processes launch and warm up in
        the background (deferred join); the eventual incorporation is the
        standard batched join patch."""
        self._relaunch_for_join(ranks, kind="scale_up")
        return {"epoch": self.epoch, "warming": list(ranks)}

    def _relaunch_for_join(self, ranks: list[int], *, kind: str) -> None:
        for r in ranks:
            incident = self.obs.incident_of(r, -1)
            self.record(kind, _incident=incident, rank=r)
            self.controller.schedule_relaunch(r)
            self.obs.open_span(("warmup", r), "warmup", incident=incident,
                               rank=r, planned=True)

    # ------------------------------------------------------------------
    # Straggler mitigation (beyond the paper's fail-stop timeout: de-weight
    # persistently slow-but-alive ranks via capacity-aware EPLB re-placement
    # — an in-place table patch, no membership change, no recompile)
    # ------------------------------------------------------------------
    def observe_step_latencies(self, base_step_s: float) -> None:
        lat = base_step_s * self.rank_slowdown
        self.straggler.observe(lat, self.table.active_mask)

    def mitigate_stragglers(self) -> list[int]:
        """Between forward passes: if the flagged set changed, re-place with
        capacity weights and patch the tables."""
        before = set(self.straggler.flagged)
        flagged = self.straggler.classify(self.table.active_mask)
        if flagged == before or not self.cfg.is_moe:
            return sorted(flagged)
        caps = self.straggler.capacity_weights(self.table.active_mask)
        txn = self.begin("straggler")
        txn.set_rank_capacity(caps)
        try:
            plan = txn.plan()
            txn.commit()
        except TransitionAborted as e:
            if "violations" in e.detail:
                # validity failure at commit = invariant regression: fail
                # loudly (the pre-transactional code asserted here)
                raise
            # a re-place that cannot cover every expert is simply skipped:
            # the staged state is discarded, the instance keeps serving on
            # the previous placement
            return sorted(flagged)
        self.record("straggler_mitigation", flagged=sorted(flagged),
                    capacities={int(r): round(float(caps[r]), 2)
                                for r in flagged},
                    epoch=self.epoch,
                    tier2_bytes=plan.tier2_bytes if plan else 0,
                    tier3_bytes=plan.tier3_bytes if plan else 0)
        return sorted(flagged)

    # ------------------------------------------------------------------
    def heartbeat(self) -> None:
        # drained ranks are alive (idling for maintenance) — they heartbeat
        # too, so the detector does not misread a planned drain as a fault
        self.detector.heartbeat(self.table.live_ranks())

    def _advance(self, dt: float) -> None:
        """Advance the SimClock across a synchronous control-plane pause
        (recovery phase, drain transfer, join patch) AND refresh live
        ranks' heartbeats: healthy workers keep heartbeating while the
        control plane holds them paused, so a pause longer than the
        suspicion grace window must never convert the whole world into
        suspects. Injector events that come due INSIDE the pause are
        applied first (their ranks go silent from the fire time, so the
        post-pause poll sees a real heartbeat age instead of a refresh
        that a dying rank could never have sent); the fired events are
        banked for the next ``_poll_transitions`` to record."""
        self.clock.advance(dt)
        self._fired_backlog.extend(self.injector.step())
        self.heartbeat()
