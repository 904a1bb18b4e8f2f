"""Failure-aware serving engine: continuous batching over ONE compiled step.

Prefill rides the decode step (chunk-size-1 prompt replay), so steady state,
degraded execution and the restored configuration all replay a single
compiled executable — the runtime asserts it never recompiles across
failure/reintegration (the paper's CUDA-graph-stability analogue).

The same chunk-1 replay path powers **continuation semantics**: when a
fault or planned drain evicts in-flight work under the elastic policy, the
scheduler snapshots each request's prompt + generated prefix (epoch-tagged)
and replays it here at resume, so clients observe a bounded stall — never
an error, never a duplicated token. ``FullRestartPolicy`` keeps the paper's
fail-and-retry-from-scratch baseline. Drivers should not poke this class
directly; ``repro.serving.api.ServingFrontend`` is the serving surface.

Timing: real compute runs on CPU; serving-time dynamics (step latency,
recovery pauses, warmup) come from the deterministic SimClock + cost models
in the elastic runtime, which is what lets the Fig. 1/10/11 traces be
reproduced on this container. ``fixed_membership=True`` switches to the
full-restart baseline (the only recovery path of a fixed-membership stack).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.failure import CoverageLossError
from repro.core.transitions import (
    ElasticPolicy,
    FullRestartCostModel,
    FullRestartPolicy,
    KVPageManifest,
    TransitionPolicy,
)
from repro.launch.steps import make_serve_step
from repro.models.model import init_caches
from repro.obs.trace import span as trace_span
from repro.runtime.elastic import ControlSummary, ElasticEPRuntime
from repro.serving.kv_cache import make_pool
from repro.serving.scheduler import Scheduler

__all__ = ["FullRestartCostModel", "ServingEngine", "ThroughputSample"]


@dataclass
class ThroughputSample:
    t: float
    tokens_per_s: float
    active_fraction: float


class ServingEngine:
    def __init__(self, runtime: ElasticEPRuntime, *, max_batch: int = 16,
                 max_len: int = 128, dtype=jnp.float32,
                 base_step_time: float = 0.05,
                 fixed_membership: bool = False,
                 restart_model: Optional[FullRestartCostModel] = None,
                 max_retries: Optional[int] = None,
                 policy: Optional[TransitionPolicy] = None,
                 kv_pool: Optional[str] = None,
                 queue_policy: str = "fifo"):
        self.rt = runtime
        cfg = runtime.cfg
        self.cfg = cfg
        # dispatch layout of the compiled step ("dense" | "ragged"): fixed at
        # engine construction — recovery/reintegration patch membership
        # contents only, so the mode survives the whole fail/rejoin lifetime
        self.dispatch = getattr(runtime.dpl.moe, "dispatch", "dense")
        # KV pool flavor ("slot" | "paged"): the paged pool pins pages at
        # preemption so planned drains MIGRATE KV instead of replaying it.
        # Cross-session prefix sharing rides the paged pool when the arch's
        # cache layout actually supports it (position-indexed, no ring
        # wrap, no recurrent state) — otherwise the toggle is inert.
        kind = kv_pool or getattr(cfg, "kv_pool", "paged")
        self.prefix_enabled = (kind == "paged"
                               and getattr(cfg, "prefix_cache", False)
                               and self.prefix_cache_supported(cfg, max_len))
        self.kv = make_pool(kind, max_batch, max_len,
                            block_size=getattr(cfg, "kv_block_size", 16),
                            prefix_cache=self.prefix_enabled)
        self.sched = Scheduler(self.kv, max_retries=max_retries,
                               queue_policy=queue_policy)
        self.caches = init_caches(cfg, max_batch, max_len, dtype)
        self.base_step_time = base_step_time
        self.restart_model = restart_model or FullRestartCostModel()
        # transition policy, selected at construction (no more monkeypatching
        # a failure handler onto the runtime): the full-restart baseline is a
        # TransitionPolicy like any other. One engine drives a runtime at a
        # time, so the most recently constructed engine's policy wins.
        if policy is None:
            policy = (FullRestartPolicy(self.restart_model)
                      if fixed_membership else ElasticPolicy())
        elif fixed_membership or restart_model is not None:
            # don't let a conflicting convenience flag be silently ignored
            raise ValueError(
                "pass either an explicit policy= or the fixed_membership/"
                "restart_model convenience args, not both")
        self.policy = policy
        self.fixed_membership = not policy.mutates_membership
        runtime.set_policy(policy)
        # the runtime asks the live engine for a KV-page manifest when a
        # planned drain opens its kv-migrate window (transfer sequenced
        # before the table patch); only a migration-capable pool under the
        # elastic policy has pages worth shipping
        runtime.kv_migration_source = (
            self._kv_manifest if self.kv.supports_migration
            and not self.fixed_membership else None)
        self.trace: list[ThroughputSample] = []
        # graceful degradation: set when a fault's recovery aborts on
        # coverage loss — the engine keeps stepping (serving what the
        # surviving experts can cover) but in-flight work was failed
        # terminally and the frontend refuses new admissions
        self.degraded = False
        self.degraded_reason = ""
        self._prompt_pos = np.zeros((max_batch,), np.int64)
        # unplanned faults: the recovery pause (detect..rejoin) is dead
        # time the speculative re-prefill can hide inside — replay-only
        # steps consume this budget instead of wall-clock
        self._overlap_budget = 0.0

        self._step = jax.jit(make_serve_step(cfg, runtime.dpl),
                             donate_argnums=(1,))

        def reset_slots(caches, mask):
            def fix(path, leaf):
                name = path[-1].key if hasattr(path[-1], "key") else ""
                m = mask[None, :]
                if name == "pos":
                    return jnp.where(m[..., None], -1, leaf)
                if name in ("c", "n", "h", "C", "conv", "latent", "k_rope",
                            "k", "v"):
                    shape = (1, mask.shape[0]) + (1,) * (leaf.ndim - 2)
                    return jnp.where(mask.reshape((1, -1) + (1,) * (leaf.ndim - 2)),
                                     jnp.zeros_like(leaf), leaf)
                if name == "m":
                    return jnp.where(mask.reshape((1, -1) + (1,) * (leaf.ndim - 2)),
                                     jnp.full_like(leaf, -1e30), leaf)
                return leaf
            return jax.tree_util.tree_map_with_path(fix, caches)

        self._reset_slots = jax.jit(reset_slots, donate_argnums=(0,))

        # paged-pool page relocation: the pool's pending (src, dst) moves
        # fold into one slot-permutation gather over the donated cache
        # buffers — the compiled-step analogue of patching an indirection
        # table. Separate jitted helper, same donated-buffer discipline as
        # _reset_slots; compile_count() tracks the serve step only.
        def gather_slots(caches, src):
            return jax.tree_util.tree_map(
                lambda leaf: jnp.take(leaf, src, axis=1), caches)

        self._gather_slots = jax.jit(gather_slots, donate_argnums=(0,))
        self._last_input = np.zeros((max_batch, 1), np.int32)

    # ------------------------------------------------------------------
    @staticmethod
    def prefix_cache_supported(cfg: ArchConfig, max_len: int) -> bool:
        """Whether the arch's cache layout admits cross-session prefix
        sharing. A donor row is reusable only when every cache leaf is
        position-indexed and never rewritten below the current length:
        recurrent state (mamba/xlstm mixers) folds the whole context into
        one vector a prefix cannot be cut out of; encoder cross-attention
        and modality frontends key on per-request inputs outside the
        prompt tokens; and a sliding-window ring buffer wraps once the
        context exceeds the window, overwriting cached prefix positions
        in place."""
        if cfg.family not in ("dense", "moe"):
            return False
        if cfg.attention == "none":
            return False
        if cfg.encoder is not None or getattr(cfg, "frontend", None):
            return False
        if cfg.attention == "swa" and 0 < cfg.window < max_len:
            return False
        return True

    # ------------------------------------------------------------------
    def compile_count(self) -> int:
        """Number of serve-step compilations so far (must be 1 for the whole
        fail/recover/rejoin lifetime — asserted by tests)."""
        return self._step._cache_size()

    # ------------------------------------------------------------------
    def _kv_token_bytes(self) -> int:
        """Modeled bytes of KV state one resident token occupies across the
        attention layers (fp32 sim arrays, K + V per kv head)."""
        cfg = self.cfg
        n_attn = max(1, len(cfg.attn_layer_ids()))
        return n_attn * 2 * cfg.num_kv_heads * cfg.head_dim * 4

    def _kv_manifest(self, ranks) -> KVPageManifest:
        """KV-page manifest for a planned drain of ``ranks``: the share of
        live pages resident on the departing ranks, which must ship to the
        survivors over the Tier-2 transfer path BEFORE the table patch
        publishes the shrunk membership. Called by the runtime inside the
        drain window (its kv-migrate phase)."""
        pool = self.kv
        # PHYSICAL pages: a prefix-shared page referenced by many block
        # tables ships exactly once. The logical count (per-table
        # references) rides along so the dedup win is observable.
        pages_total = pool.inflight_pages()
        pages_logical = getattr(pool, "inflight_pages_logical",
                                pool.inflight_pages)()
        mask = np.asarray(self.rt.table.active_mask, bool)
        # pre-drain active count, whether or not the transaction already
        # deactivated the departing ranks on the live table
        pre = int(mask.sum()) + sum(1 for r in ranks if not mask[r])
        share = min(1.0, len(ranks) / max(1, pre))
        pages_moved = int(np.ceil(pages_total * share))
        page_bytes = getattr(pool, "block_size", 0) * self._kv_token_bytes()
        return KVPageManifest(
            pages_total=pages_total,
            pages_moved=pages_moved,
            bytes_moved=pages_moved * page_bytes,
            requests=len(pool.active_slots()) + pool.stats()["pinned"],
            page_bytes=page_bytes,
            pages_logical=pages_logical,
            pages_deduped=pages_logical - pages_total)

    # ------------------------------------------------------------------
    def _build_inputs(self):
        tokens = np.zeros((self.kv.num_slots, 1), np.int32)
        # The compiled step writes k/v at ring position ``length % W`` for
        # EVERY batch row, occupied or not. Idle rows (free slots, parked
        # cache-resident donors, pinned snapshots) feed length -1 so that
        # stray write lands on the LAST ring position with cpos=-1: always
        # masked, re-written by a real occupant before it could ever be
        # attended, and never inside a shareable prefix block (a full
        # final block needs a max_len-token prompt, which never fits).
        # Length 0 instead would clobber position 0 of a parked donor row
        # with garbage every step — and borrowers copy that row.
        lengths = np.full(self.kv.num_slots, -1, np.int32)
        for slot in self.kv.active_slots():
            req = self.sched.running[self.kv.owner_of(slot)]
            pos = self._prompt_pos[slot]
            if pos < req.replay_len:
                # chunk-1 prefill replay: the prompt — and, on a
                # continuation resume, the preserved generated prefix.
                # A migrated request re-enters here too, but with
                # _prompt_pos already at its restored resident length,
                # so nothing actually replays.
                tokens[slot, 0] = req.replay_token(pos)
            else:
                tokens[slot, 0] = req.generated[-1] if req.generated else 0
            lengths[slot] = self.kv.length_of(slot)
        return tokens, lengths

    def step(self) -> int:
        """One engine iteration. Returns tokens produced. Each stage runs
        under its own profiler span (``repro.engine.<stage>``,
        ``repro.obs.trace``), so a device trace puts the host's share of
        the step down to the stage that took it."""
        rt = self.rt
        with trace_span("engine.control"):
            self._control()
        with trace_span("engine.admit"):
            self._admit()
        active = self.kv.active_slots()
        if not active:
            rt.clock.advance(self.base_step_time)
            rt.heartbeat()
            return 0
        with trace_span("engine.inputs"):
            tokens, lengths = self._build_inputs()
            next_tok, _, self.caches = self._step(
                rt.params, self.caches, rt.membership,
                jnp.asarray(tokens), jnp.asarray(lengths))
        with trace_span("engine.sync"):
            next_tok = np.asarray(next_tok)
        with trace_span("engine.bookkeep"):
            return self._bookkeep(active, next_tok)

    def _control(self) -> None:
        """Pump the control plane: faults, drains, joins, rebalances, and
        straggler mitigation, each with its effect on in-flight work."""
        rt = self.rt
        rt.obs.tick()      # telemetry: events/spans carry the step index
        # --- fault handling (between forward passes, paper §3.1): one pump
        # drains every pending control transition — possibly several
        # overlapping failures and a batch of joins — in event order. ---
        t_pre = rt.clock.now()
        try:
            ctl = rt.pump_control()
        except CoverageLossError as e:
            # graceful degradation instead of a crashed serving loop: the
            # survivors cannot cover every expert, so the work that needed
            # the lost ones can never finish. Fail in-flight AND queued
            # requests terminally (final=true, no retry budget burned),
            # flip the degraded flag (the frontend rejects new submits),
            # and keep stepping for observability/admin traffic.
            if not self.degraded:
                self.degraded = True
                self.degraded_reason = str(e)
                self.sched.fail_inflight(now=rt.clock.now(),
                                         cause="coverage_loss",
                                         force_final=True)
                self._prompt_pos[:] = 0
                self.trace.append(ThroughputSample(rt.clock.now(), 0.0,
                                                   rt.active_fraction()))
            ctl = ControlSummary()
        now = rt.clock.now()
        if ctl.failures_handled or ctl.restarts:
            # one eviction per interruption batch (overlapping failures
            # were composed into a single recovery by the runtime). The
            # elastic path SUSPENDS in-flight work with its generated
            # prefix intact — an epoch-tagged continuation snapshot that
            # replays through the chunk-1 prefill path, so clients observe
            # a bounded stall instead of an error. The fixed-membership
            # baseline (a full restart — including one answering a planned
            # drain) keeps the paper's fail-and-retry-from-scratch.
            if ctl.restarts or self.fixed_membership:
                self.sched.fail_inflight(
                    now=now, cause="restart" if ctl.restarts else "fault")
            else:
                self.sched.suspend_inflight(now=now, cause="fault",
                                            epoch=rt.epoch)
                # speculative re-prefill: the recovery pause the pump just
                # charged (detect..rejoin) is the window replay-only steps
                # may hide inside — with paged KV the replay was already
                # overlapped with the repair transfer, so it costs no
                # extra wall-clock until the budget runs out
                if self.kv.supports_migration:
                    self._overlap_budget = max(0.0, now - t_pre)
            self._prompt_pos[:] = 0
            self.trace.append(ThroughputSample(now, 0.0,
                                               rt.active_fraction()))
        if ctl.drained or ctl.scaled_down:
            # planned shrink: in-flight work on the departing ranks is
            # PREEMPTED, not failed — requeued at the front with progress
            # kept and no retry budget consumed (the clients never see an
            # error). Over a migration-capable pool the KV pages are
            # PINNED, not released: they shipped to the survivors inside
            # the drain window (the runtime's kv-migrate phase, sequenced
            # before the table patch), so re-admission replays nothing.
            if self.kv.supports_migration and not self.fixed_membership:
                self.sched.migrate_inflight(
                    now=now, cause="drain" if ctl.drained else "scale_down",
                    epoch=rt.epoch)
            else:
                self.sched.preempt_inflight(
                    now=now, cause="drain" if ctl.drained else "scale_down",
                    epoch=rt.epoch)
            self._prompt_pos[:] = 0
            self.trace.append(ThroughputSample(now, 0.0,
                                               rt.active_fraction()))
        if ctl.joined or ctl.undrained:
            self.trace.append(ThroughputSample(rt.clock.now(), 0.0,
                                               rt.active_fraction()))
        if ctl.rebalanced:
            # popularity rebalance: no rank left, so nothing is evicted or
            # preempted — the only serving-visible cost is the table-patch
            # pause the runtime already charged. Drop a trace sample so the
            # throughput trajectory shows the flip point.
            self.trace.append(ThroughputSample(rt.clock.now(), 0.0,
                                               rt.active_fraction()))
        if not self.fixed_membership:
            rt.observe_step_latencies(self.base_step_time)
            rt.mitigate_stragglers()

    def _admit(self) -> None:
        """Admit queued work into free slots (resumes validate their
        continuation snapshot against the device-published membership
        epoch), reset the fresh slots' cache rows, and apply pending page
        moves."""
        rt = self.rt
        admitted = self.sched.admit(now=rt.clock.now(),
                                    epoch=int(np.asarray(rt.membership.version)))
        if admitted:
            mask = np.zeros((self.kv.num_slots,), bool)
            fresh = False
            for req in admitted:
                if req.kv_intact:
                    # pages moved intact (MIGRATED): the slot's cache rows
                    # are live state — do NOT reset them; resume feeding
                    # from the restored resident length, replaying nothing
                    req.kv_intact = False
                    self._prompt_pos[req.slot] = self.kv.length_of(req.slot)
                else:
                    mask[req.slot] = True
                    fresh = True
                    # the pool allocated the slot with the whole context
                    # resident; replay starts at the prefix skip (0 with
                    # no hit), so rewind the resident length to match, or
                    # the first replayed token would land past the end of
                    # the context. On a prefix hit positions [0, skip)
                    # arrive via the queued donor-row gather below
                    # (applied AFTER the reset, so the copy lands clean).
                    skip = req.prefix_skip
                    self.kv.set_length(req.slot, skip)
                    self._prompt_pos[req.slot] = skip
            if fresh:
                self.caches = self._reset_slots(self.caches,
                                                jnp.asarray(mask))

        # pending page relocations (PagedKVPool.migrate) fold into ONE
        # slot-permutation gather over the donated cache buffers, applied
        # before the step reads them
        moves = self.kv.take_moves()
        if moves:
            src = np.arange(self.kv.num_slots)
            for a, b in moves:
                src[b] = a
            self.caches = self._gather_slots(self.caches,
                                             jnp.asarray(src, jnp.int32))

    def _bookkeep(self, active, next_tok) -> int:
        """Advance each slot's replay or deliver its token, then the load
        EMA, the modelled step latency and the throughput sample."""
        rt = self.rt
        # prefill replay vs real decode: ``replay_len`` covers the prompt
        # plus, on a continuation resume, the preserved generated prefix:
        # replayed positions rebuild KV state without re-emitting tokens,
        # so the client stream stays exactly-once. The throughput trace
        # still counts re-decoded prefix positions — that is real
        # decode-rate work (the retry baseline regenerates and counts the
        # same tokens), only the client-facing delivery is deduplicated.
        produced = {}
        redecoded = 0
        resume_replaying = False
        for slot in active:
            req = self.sched.running.get(self.kv.owner_of(slot))
            if req is None:
                continue
            pos = self._prompt_pos[slot]
            if pos + 1 < req.replay_len:
                # still consuming the replay sequence
                self._prompt_pos[slot] += 1
                self.kv.set_length(slot, int(pos + 1))
                if req.generated:
                    resume_replaying = True  # a true resume, not a fresh prefill
                if pos >= len(req.prompt):
                    redecoded += 1       # generated-prefix replay (resume)
            else:
                if pos + 1 == req.replay_len:
                    self._prompt_pos[slot] += 1
                    # prefill just completed: every prompt position is
                    # resident in this slot's pages and will never be
                    # rewritten (decode appends past them) — register the
                    # full blocks for cross-session reuse NOW, so
                    # concurrent sessions sharing the prefix hit while
                    # this one still decodes
                    self.kv.cache_prompt(slot, req.prompt)
                produced[slot] = int(next_tok[slot, 0]) % self.cfg.vocab_size
        now = rt.clock.now()
        self.sched.step_complete(produced, now)

        # --- popularity tracking: fold this step's routing mass into the
        #     runtime's per-expert EMA (the planners' input). The simulated
        #     router follows rt.router_skew (uniform unless a scenario
        #     injected one), scaled by the live token count so heavier
        #     steps weigh more — a popularity-blind runtime discards it. ---
        dist = rt.router_distribution()
        if dist is not None and active:
            rt.update_expert_load(dist * len(active))

        # --- modeled step latency: wide-EP step time scales with the
        #     reciprocal of the live-rank fraction (reduced capacity) AND
        #     with the placement's load imbalance — MoE decode is gated by
        #     the most-loaded rank, so a hot expert squeezed onto too few
        #     replicas costs real tokens even when coverage is nominal
        #     (imbalance is exactly 1.0 under uniform traffic on a
        #     balanced placement, leaving skew-free scenarios untouched).
        #     Replay-only steps right after an unplanned fault draw down
        #     the overlap budget instead of wall-clock: the speculative
        #     re-prefill ran inside the recovery pause (repair-transfer
        #     window), so the stall the client sees stops growing. ---
        step_t = (self.base_step_time * rt.load_imbalance()
                  / max(rt.active_fraction(), 1e-6))
        charged = step_t
        if not produced and resume_replaying and self._overlap_budget > 0:
            hidden = min(charged, self._overlap_budget)
            self._overlap_budget -= hidden
            charged -= hidden
        rt.clock.advance(charged)
        rt.heartbeat()
        self.trace.append(ThroughputSample(
            rt.clock.now(), (len(produced) + redecoded) / step_t,
            rt.active_fraction()))
        return len(produced)

    # ------------------------------------------------------------------
    def run(self, *, until: Optional[float] = None,
            max_steps: int = 10_000,
            before_step: Optional[callable] = None,
            idle_stop: Optional[callable] = None) -> None:
        """Step until ``until`` (sim seconds) or the work dries up.
        ``before_step`` runs ahead of each step — the hook drivers use to
        fire time-scheduled planned transitions (ControlPlane requests)
        without re-implementing this loop. ``idle_stop`` replaces the
        default drained-out check: the engine alone cannot see transitions
        a driver has scheduled for a FUTURE time, so the frontend supplies
        its "no live sessions and no pending admin ops" predicate here —
        otherwise an idle engine would exit before a scheduled drain ever
        fires."""
        steps = 0
        if idle_stop is None:
            idle_stop = (lambda: self.sched.inflight == 0
                         and not self.sched.queue
                         and not self.rt.control_queue
                         and not self.rt.controller.recovering)
        while steps < max_steps:
            if until is not None and self.rt.clock.now() >= until:
                break
            if before_step is not None:
                before_step()
            if until is None and idle_stop():
                break
            self.step()
            steps += 1
