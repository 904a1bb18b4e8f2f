"""Client-session serving API: the gateway drivers talk to.

The paper's headline claim is user-visible — a rank fault becomes "two
bounded interruptions" instead of downtime — so the repro needs a
user-visible surface. This module is it, split the same way the system
underneath is:

  * **data plane** — :class:`ServingFrontend`. ``submit(prompt, ...)``
    returns a :class:`StreamHandle` yielding an ordered per-request event
    stream (vocabulary in ``repro.serving.events`` / docs/serving-api.md),
    with client-side ``cancel()``, per-request deadlines and admission
    control against queue depth. Under the elastic policy an interruption
    surfaces as a bounded ``STALL_BEGIN``/``PREEMPTED`` .. ``RESUMED`` ..
    ``STALL_END`` window — never an error event, never a duplicated or
    reordered token (the continuation snapshot replays through the
    engine's chunk-1 prefill path). The fixed-membership baseline keeps
    the paper's fail-and-retry: clients see explicit ``FAILED`` events and
    recomputed duplicates are suppressed so streams stay exactly-once.

  * **control plane** — :class:`AdminGateway`, a serializable JSON
    command/response protocol over the runtime's
    :class:`~repro.core.transitions.ControlPlane` (drain / undrain /
    scale_down / scale_up, plus status / epoch / incidents queries), so
    CLI drivers, the scenario runner and future RPC servers share one
    entry point. Commands may carry ``"at"`` (sim seconds) to schedule a
    transition; the frontend fires it when the clock crosses and —
    unlike the bare engine loop — never exits while one is pending.

Drivers (``launch/serve.py``, the scenario runner, ``examples/``) go
through this module exclusively; poking ``Scheduler`` or ``engine.run``
directly is a layering violation.
"""
from __future__ import annotations

import json
from dataclasses import asdict
from typing import Iterator, Optional

import numpy as np

from repro.obs.trace import span as trace_span
from repro.serving.events import (
    ERROR_KINDS,
    EVENT_KINDS,
    StreamEvent,
    validate_stream,
)
from repro.serving.request import Request

__all__ = ["AdminGateway", "ServingFrontend", "StreamHandle"]


def _jsonable(x):
    """Plain-JSON coercion (numpy scalars/arrays included) so every admin
    response round-trips through ``json.dumps``/``loads`` unchanged."""
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, set):
        return [_jsonable(v) for v in sorted(x)]
    if isinstance(x, (bool, np.bool_)):
        return bool(x)
    if isinstance(x, (int, np.integer)):
        return int(x)
    if isinstance(x, (float, np.floating)):
        return float(x)
    if isinstance(x, np.ndarray):
        return x.tolist()
    return x


def _percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile; -1.0 for an empty sample (the same "no
    measurement" sentinel ``restore_95_s`` uses)."""
    if not values:
        return -1.0
    ordered = sorted(values)
    rank = min(len(ordered) - 1, max(0, int(round(q * (len(ordered) - 1)))))
    return float(ordered[rank])


# ---------------------------------------------------------------------------
# Data plane
# ---------------------------------------------------------------------------

class StreamHandle:
    """The client's view of one request: an ordered event stream.

    Events accumulate as the frontend steps the engine; iterating the
    handle yields them in order, driving the engine as needed until the
    stream terminates. ``tokens`` is the exactly-once output so far.
    """

    def __init__(self, frontend: "ServingFrontend", rid: int,
                 prompt: list[int], max_new: int,
                 deadline: Optional[float], t_submit: float,
                 tenant: str = "default"):
        self._fe = frontend
        self.rid = rid
        self.prompt = prompt
        self.max_new = max_new
        self.deadline = deadline      # ABSOLUTE sim time (submit + offset)
        self.t_submit = t_submit
        self.tenant = tenant
        self.events: list[StreamEvent] = []
        self.delivered = 0          # token indices emitted so far (== next)
        self.suppressed = 0         # recomputed duplicates never re-delivered
        self.stalls = 0             # interruption windows observed
        self._stall_open = False
        self._stall_t0 = 0.0

    # -- stream state -------------------------------------------------------
    @property
    def done(self) -> bool:
        return bool(self.events) and self.events[-1].terminal

    @property
    def outcome(self) -> Optional[str]:
        """Terminal event kind, or ``None`` while the stream is live."""
        return self.events[-1].kind if self.done else None

    @property
    def tokens(self) -> list[int]:
        return [e.token for e in self.events if e.kind == "TOKEN"]

    @property
    def error_events(self) -> list[StreamEvent]:
        return [e for e in self.events if e.is_error]

    def cancel(self, cause: str = "client") -> bool:
        """Client-side cancellation: terminal from any live state."""
        return self._fe.cancel(self.rid, cause=cause)

    def __iter__(self) -> Iterator[StreamEvent]:
        """Yield events in order, stepping the frontend until the stream
        terminates (or the step budget runs out — a safety valve, not an
        API: callers wanting bounded time pass ``deadline=``)."""
        i = 0
        budget = 100_000
        while True:
            while i < len(self.events):
                yield self.events[i]
                i += 1
            if self.done or budget <= 0:
                return
            self._fe.step()
            budget -= 1

    # -- internal -----------------------------------------------------------
    def _emit(self, kind: str, t: float, index: int = -1, token: int = -1,
              **detail) -> None:
        assert kind in EVENT_KINDS, kind
        if self.done:        # contract: nothing follows a terminal event
            return
        self.events.append(StreamEvent(kind=kind, t=float(t),
                                       seq=len(self.events), index=index,
                                       token=token, detail=detail))

    def _open_stall(self, t: float) -> None:
        self._stall_open = True
        self._stall_t0 = float(t)
        self.stalls += 1

    def _close_stall(self, t: float) -> None:
        if self._stall_open:
            self._emit("STALL_END", t, stall_s=round(t - self._stall_t0, 6))
            self._stall_open = False


class ServingFrontend:
    """The serving gateway: owns a :class:`ServingEngine`, translates
    scheduler transitions into per-request event streams, and exposes the
    admin control plane. One frontend drives one engine."""

    def __init__(self, engine, *, max_queue_depth: Optional[int] = None,
                 tenant_quotas: Optional[dict] = None):
        self.engine = engine
        self.rt = engine.rt
        self.max_queue_depth = max_queue_depth
        # per-tenant cap on LIVE streams (queued + in-flight + stalled);
        # None / missing tenant = uncapped. The noisy-neighbor guard: one
        # tenant's storm cannot starve the rest of the queue-depth budget.
        self.tenant_quotas = dict(tenant_quotas or {})
        self.streams: dict[int, StreamHandle] = {}
        self.rejected_admission = 0     # refused on queue depth / tenant
                                        # quota (frontend-level; overflow
                                        # counts in scheduler)
        self._next_rid = 0
        self._scheduled: list[dict] = []   # admin ops awaiting their time
        self._deadline_watch: list[StreamHandle] = []   # live handles that
                                                        # carry a deadline
        self.admin = AdminGateway(self)
        engine.sched.sink = self._sink

    # -- data plane ---------------------------------------------------------
    def submit(self, prompt, *, max_new: int = 16,
               deadline: Optional[float] = None,
               tenant: str = "default") -> StreamHandle:
        """Enter one request. ``deadline`` is sim-seconds FROM SUBMIT; a
        stream that has not terminated by then is cancelled. Always
        returns a handle; a request refused by admission control (queue
        depth or tenant quota) or the KV overflow guard carries a terminal
        ``REJECTED`` event instead of raising."""
        now = self.rt.clock.now()
        rid = self._next_rid
        self._next_rid += 1
        expires = None if deadline is None else now + deadline
        quota = self.tenant_quotas.get(tenant)
        tenant_live = (sum(1 for h in self.streams.values()
                           if h.tenant == tenant and not h.done)
                       if quota is not None else 0)
        handle = StreamHandle(self, rid, list(prompt), max_new, expires, now,
                              tenant)
        self.streams[rid] = handle
        if expires is not None:
            self._deadline_watch.append(handle)
        sched = self.engine.sched
        if self.engine.degraded:
            # graceful degradation after coverage loss: the frontend stays
            # up and answers, but refuses work it could never finish —
            # a structured terminal REJECTED, not a hang or a crash
            self.rejected_admission += 1
            handle._emit("REJECTED", now, reason="coverage_loss",
                         degraded=self.engine.degraded_reason)
            return handle
        if quota is not None and tenant_live >= quota:
            self.rejected_admission += 1
            handle._emit("REJECTED", now, reason="tenant_quota",
                         tenant=tenant, live=tenant_live, quota=quota)
            return handle
        if (self.max_queue_depth is not None
                and self._effective_depth() >= self.max_queue_depth):
            self.rejected_admission += 1
            handle._emit("REJECTED", now, reason="queue_full",
                         queue_depth=self._effective_depth(),
                         max_queue_depth=self.max_queue_depth)
            return handle
        sched.submit(Request(rid=rid, prompt=list(prompt),
                             max_new_tokens=max_new, t_submit=now,
                             deadline=expires, tenant=tenant))
        return handle

    def _effective_depth(self) -> int:
        """Queue depth as admission control must see it: queued requests
        PLUS in-flight work that is about to requeue. A fault or drain
        sitting in the control queue (requested but not yet committed at a
        step boundary) will push every in-flight request back onto the
        queue front — admitting a burst up to ``max_queue_depth`` inside
        that window would overshoot the cap the moment the transition
        commits, which is exactly when the system can least afford the
        extra load."""
        sched = self.engine.sched
        depth = len(sched.queue)
        interrupt_pending = any(
            ev.kind in ("failure_detected", "drain", "scale_down")
            for ev in self.rt.control_queue)
        if interrupt_pending:
            depth += sched.inflight
        return depth

    def cancel(self, rid: int, *, cause: str = "client") -> bool:
        return self.engine.sched.cancel(rid, now=self.rt.clock.now(),
                                        cause=cause)

    def step(self) -> int:
        """One engine iteration through the gateway: fire scheduled admin
        transitions whose time has come, expire deadlines, then step
        (profiler spans ``repro.frontend.step`` around the whole and
        ``repro.frontend.admin`` around the first two)."""
        with trace_span("frontend.step"):
            with trace_span("frontend.admin"):
                self._pump_admin()
            return self.engine.step()

    def run(self, *, until: Optional[float] = None,
            max_steps: int = 10_000) -> None:
        """Drive the engine until ``until`` (sim seconds) or until no live
        session remains AND no admin operation is pending — the engine's
        bare idle check cannot see future-scheduled transitions, so
        termination routes through this predicate."""
        self.engine.run(until=until, max_steps=max_steps,
                        before_step=self._pump_admin,
                        idle_stop=self._idle_stop)

    @property
    def live_streams(self) -> list[StreamHandle]:
        return [h for h in self.streams.values() if not h.done]

    def _idle_stop(self) -> bool:
        sched = self.engine.sched
        return (sched.inflight == 0 and not sched.queue
                and not self._scheduled
                and not self.rt.control_queue
                and not self.rt.controller.recovering)

    def _pump_admin(self) -> None:
        now = self.rt.clock.now()
        while self._scheduled and self._scheduled[0]["at"] <= now:
            op = self._scheduled.pop(0)
            self.rt.control.request(op["cmd"], op["ranks"])
        if self._deadline_watch:
            for handle in self._deadline_watch:
                if not handle.done and now > handle.deadline:
                    self.cancel(handle.rid, cause="deadline")
            self._deadline_watch = [h for h in self._deadline_watch
                                    if not h.done]

    # -- scheduler sink: state changes -> client-visible events -------------
    def _sink(self, kind: str, req: Request, t: float = 0.0, **detail):
        handle = self.streams.get(req.rid)
        if handle is None:      # not submitted through this frontend
            return
        if kind == "token":
            index = detail["index"]
            if index < handle.delivered:
                # baseline retry recomputing an already-delivered prefix:
                # suppressed so the stream stays exactly-once
                handle.suppressed += 1
                return
            handle._close_stall(t)
            handle._emit("TOKEN", t, index=index, token=detail["token"])
            handle.delivered = index + 1
        elif kind == "finished":
            handle._emit("FINISHED", t, tokens=detail["tokens"],
                         ttft_s=round(req.t_first_token - req.t_submit, 6))
        elif kind == "failed":
            final = detail["final"]
            handle._emit("FAILED", t, cause=detail["cause"], final=final,
                         retry=detail["retry"])
            if not final and not handle._stall_open:
                handle._open_stall(t)
        elif kind in ("suspended", "preempted"):
            # a second interruption landing inside a still-open window
            # extends the stall rather than nesting a new one
            if not handle._stall_open:
                handle._open_stall(t)
                handle._emit(
                    "STALL_BEGIN" if kind == "suspended" else "PREEMPTED",
                    t, cause=detail["cause"], epoch=detail["epoch"],
                    progress=detail["progress"])
        elif kind == "resumed":
            handle._emit("RESUMED", t, epoch=detail["epoch"],
                         snapshot_epoch=detail["snapshot_epoch"],
                         recomputed=detail["recomputed"])
        elif kind == "migrated":
            # KV pages moved intact (paged pool, planned drain): nothing
            # replays, so the stall is over the moment the pages land —
            # the window its PREEMPTED opened closes here, and a later
            # fault opens a fresh one (MIGRATED and RESUMED never share
            # a window; validate_stream enforces it)
            handle._emit("MIGRATED", t, epoch=detail["epoch"],
                         snapshot_epoch=detail["snapshot_epoch"],
                         pages=detail["pages"], tokens=detail["tokens"])
            handle._close_stall(t)
        elif kind == "cancelled":
            handle._emit("CANCELLED", t, cause=detail["cause"],
                         tokens=detail["tokens"])
        elif kind == "rejected":
            handle._emit("REJECTED", t, reason=detail["reason"],
                         context_len=detail["context_len"],
                         max_new=detail["max_new"],
                         max_len=detail["max_len"])

    # -- client-perceived metrics ------------------------------------------
    def metrics(self) -> dict:
        """Client-perceived serving metrics over every stream this frontend
        has opened: TTFT, inter-token stall percentiles (measured between
        TOKEN timestamps, so recovery pauses are included exactly as a
        client would feel them), goodput, and the continuation cost
        (tokens recomputed on resume)."""
        ttfts: list[float] = []
        gaps: list[float] = []
        delivered = 0
        event_counts: dict[str, int] = {}
        stall_events = 0
        error_events = 0
        t_first_submit = None
        tenants: dict[str, dict] = {}
        for handle in self.streams.values():
            ts = [e.t for e in handle.events if e.kind == "TOKEN"]
            delivered += len(ts)
            if ts:
                ttfts.append(ts[0] - handle.t_submit)
            gaps += [b - a for a, b in zip(ts, ts[1:])]
            bucket = tenants.setdefault(handle.tenant, {
                "submitted": 0, "admitted": 0, "rejected": 0,
                "finished": 0, "cancelled": 0, "delivered_tokens": 0})
            bucket["submitted"] += 1
            # a rejection is immediate at submit, so admitted is exactly
            # the complement; finished/cancelled refine the admitted set
            bucket["rejected" if handle.outcome == "REJECTED"
                   else "admitted"] += 1
            bucket["finished"] += handle.outcome == "FINISHED"
            bucket["cancelled"] += handle.outcome == "CANCELLED"
            bucket["delivered_tokens"] += len(ts)
            # windows actually opened (STALL_BEGIN, PREEMPTED, or the
            # baseline's non-final FAILED — all three stall the client)
            stall_events += handle.stalls
            for e in handle.events:
                event_counts[e.kind] = event_counts.get(e.kind, 0) + 1
                error_events += e.kind in ERROR_KINDS
            if t_first_submit is None or handle.t_submit < t_first_submit:
                t_first_submit = handle.t_submit
        elapsed = (self.rt.clock.now() - t_first_submit
                   if t_first_submit is not None else 0.0)
        stats = self.engine.sched.stats
        return {
            "requests": len(self.streams),
            "delivered_tokens": delivered,
            "ttft_p50_s": round(_percentile(ttfts, 0.50), 6),
            "ttft_p99_s": round(_percentile(ttfts, 0.99), 6),
            "stall_p50_s": round(_percentile(gaps, 0.50), 6),
            "stall_p99_s": round(_percentile(gaps, 0.99), 6),
            "stall_max_s": round(max(gaps), 6) if gaps else -1.0,
            "goodput_tok_s": round(delivered / elapsed, 3)
                             if elapsed > 0 else 0.0,
            "tokens_recomputed": stats.tokens_recomputed
                                 + sum(h.suppressed
                                       for h in self.streams.values()),
            "tokens_migrated": stats.tokens_migrated,
            "migrations": stats.migrated,
            # prefix-cache economics: admissions that borrowed cached
            # pages, and the prompt positions prefill never replayed
            "prefix_hits": stats.prefix_hits,
            "prefix_hit_rate": round(
                stats.prefix_hits / stats.admitted, 6)
                if stats.admitted else 0.0,
            "tokens_prefill_skipped": stats.tokens_prefill_skipped,
            "stall_events": stall_events,
            "error_events": error_events,
            "rejected_admission": self.rejected_admission,
            "events": dict(sorted(event_counts.items())),
            "tenants": {k: tenants[k] for k in sorted(tenants)},
        }

    def stream_violations(self) -> list[str]:
        """Every exactly-once/ordering-contract violation across all
        streams (empty = the API contract held)."""
        return [f"rid {rid}: {v}"
                for rid, handle in sorted(self.streams.items())
                for v in validate_stream(handle.events)]


# ---------------------------------------------------------------------------
# Control plane
# ---------------------------------------------------------------------------

class AdminGateway:
    """Serializable JSON command/response protocol over the runtime's
    ControlPlane, so CLI drivers, the scenario runner and future RPC
    servers share one entry point.

    Command schema (dict or JSON string)::

        {"cmd": "drain",      "ranks": [2], "at": 10.0}   # "at" optional
        {"cmd": "undrain",    "ranks": [2]}
        {"cmd": "scale_down", "ranks": [6, 7]}
        {"cmd": "scale_up",   "ranks": [6, 7]}
        {"cmd": "status"} | {"cmd": "epoch"} | {"cmd": "incidents", "last": 20}

    Responses are plain-JSON dicts: ``{"ok": true, "cmd": ..., "result":
    ..., "epoch": ...}`` or ``{"ok": false, "cmd": ..., "error": ...}``.
    Transition commands without ``"at"`` are requested immediately and
    commit at the next step boundary (where the engine applies the
    preemption requeue semantics); with ``"at"`` they are scheduled and
    fired by the frontend when the SimClock crosses — the frontend's run
    loop never exits while one is pending.
    """

    #: Planned membership transitions routed to the ControlPlane.
    #: ``rebalance`` is rank-less (it targets the whole active set).
    TRANSITIONS = ("drain", "undrain", "scale_down", "scale_up", "rebalance")
    #: Read-only queries answered from live runtime state.
    QUERIES = ("status", "epoch", "incidents")
    COMMANDS = TRANSITIONS + QUERIES

    def __init__(self, frontend: ServingFrontend):
        self.fe = frontend

    # -- protocol entry points ----------------------------------------------
    def execute(self, command) -> dict:
        """Run one command (dict or JSON string), returning a plain-JSON
        response dict. Never raises on a malformed command — the error
        comes back in the response, like any RPC server."""
        cmd = "?"
        try:
            if isinstance(command, (str, bytes)):
                command = json.loads(command)
            if not isinstance(command, dict):
                raise ValueError("command must be a JSON object")
            cmd = command.get("cmd", "?")
            if cmd not in self.COMMANDS:
                raise ValueError(f"unknown cmd {cmd!r}; "
                                 f"have {sorted(self.COMMANDS)}")
            if cmd in self.TRANSITIONS:
                result = self._transition(cmd, command)
            elif cmd == "status":
                result = self._status()
            elif cmd == "epoch":
                result = self._epoch()
            else:
                result = self._incidents(command)
            return _jsonable({"ok": True, "cmd": cmd, "result": result,
                              "epoch": self.fe.rt.epoch})
        except (ValueError, KeyError, TypeError, json.JSONDecodeError) as e:
            return _jsonable({"ok": False, "cmd": cmd, "error": str(e)})

    def execute_json(self, command: str) -> str:
        """String-in/string-out variant (what an RPC server would speak)."""
        return json.dumps(self.execute(command), sort_keys=True)

    # -- commands -----------------------------------------------------------
    def _transition(self, cmd: str, command: dict) -> dict:
        rt = self.fe.rt
        ranks = command.get("ranks")
        if cmd == "rebalance":
            # rank-less: a popularity rebalance targets the whole active
            # set; an explicit ranks list is a caller error (it would
            # silently mean something else)
            if ranks:
                raise ValueError("rebalance takes no 'ranks' (it re-places "
                                 "over the whole active set)")
            ranks = []
        else:
            if not isinstance(ranks, (list, tuple)) or not ranks:
                raise ValueError(f"{cmd} needs a non-empty 'ranks' list")
            ranks = [int(r) for r in ranks]
            bad = [r for r in ranks if not 0 <= r < rt.table.world]
            if bad:
                raise ValueError(f"ranks {bad} out of range for "
                                 f"world={rt.table.world}")
        at = command.get("at")
        if at is not None:
            at = float(at)
            if at < rt.clock.now():
                raise ValueError(f"'at'={at} is in the past "
                                 f"(clock={rt.clock.now():.3f})")
            self.fe._scheduled.append({"cmd": cmd, "ranks": ranks, "at": at})
            self.fe._scheduled.sort(key=lambda op: op["at"])
            return {"ranks": ranks, "at": at, "scheduled": True}
        rt.control.request(cmd, ranks)
        return {"ranks": ranks, "at": None, "requested": True}

    def _status(self) -> dict:
        fe, rt, eng = self.fe, self.fe.rt, self.fe.engine
        entries = rt.table.entries
        return {
            "clock_s": rt.clock.now(),
            "epoch": rt.epoch,
            "version": int(np.asarray(rt.membership.version)),
            "policy": rt.policy.name,
            "dispatch": eng.dispatch,
            "world": rt.table.world,
            "active_ranks": [r for r in range(rt.table.world)
                             if entries[r].active],
            "drained_ranks": [r for r in range(rt.table.world)
                              if entries[r].drained],
            "active_fraction": rt.active_fraction(),
            "compile_count": eng.compile_count(),
            "queue_depth": len(eng.sched.queue),
            "inflight": eng.sched.inflight,
            "live_streams": len(fe.live_streams),
            "pending_admin": len(fe._scheduled),
            "scheduler": asdict(eng.sched.stats),
            "kv": eng.kv.stats(),
            "degraded": eng.degraded,
            # imperfect-detection surface: per-rank heartbeat ages,
            # suspicion verdicts and the fault-domain tree, so an operator
            # can tell a fenced-but-alive rank from a dead one
            "suspicion": rt.detector.suspicion_state(),
            "topology": rt.table.topology.to_json(),
            "fences": len(rt.fence_events),
            # popularity surface: what the runtime has LEARNED about the
            # router distribution (EMA, normalized), how the placement
            # answers it (replicas per expert), and how balanced the
            # result is (1.0 = every active rank equally loaded)
            "expert_load": (None if rt.expert_load is None else
                            [round(float(x), 6) for x in
                             rt.expert_load / rt.expert_load.sum()]),
            "expert_replicas": {str(e): n for e, n in
                                sorted(rt.expert_replica_counts().items())},
            "load_imbalance": round(rt.load_imbalance(), 6),
            "popularity_aware": rt.popularity_aware,
        }

    def _epoch(self) -> dict:
        rt = self.fe.rt
        return {"epoch": rt.epoch,
                "version": int(np.asarray(rt.membership.version))}

    def _incidents(self, command: dict) -> dict:
        rt = self.fe.rt
        last = int(command.get("last", 20))
        return {
            "incidents": [{"incident": inc, "phases": phases}
                          for inc, phases in
                          sorted(rt.obs.incident_totals().items())],
            "events": [e.to_dict() for e in rt.obs.events[-last:]],
            "fences": list(rt.fence_events[-last:]),
        }
