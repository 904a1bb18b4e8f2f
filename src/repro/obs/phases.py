"""PhaseClock: structured, phase-aware recovery telemetry.

The paper's headline claims are *time-shaped* (an 11 s recovery pause, an
8 s reintegration pause, throughput back to 95% within 52 s), so the
runtime's telemetry must be too. A flat event list cannot answer "how long
was the replan phase of incident 2" without re-parsing ad-hoc detail dicts;
this module makes the recovery lifecycle first-class:

  * an **incident** is one composed recovery saga — everything between a
    failure detection and the final rejoin of every casualty, including
    cascades that restart repair rounds mid-flight;
  * a **phase span** is one timed segment of an incident, tagged with the
    canonical phase vocabulary (``PHASES``, defined below and documented in
    ``docs/recovery-lifecycle.md`` — code and prose share this one list);
  * every **event** emitted while a span is open inherits (incident, phase,
    step index, active fraction, scenario, dispatch mode) automatically.

Phase vocabulary (critical-path phases pause healthy ranks; background
phases run off the serving path):

  detect           failure timeout + in-flight request drain   (critical)
  replan           EPLB over survivors + repair planning +
                   metadata broadcast                          (critical)
  repair-transfer  tier-2/3 weight movement incl. escalations  (critical)
  warmup           casualty's local relaunch/init/load/capture (background)
  table-patch      healthy-rank join patch (peer entry refresh
                   + placement publish)                        (critical)
  rejoin           instantaneous marker: rank active again     (marker)
  drain            planned maintenance drain: replan + weight
                   transfer, no detect window                  (critical)
  scale-down       planned elastic shrink (same mechanics as
                   drain; tracked separately)                  (critical)
  rebalance        popularity-driven re-place toward the
                   tracked hot experts; membership untouched   (background)
  kv-migrate       departing ranks' KV pages ship to the
                   survivors, nested INSIDE the drain /
                   scale-down window before its table patch    (nested)

The fixed-membership baseline reports a single ``full-restart`` span.
``kv-migrate`` is deliberately NOT critical-path: it nests inside the
already-critical drain span (the pause is charged once, by the outer
span), so the no-critical-overlap rule stays intact.

Well-formedness (checked by :func:`validate_spans`, asserted across the
whole scenario registry by the tier-1 tests): spans are closed and
monotonic, critical-path spans never overlap (healthy ranks are paused —
there is exactly one control plane), no warmup/join span of an incident
starts before that incident's recovery control plane (detect + repair
rounds) has ended, and per rank the rejoin marker never precedes the end
of the rank's last warmup. Repair rounds may alternate
replan/repair-transfer (cascade composition), a rank may restart warmup
(abort) — even while a sibling rank of the same incident has already
rejoined — and warmups of different ranks overlap freely: they are
background work.

Two clocks. Span and event times (``t_start``/``t_end``, ``t``) are read
from the clock the recorder is given (the runtime passes ``SimClock.now``):
the modelled seconds every report, check and test reads. Beside them,
``wall_start``/``wall_end`` (``wall_t`` on events) are read from
``time.perf_counter``: the wall time of the real work the program did
(slot moves, membership publication). A synchronous span and a marker
also put ``repro.phase.<phase>`` on the profiler's timeline
(``repro.obs.trace``), so recovery work shows on the same device trace
as the serve step.

Dependency-free on purpose: the CI lint job runs the report selftest with
nothing installed beyond the standard library.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Callable, Optional

from repro.obs import trace

#: Canonical recovery phases, in lifecycle order (see module docstring and
#: docs/recovery-lifecycle.md — keep the two in sync).
PHASES = ("detect", "replan", "repair-transfer", "warmup", "table-patch",
          "rejoin")
#: Planned-transition phases: deliberate membership changes issued through
#: the control plane (repro.core.transitions). A ``drain`` / ``scale-down``
#: span covers the whole planned pause — replan + weight transfer, with no
#: detect window (the departing rank is alive and cooperating). Undrains
#: and scale-ups reuse ``warmup``/``table-patch``/``rejoin``. A
#: ``rebalance`` span covers a popularity-driven re-place (replicas move
#: toward the tracked hot experts; membership itself is untouched) — it is
#: deliberately NOT critical-path: the extra replica copies stream in the
#: background while every rank keeps serving from its current placement,
#: and only the final table patch (charged to the span's recorded pause)
#: flips routing.
PLANNED_PHASES = ("drain", "scale-down", "rebalance")
#: Sub-phases: timed segments nested inside another phase's span. The KV
#: page transfer of a planned drain (serving data plane: PagedKVPool
#: residency moving to the survivors) runs inside the drain/scale-down
#: window, sequenced before the table patch.
SUB_PHASES = ("kv-migrate",)
#: Fencing marker: the moment a commit invalidates a suspected /
#: partitioned rank's epoch. Instantaneous (the fence IS the epoch bump of
#: the shrink commit, whose time is already charged to ``replan``), so it
#: is a marker like ``rejoin``, not a critical-path pause.
FENCE_PHASES = ("fence",)
#: Phases only the fixed-membership baseline emits.
BASELINE_PHASES = ("full-restart",)
ALL_PHASES = (PHASES + PLANNED_PHASES + SUB_PHASES + FENCE_PHASES
              + BASELINE_PHASES)

#: Lifecycle stage per phase: within one incident the stage index of
#: successive spans (by start time) must be non-decreasing.
_STAGE = {"detect": 0, "replan": 1, "repair-transfer": 1, "warmup": 2,
          "table-patch": 3, "rejoin": 3, "full-restart": 0,
          "drain": 1, "scale-down": 1, "rebalance": 1, "kv-migrate": 1,
          "fence": 1}

#: Critical-path phases pause every healthy rank, so they are globally
#: serial: no two such spans may overlap, across incidents included.
CRITICAL_PHASES = ("detect", "replan", "repair-transfer", "table-patch",
                   "full-restart", "drain", "scale-down")

_OPEN = -1.0      # sentinel t_end of a span that has not been closed yet


@dataclass
class PhaseSpan:
    """One timed segment of a recovery incident."""
    incident: int
    phase: str
    t_start: float
    t_end: float = _OPEN
    step_start: int = 0
    step_end: int = 0
    active_fraction: float = 1.0     # sampled when the span closes
    meta: dict = field(default_factory=dict)
    wall_start: float = 0.0          # perf_counter seconds of the work
    wall_end: float = _OPEN

    @property
    def open(self) -> bool:
        return self.t_end == _OPEN

    @property
    def duration_s(self) -> float:
        return 0.0 if self.open else self.t_end - self.t_start

    def to_dict(self) -> dict:
        return {
            "incident": self.incident,
            "phase": self.phase,
            "t_start": round(self.t_start, 6),
            "t_end": round(self.t_end, 6),
            "duration_s": round(self.duration_s, 6),
            "step_start": self.step_start,
            "step_end": self.step_end,
            "active_fraction": round(self.active_fraction, 6),
            "meta": dict(self.meta),
        }


@dataclass
class ObsEvent:
    """A timeline event enriched with its telemetry context."""
    t: float
    kind: str
    incident: int                    # -1 when outside any incident
    phase: Optional[str]             # innermost open stacked span, if any
    step: int
    active_fraction: float
    detail: dict = field(default_factory=dict)
    wall_t: float = 0.0              # perf_counter seconds

    def to_dict(self) -> dict:
        return {"t": round(self.t, 6), "kind": self.kind,
                "incident": self.incident, "phase": self.phase,
                "step": self.step,
                "active_fraction": round(self.active_fraction, 6),
                "detail": dict(self.detail)}


class PhaseClock:
    """Span/event recorder over a monotonic clock.

    Two kinds of spans:

      * **stacked** spans (``span(...)`` context manager) for the synchronous
        control-plane phases — they nest, and events emitted inside inherit
        the innermost one;
      * **keyed** spans (``open_span``/``close_span``) for background work
        that outlives the call stack, e.g. a casualty's warmup that runs
        across many serving steps.

    The clock is any ``() -> float`` (the runtime passes ``SimClock.now``),
    so the same recorder works under simulated or wall time; the
    wall-clock fields are ``time.perf_counter`` seconds.
    """

    def __init__(self, now: Callable[[], float], *,
                 scenario: Optional[str] = None, dispatch: str = "dense",
                 sample_active: Optional[Callable[[], float]] = None):
        self.now = now
        self.scenario = scenario
        self.dispatch = dispatch
        self.sample_active = sample_active or (lambda: 1.0)
        self.step = 0                       # serving-step index (engine ticks)
        self.spans: list[PhaseSpan] = []    # append-ordered by t_start
        self.events: list[ObsEvent] = []
        self._stack: list[PhaseSpan] = []
        self._keyed: dict = {}
        self._n_incidents = 0
        self._rank_incident: dict[int, int] = {}

    # -- context -----------------------------------------------------------
    def tick(self) -> None:
        """One serving-engine step boundary."""
        self.step += 1

    def incident(self, kind: str, ranks=()) -> int:
        """Open a new incident and bind the given ranks to it."""
        i = self._n_incidents
        self._n_incidents += 1
        for r in ranks:
            self._rank_incident[int(r)] = i
        return i

    def bind_rank(self, rank: int, incident: int) -> None:
        self._rank_incident[int(rank)] = incident

    def incident_of(self, rank: int, default: int = -1) -> int:
        return self._rank_incident.get(int(rank), default)

    # -- spans -------------------------------------------------------------
    def _new_span(self, phase: str, incident: int, meta: dict,
                  t_start: Optional[float] = None) -> PhaseSpan:
        now = self.now()
        if t_start is None:
            t_start = now
        else:
            # Retroactive start: a detect span opens at the silent rank's
            # last heartbeat, which is in the past by the time the timeout
            # fires. Clamp so the recorded list stays monotonic (rule 3)
            # and critical spans stay serial (rule 4) even when the
            # measured age reaches back past an earlier span.
            floor = self.spans[-1].t_start if self.spans else 0.0
            if phase in CRITICAL_PHASES:
                for s in self.spans:
                    if s.phase in CRITICAL_PHASES and not s.open:
                        floor = max(floor, s.t_end)
            t_start = min(now, max(float(t_start), floor))
        sp = PhaseSpan(incident=incident, phase=phase, t_start=t_start,
                       step_start=self.step, meta=meta,
                       wall_start=time.perf_counter())
        self.spans.append(sp)
        return sp

    def _close(self, sp: PhaseSpan, extra: dict) -> PhaseSpan:
        sp.wall_end = time.perf_counter()
        sp.t_end = self.now()
        sp.step_end = self.step
        sp.active_fraction = float(self.sample_active())
        if extra:
            sp.meta.update(extra)
        return sp

    @contextmanager
    def span(self, phase: str, incident: int,
             t_start: Optional[float] = None, **meta):
        """A synchronous (stacked) phase span around a block of work.
        ``t_start`` opens the span retroactively (clamped to keep the
        span list well-formed) — used by the detect phase, whose real
        beginning is the failed rank's last heartbeat."""
        sp = self._new_span(phase, incident, meta, t_start=t_start)
        self._stack.append(sp)
        try:
            with trace.span("phase." + phase):
                yield sp
        finally:
            self._stack.pop()
            self._close(sp, {})

    def open_span(self, key, phase: str, incident: int, **meta) -> PhaseSpan:
        """Begin a background span that a later call will close by key."""
        if key in self._keyed:                 # defensive: never leak opens
            self.close_span(key, superseded=True)
        sp = self._new_span(phase, incident, meta)
        self._keyed[key] = sp
        return sp

    def close_span(self, key, **meta) -> Optional[PhaseSpan]:
        sp = self._keyed.pop(key, None)
        return None if sp is None else self._close(sp, meta)

    def mark(self, phase: str, incident: int, **meta) -> PhaseSpan:
        """An instantaneous marker span (t_end == t_start)."""
        sp = self._new_span(phase, incident, meta)
        with trace.span("phase." + phase):
            pass
        return self._close(sp, {})

    def finalize(self) -> None:
        """Close every still-open span (e.g. a warmup cut off by the
        scenario horizon) so harvested spans are always well-formed."""
        for key in list(self._keyed):
            self.close_span(key, truncated=True)
        while self._stack:
            self._close(self._stack.pop(), {"truncated": True})

    # -- events ------------------------------------------------------------
    def current_phase(self) -> Optional[str]:
        return self._stack[-1].phase if self._stack else None

    def current_incident(self) -> int:
        return self._stack[-1].incident if self._stack else -1

    def emit(self, _kind: str, _incident: Optional[int] = None,
             **detail) -> ObsEvent:
        """Record one event with the current telemetry context. Events
        emitted outside any span (e.g. the failure that OPENS an incident,
        or the recovery_done after its spans closed) pass ``_incident``
        explicitly; inside a span the innermost one wins. The positional
        parameter is underscored so ``detail`` may itself carry a ``kind``
        key (e.g. a fence event's failure kind)."""
        inc = self.current_incident()
        if inc < 0 and _incident is not None:
            inc = _incident
        ev = ObsEvent(t=self.now(), kind=_kind, incident=inc,
                      phase=self.current_phase(), step=self.step,
                      active_fraction=float(self.sample_active()),
                      detail=detail, wall_t=time.perf_counter())
        self.events.append(ev)
        return ev

    # -- summaries ---------------------------------------------------------
    def phase_totals(self) -> dict[str, float]:
        """Summed seconds per phase over all closed spans."""
        out: dict[str, float] = {}
        for sp in self.spans:
            if not sp.open:
                out[sp.phase] = out.get(sp.phase, 0.0) + sp.duration_s
        return out

    def incident_totals(self) -> dict[int, dict[str, float]]:
        """Per-incident phase breakdown (seconds)."""
        out: dict[int, dict[str, float]] = {}
        for sp in self.spans:
            if sp.open:
                continue
            d = out.setdefault(sp.incident, {})
            d[sp.phase] = d.get(sp.phase, 0.0) + sp.duration_s
        return out


# ---------------------------------------------------------------------------
# Well-formedness checking (shared by tests and the report generator)
# ---------------------------------------------------------------------------

def _get(sp, name):
    return sp[name] if isinstance(sp, dict) else getattr(sp, name)


def validate_spans(spans, eps: float = 1e-9) -> list[str]:
    """Return every well-formedness violation in a span list (empty = ok).

    Checks, in order:
      1. every phase is in the canonical vocabulary;
      2. every span is closed, with ``0 <= t_start <= t_end``;
      3. spans were recorded in non-decreasing start order (monotonic);
      4. critical-path spans never overlap — across incidents too;
      5. within an incident, no warmup/join span starts before the
         recovery control plane (detect + repair rounds) has ended —
         warmup/join spans of different ranks may interleave freely;
      6. a rank's rejoin marker never precedes the end of that rank's
         last warmup span in the same incident.
    """
    bad: list[str] = []

    def say(msg, sp):
        bad.append(f"{msg}: incident={_get(sp, 'incident')} "
                   f"phase={_get(sp, 'phase')} "
                   f"[{_get(sp, 't_start')}, {_get(sp, 't_end')}]")

    prev_start = -1.0
    for sp in spans:
        phase, t0, t1 = _get(sp, "phase"), _get(sp, "t_start"), _get(sp, "t_end")
        if phase not in ALL_PHASES:
            say("unknown phase", sp)
            continue
        if t1 == _OPEN:
            say("span never closed", sp)
            continue
        if t0 < 0 or t1 < t0 - eps:
            say("negative time or inverted span", sp)
        if t0 < prev_start - eps:
            say("span starts before its predecessor (non-monotonic)", sp)
        prev_start = max(prev_start, t0)

    # 4. critical-path spans are globally serial
    crit = sorted((s for s in spans if _get(s, "phase") in CRITICAL_PHASES
                   and _get(s, "t_end") != _OPEN),
                  key=lambda s: (_get(s, "t_start"), _get(s, "t_end")))
    for a, b in zip(crit, crit[1:]):
        if _get(b, "t_start") < _get(a, "t_end") - eps:
            say(f"critical-path overlap with {_get(a, 'phase')} "
                f"(incident {_get(a, 'incident')})", b)

    # 5. stage ordering within each incident
    by_inc: dict[int, list] = {}
    for sp in spans:
        if _get(sp, "phase") in ALL_PHASES and _get(sp, "t_end") != _OPEN:
            by_inc.setdefault(_get(sp, "incident"), []).append(sp)
    for inc, group in by_inc.items():
        group.sort(key=lambda s: (_get(s, "t_start"),
                                  _STAGE[_get(s, "phase")]))
        # the recovery control plane (detect + repair rounds) runs
        # synchronously inside handle_failure, so every stage-0/1 span of
        # the incident must end before its first warmup/join span starts.
        # Stages 2/3 interleave per rank (aborted warmups restart while a
        # sibling rank is already rejoining), so they are NOT mutually
        # ordered at incident level — only per rank (checked below).
        recovery_end = max((_get(s, "t_end") for s in group
                            if _STAGE[_get(s, "phase")] <= 1), default=None)
        if recovery_end is not None:
            for sp in group:
                if _STAGE[_get(sp, "phase")] >= 2 \
                        and _get(sp, "t_start") < recovery_end - eps:
                    say(f"stage regression (incident {inc}: warmup/join "
                        f"span starts before recovery ended)", sp)
        # 6. per-rank: rejoin after that rank's warmup ended
        warm_end: dict[int, float] = {}
        for sp in group:
            if _get(sp, "phase") == "warmup":
                r = _get(sp, "meta").get("rank")
                if r is not None:
                    warm_end[int(r)] = max(warm_end.get(int(r), 0.0),
                                           _get(sp, "t_end"))
        for sp in group:
            if _get(sp, "phase") == "rejoin":
                r = _get(sp, "meta").get("rank")
                if r is not None and int(r) in warm_end \
                        and _get(sp, "t_start") < warm_end[int(r)] - eps:
                    say("rejoin before warmup completed", sp)
    return bad
