"""Telemetry layer (repro.obs): PhaseClock span semantics, span
well-formedness validation, report-generator determinism on a golden
fixture, and ci_compare round-trips of the widened metric set.

The registry-wide "spans are well-nested and monotonic across every
scenario x both dispatch modes" assertion lives in
tests/test_scenarios.py::test_registry_e2e_invariants (which already runs
the full sweep); this file covers the layer itself plus targeted e2e
probes of the span shapes each scenario class must produce.
"""
import json

import pytest

from repro.obs.phases import (
    ALL_PHASES,
    PHASES,
    PhaseClock,
    validate_spans,
)
from repro.obs.report import (
    PAPER_CLAIMS,
    _synthetic_doc,
    build_report,
    measure,
    render_json,
    selftest,
)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def now(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# PhaseClock unit semantics
# ---------------------------------------------------------------------------

def test_phaseclock_span_records_time_step_and_context():
    clk = FakeClock()
    pc = PhaseClock(clk.now, scenario="s", dispatch="ragged",
                    sample_active=lambda: 0.75)
    pc.tick()
    inc = pc.incident("failure", ranks=[2, 5])
    with pc.span("detect", inc, ranks=[2, 5]):
        clk.advance(1.5)
        ev = pc.emit("failure", ranks=[2, 5])
    assert ev.phase == "detect" and ev.incident == inc and ev.step == 1
    assert ev.active_fraction == 0.75
    (sp,) = pc.spans
    assert (sp.phase, sp.incident) == ("detect", inc)
    assert sp.t_start == 0.0 and sp.t_end == 1.5 and sp.duration_s == 1.5
    assert sp.step_start == sp.step_end == 1
    assert pc.incident_of(2) == inc and pc.incident_of(5) == inc
    assert pc.incident_of(7, -1) == -1
    assert pc.current_phase() is None        # span closed
    assert pc.emit("outside").phase is None


def test_phaseclock_keyed_spans_abort_and_finalize():
    clk = FakeClock()
    pc = PhaseClock(clk.now)
    inc = pc.incident("failure", ranks=[3])
    pc.open_span(("warmup", 3), "warmup", incident=inc, rank=3)
    clk.advance(2.0)
    sp = pc.close_span(("warmup", 3), aborted=True)
    assert sp.duration_s == 2.0 and sp.meta["aborted"]
    pc.open_span(("warmup", 3), "warmup", incident=inc, rank=3,
                 restarted=True)
    clk.advance(1.0)
    pc.finalize()                            # horizon cut the warmup short
    assert all(not s.open for s in pc.spans)
    assert pc.spans[-1].meta["truncated"]
    assert pc.close_span(("warmup", 99)) is None   # unknown key: no-op
    totals = pc.phase_totals()
    assert totals == {"warmup": 3.0}


def test_phaseclock_incident_totals_and_mark():
    clk = FakeClock()
    pc = PhaseClock(clk.now)
    i0 = pc.incident("failure")
    with pc.span("detect", i0):
        clk.advance(1.0)
    with pc.span("replan", i0):
        clk.advance(0.5)
    sp = pc.mark("rejoin", i0, rank=1)
    assert sp.duration_s == 0.0
    assert pc.incident_totals() == {
        i0: {"detect": 1.0, "replan": 0.5, "rejoin": 0.0}}


# ---------------------------------------------------------------------------
# validate_spans
# ---------------------------------------------------------------------------

def _span(phase, t0, t1, inc=0, **meta):
    return {"incident": inc, "phase": phase, "t_start": t0, "t_end": t1,
            "duration_s": t1 - t0, "step_start": 0, "step_end": 0,
            "active_fraction": 1.0, "meta": meta}


def test_validate_spans_accepts_composed_lifecycle():
    spans = [
        _span("detect", 1.0, 2.5, ranks=[2]),
        _span("replan", 2.5, 3.3),
        _span("repair-transfer", 3.3, 3.4),
        _span("replan", 3.4, 4.2),           # cascade: round restarts
        _span("repair-transfer", 4.2, 4.3),
        _span("warmup", 4.3, 6.0, rank=2),
        _span("warmup", 4.3, 9.3, rank=5),   # concurrent warmups are fine
        _span("table-patch", 9.3, 9.7, ranks=[2, 5]),
        _span("rejoin", 9.7, 9.7, rank=2),
        _span("rejoin", 9.7, 9.7, rank=5),
    ]
    assert validate_spans(spans) == []


@pytest.mark.parametrize("bad,needle", [
    ([_span("explode", 0, 1)], "unknown phase"),
    ([_span("detect", 0, -1.0)], "never closed"),       # -1 == open sentinel
    ([_span("detect", 2.0, 1.0)], "inverted"),
    ([_span("replan", 5, 6), _span("detect", 0, 1)], "non-monotonic"),
    ([_span("detect", 0, 2), _span("replan", 1, 3)], "critical-path overlap"),
    ([_span("warmup", 0, 5, rank=1), _span("detect", 6, 7)],
     "stage regression"),
    ([_span("detect", 0, 1), _span("warmup", 1, 9, rank=3),
      _span("rejoin", 5, 5, rank=3)], "rejoin before warmup"),
])
def test_validate_spans_flags_violations(bad, needle):
    msgs = validate_spans(bad)
    assert msgs and any(needle in m for m in msgs), (needle, msgs)


def test_validate_spans_allows_warmup_restart_after_sibling_rejoin():
    """Flapping casualty: rank 5's warmup aborts and restarts AFTER rank 2
    (same incident) already rejoined. Stages 2/3 interleave per rank; this
    must not be flagged as a stage regression."""
    spans = [
        _span("detect", 1.0, 2.5, ranks=[2, 5]),
        _span("replan", 2.5, 3.3),
        _span("repair-transfer", 3.3, 3.4),
        _span("warmup", 3.4, 9.4, rank=2),
        _span("warmup", 3.4, 9.7, rank=5, aborted=True),
        _span("table-patch", 9.4, 9.8, ranks=[2]),
        _span("rejoin", 9.8, 9.8, rank=2),
        _span("warmup", 9.82, 14.85, rank=5, restarted=True),
        _span("table-patch", 14.9, 15.3, ranks=[5]),
        _span("rejoin", 15.3, 15.3, rank=5),
    ]
    assert validate_spans(spans) == []


def test_validate_spans_allows_concurrent_warmup_under_critical_span():
    # a later incident's detect may start while an earlier incident's
    # casualty is still warming: warmup is background, not critical-path
    spans = [
        _span("detect", 0.0, 1.0, inc=0),
        _span("warmup", 1.0, 20.0, inc=0, rank=1),
        _span("detect", 5.0, 6.0, inc=1),
        _span("replan", 6.0, 6.8, inc=1),
    ]
    assert validate_spans(spans) == []


# ---------------------------------------------------------------------------
# e2e span shapes per scenario class (the full-registry sweep lives in
# test_scenarios.py; these probe the specific structures)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cascade_result():
    from repro.runtime.scenario_runner import run_scenario
    return run_scenario("cascade_mid_recovery")


def test_cascade_composes_rounds_into_one_incident(cascade_result):
    res = cascade_result
    assert validate_spans(res.spans) == []
    incidents = {s["incident"] for s in res.spans}
    assert incidents == {0}                   # ONE composed incident
    replans = [s for s in res.spans if s["phase"] == "replan"]
    assert len(replans) >= 2                  # the cascade restarted a round
    assert res.phase_totals["replan"] == pytest.approx(
        sum(s["duration_s"] for s in replans))
    # both casualties warmed up and rejoined under the same incident
    warm_ranks = {s["meta"]["rank"] for s in res.spans
                  if s["phase"] == "warmup"}
    rejoin_ranks = {s["meta"]["rank"] for s in res.spans
                    if s["phase"] == "rejoin"}
    assert warm_ranks == rejoin_ranks == {2, 5}


def test_cascade_restore_95_and_summary_fields(cascade_result):
    res = cascade_result
    assert 0 < res.restore_95_s < 30.0
    s = res.summary()
    assert s["restore_95_s"] == pytest.approx(res.restore_95_s)
    assert set(s["phases"]) <= set(ALL_PHASES)
    assert s["phases"]["detect"] == pytest.approx(1.5)
    # events carry the scenario/dispatch/step context
    assert res.dispatch == "dense"


def test_rejoin_storm_single_table_patch_span():
    from repro.runtime.scenario_runner import run_scenario
    res = run_scenario("rejoin_storm")
    assert validate_spans(res.spans) == []
    patches = [s for s in res.spans if s["phase"] == "table-patch"]
    assert len(patches) == 1                  # ONE batched patch, not three
    assert patches[0]["meta"]["ranks"] == [1, 3, 5]
    assert len([s for s in res.spans if s["phase"] == "warmup"]) == 3


def test_warmup_abort_closes_and_reopens_span():
    from repro.runtime.scenario_runner import run_scenario
    res = run_scenario("failure_during_warmup")
    assert validate_spans(res.spans) == []
    warmups = [s for s in res.spans if s["phase"] == "warmup"]
    assert len(warmups) == 2
    assert warmups[0]["meta"].get("aborted") is True
    assert warmups[1]["meta"].get("restarted") is True
    assert warmups[1]["t_start"] >= warmups[0]["t_end"]


def test_warmup_restart_after_sibling_rejoin_e2e():
    """Regression: a casualty whose warmup aborts again AFTER a sibling
    rank of the same incident already rejoined must still produce a valid
    span list (stages 2/3 interleave per rank)."""
    from repro.core.scenarios import Scenario
    from repro.runtime.scenario_runner import run_scenario
    scn = Scenario(
        name="tmp_flap_during_join", description="",
        schedule="@1.0 fail 2 5\n@5.0 fail 5\n@9.7 fail 5",
        world=8, horizon_s=30.0)
    res = run_scenario(scn)
    assert validate_spans(res.spans) == []
    assert res.warmup_aborts >= 2
    warm5 = [s for s in res.spans if s["phase"] == "warmup"
             and s["meta"]["rank"] == 5]
    rejoin2 = [s for s in res.spans if s["phase"] == "rejoin"
               and s["meta"]["rank"] == 2]
    assert len(warm5) >= 3 and rejoin2
    # the pattern under test actually occurred: a restarted warmup began
    # after the sibling's rejoin
    assert any(w["t_start"] >= rejoin2[0]["t_start"] for w in warm5)
    assert res.final_active_fraction == 1.0 and res.invariants_ok


def test_full_restart_baseline_single_span():
    from repro.runtime.scenario_runner import run_scenario
    res = run_scenario("concurrent_multi_failure", fixed_membership=True,
                       check_invariants=False)
    assert validate_spans(res.spans) == []
    assert [s["phase"] for s in res.spans] == ["full-restart"]
    assert res.spans[0]["duration_s"] == pytest.approx(348.0)


# ---------------------------------------------------------------------------
# Report generator: deterministic on the golden fixture
# ---------------------------------------------------------------------------

def test_report_selftest():
    selftest()


def test_report_deterministic_and_complete_on_golden_fixture():
    doc = _synthetic_doc()
    static = {"rows": [{"concurrency": 8, "overhead_pct": 1.9},
                       {"concurrency": 16, "overhead_pct": -3.0}]}
    md1, js1, svg1 = build_report(doc, static)
    md2, js2, svg2 = build_report(_synthetic_doc(), static)
    assert md1 == md2 and render_json(js1) == render_json(js2)
    assert svg1 == svg2
    # parity table covers every paper claim, with the ragged row measured
    claims = {p["claim"] for p in js1["parity"]}
    assert claims == set(PAPER_CLAIMS)
    m = measure(doc, static)
    assert m["recovery_pause_s"] == pytest.approx(2.4)   # 1.5 + 0.8 + 0.1
    assert m["reintegration_pause_s"] == pytest.approx(0.4)
    assert m["restore_95_s"] == pytest.approx(7.9)
    assert m["full_restart_outage_s"] == pytest.approx(348.0)
    assert m["steady_overhead_pct"] == pytest.approx(3.0)
    # per-mode rows present
    assert [(r["name"], r["dispatch"]) for r in js1["scenarios"]] == [
        ("synthetic_single_failure", "dense"),
        ("synthetic_single_failure", "ragged")]
    # one trajectory SVG per elastic row + the phase-breakdown chart
    assert sorted(svg1) == ["svg/phase_breakdown.svg",
                            "svg/synthetic_single_failure_dense.svg",
                            "svg/synthetic_single_failure_ragged.svg"]


def test_report_cli_writes_files(tmp_path):
    from repro.launch.report import main as report_main
    doc = _synthetic_doc()
    scen = tmp_path / "BENCH_scenarios.json"
    scen.write_text(json.dumps(doc))
    out = tmp_path / "report"
    rc = report_main(["--scenarios", str(scen), "--static",
                      str(tmp_path / "missing.json"), "--out-dir", str(out)])
    assert rc == 0
    assert (out / "REPORT.md").exists()
    got = json.loads((out / "REPORT.json").read_text())
    assert got["parity"] and got["scenarios"]
    svgs = sorted(p.name for p in (out / "svg").iterdir())
    assert "phase_breakdown.svg" in svgs
    # deterministic across runs: re-render and byte-compare
    md_first = (out / "REPORT.md").read_text()
    report_main(["--scenarios", str(scen), "--static",
                 str(tmp_path / "missing.json"), "--out-dir", str(out)])
    assert (out / "REPORT.md").read_text() == md_first


def test_report_soft_claim_warns_but_does_not_gate(tmp_path):
    """The steady-overhead claim is real wall time: a noisy CPU measurement
    over the paper's bound must WARN in the table but exit 0."""
    from repro.launch.report import main as report_main
    from repro.obs.report import parity_table
    parity = parity_table({"recovery_pause_s": 3.0,
                           "reintegration_pause_s": 0.4,
                           "restore_95_s": 9.0,
                           "full_restart_outage_s": 348.0,
                           "steady_overhead_pct": 27.5})
    by = {p["claim"]: p["status"] for p in parity}
    assert by["steady_overhead_pct"] == "WARN"
    assert all(s == "PASS" for c, s in by.items()
               if c != "steady_overhead_pct")
    # and a hard claim over its bound still FAILs
    parity = parity_table({"recovery_pause_s": 30.0})
    assert {p["claim"]: p["status"]
            for p in parity}["recovery_pause_s"] == "FAIL"
    # end to end: noisy static artifact -> exit 0, WARN in REPORT.json
    scen = tmp_path / "BENCH_scenarios.json"
    scen.write_text(json.dumps(_synthetic_doc()))
    static = tmp_path / "BENCH_static.json"
    static.write_text(json.dumps(
        {"rows": [{"concurrency": 8, "overhead_pct": 27.5}]}))
    rc = report_main(["--scenarios", str(scen), "--static", str(static),
                      "--out-dir", str(tmp_path / "r")])
    assert rc == 0
    got = json.loads((tmp_path / "r" / "REPORT.json").read_text())
    assert {p["claim"]: p["status"] for p in got["parity"]}[
        "steady_overhead_pct"] == "WARN"


def test_report_cli_missing_artifact(tmp_path):
    from repro.launch.report import main as report_main
    rc = report_main(["--scenarios", str(tmp_path / "nope.json"),
                      "--out-dir", str(tmp_path / "r")])
    assert rc == 2


def test_report_flags_malformed_spans(tmp_path):
    from repro.launch.report import main as report_main
    doc = _synthetic_doc()
    # corrupt one span: replan overlapping detect (critical-path overlap)
    doc["scenarios"][0]["spans"][1]["t_start"] = 1.2
    scen = tmp_path / "BENCH_scenarios.json"
    scen.write_text(json.dumps(doc))
    rc = report_main(["--scenarios", str(scen), "--static", "",
                      "--out-dir", str(tmp_path / "r")])
    assert rc == 1
    got = json.loads((tmp_path / "r" / "REPORT.json").read_text())
    assert got["span_violations"]


# ---------------------------------------------------------------------------
# ci_compare round-trips the widened scenario metric set
# ---------------------------------------------------------------------------

def _scen_doc(downtime=2.3, replan=0.8, r95=7.8, tokens=2000, drain=0.8):
    return {"scenarios": [{
        "name": "cascade_mid_recovery", "dispatch": "ragged",
        "tokens_out": tokens, "downtime_s": downtime,
        "phases": {"detect": 1.5, "replan": replan, "repair-transfer": 0.01,
                   "warmup": 5.0, "table-patch": 0.4},
        "restore_95_s": r95,
    }, {
        "name": "majority_coverage_loss", "dispatch": "dense",
        "tokens_out": 50, "downtime_s": 0.0,
        "phases": {"detect": 1.5},
        "restore_95_s": -1.0,                 # never restored: no metric
    }, {
        "name": "rolling_maintenance_drain", "dispatch": "dense",
        "tokens_out": 1800, "downtime_s": 2 * drain,
        "phases": {"drain": 2 * drain, "table-patch": 0.8},
        "restore_95_s": -1.0,                 # planned-only: never "failed"
    }]}


def test_ci_compare_roundtrip_widened_metrics():
    from benchmarks import ci_compare
    cur = ci_compare._scenario_metrics(_scen_doc())
    key = "cascade_mid_recovery[ragged]"
    assert cur[f"{key}/phase/replan_s"] == (0.8, "lower")
    assert cur[f"{key}/phase/table-patch_s"] == (0.4, "lower")
    assert cur[f"{key}/restore_95_s"] == (7.8, "lower")
    assert cur[f"{key}/downtime_s"] == (2.3, "lower")
    assert "majority_coverage_loss[dense]/restore_95_s" not in cur
    assert "majority_coverage_loss[dense]/phase/detect_s" in cur
    # planned-transition pauses ride the same per-phase gate
    assert cur["rolling_maintenance_drain[dense]/phase/drain_s"] == \
        (1.6, "lower")
    # identical docs: round-trips with zero regressions
    assert ci_compare.compare(cur, cur, tolerance=0.15) == []


def test_ci_compare_gates_drain_pause_regressions():
    """A drain pause regressing >15% fails the build like a recovery
    pause does (the planned-transition trajectory gate)."""
    from benchmarks import ci_compare
    prev = ci_compare._scenario_metrics(_scen_doc())
    cur = ci_compare._scenario_metrics(_scen_doc(drain=1.2))
    bad = ci_compare.compare(prev, cur, tolerance=0.15)
    assert any("rolling_maintenance_drain[dense]/phase/drain_s" in b
               for b in bad), bad


def test_ci_compare_gates_client_latency_regressions():
    """TTFT and p99 inter-token stall gate per scenario x mode next to the
    recovery pauses; goodput gates higher-is-better. Pre-frontend
    artifacts (no `client` key) extract nothing and never fail."""
    from benchmarks import ci_compare

    def with_client(ttft=0.3, stall=0.07, goodput=60.0):
        doc = _scen_doc()
        doc["scenarios"][0]["client"] = {
            "delivered_tokens": 1800,
            "ttft_p50_s": ttft, "ttft_p99_s": ttft * 3,
            "stall_p50_s": 0.05, "stall_p99_s": stall,
            "stall_max_s": 5.0, "goodput_tok_s": goodput,
            "tokens_recomputed": 152, "error_events": 0}
        return doc

    prev = ci_compare._scenario_metrics(with_client())
    key = "cascade_mid_recovery[ragged]"
    # the row carries client metrics -> the exactly-once delivered count
    # replaces the legacy tokens_out trajectory for that row
    assert f"{key}/tokens_out" not in prev
    assert f"{key}/tokens_delivered" in prev
    assert prev[f"{key}/client/ttft_p50_s"] == (0.3, "lower")
    assert prev[f"{key}/client/stall_p99_s"] == (0.07, "lower")
    assert prev[f"{key}/client/goodput_tok_s"] == (60.0, "higher")
    assert ci_compare.compare(prev, prev, tolerance=0.15) == []
    cur = ci_compare._scenario_metrics(
        with_client(ttft=0.6, stall=0.2, goodput=30.0))
    bad = ci_compare.compare(prev, cur, tolerance=0.15)
    assert any("client/ttft_p50_s" in b for b in bad), bad
    assert any("client/stall_p99_s" in b for b in bad), bad
    assert any("client/goodput_tok_s" in b for b in bad), bad
    # old artifact shape: no client metrics extracted, trivially passes
    old = ci_compare._scenario_metrics(_scen_doc())
    assert not any("/client/" in k for k in old)
    assert ci_compare.compare(old, cur, tolerance=0.15) == []


def test_ci_compare_catches_phase_and_restore_regressions():
    from benchmarks import ci_compare
    prev = ci_compare._scenario_metrics(_scen_doc())
    cur = ci_compare._scenario_metrics(
        _scen_doc(downtime=4.0, replan=1.6, r95=20.0, tokens=900))
    bad = ci_compare.compare(prev, cur, tolerance=0.15)
    assert any("phase/replan_s" in b for b in bad)
    assert any("restore_95_s" in b for b in bad)
    assert any("tokens_out" in b for b in bad)


def test_ci_compare_old_artifact_shape_still_extracts():
    """Pre-telemetry BENCH_scenarios.json rows (no dispatch/phases keys)
    must not crash the extractor — the first compare after this PR sees
    exactly that shape as --prev."""
    from benchmarks import ci_compare
    old = {"scenarios": [{"name": "x", "tokens_out": 10, "downtime_s": 1.0}]}
    got = ci_compare._scenario_metrics(old)
    assert got == {"x[dense]/tokens_out": (10.0, "higher"),
                   "x[dense]/downtime_s": (1.0, "lower")}


def test_phase_vocabulary_docs_in_sync():
    """The prose phase table and the code constant must agree (the same
    check the CI docs gate runs)."""
    import pathlib
    import sys
    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root / "tools"))
    try:
        import check_docs
        assert check_docs.check_phase_vocabulary() == []
        assert check_docs.check_links() == []
    finally:
        sys.path.remove(str(root / "tools"))


def test_phases_constant_shape():
    assert PHASES == ("detect", "replan", "repair-transfer", "warmup",
                      "table-patch", "rejoin")
    assert set(PHASES) < set(ALL_PHASES)


# ---------------------------------------------------------------------------
# Two clocks (SimClock + wall) and the profiler's timeline
# ---------------------------------------------------------------------------

#: the keys a span serializes to: SimClock times only, as before the wall
#: clock came (reports and scenario artifacts stay deterministic)
SPAN_KEYS = {"incident", "phase", "t_start", "t_end", "duration_s",
             "step_start", "step_end", "active_fraction", "meta"}


def test_phaseclock_wall_times_monotonic_and_nested():
    clk = FakeClock()
    pc = PhaseClock(clk.now)
    inc = pc.incident("failure", ranks=[3])
    with pc.span("detect", inc) as detect:
        clk.advance(1.0)
        ev = pc.emit("failure", ranks=[3])
        with pc.span("replan", inc) as replan:
            clk.advance(0.5)
    pc.open_span(("warmup", 3), "warmup", incident=inc, rank=3)
    clk.advance(2.0)
    pc.close_span(("warmup", 3))
    pc.mark("rejoin", inc, rank=3)
    # SimClock fields as before
    assert (detect.t_start, detect.t_end) == (0.0, 1.5)
    assert (replan.t_start, replan.t_end) == (1.0, 1.5)
    assert ev.t == 1.0
    # wall fields: closed, monotonic in recording order, children inside
    walls = [(s.wall_start, s.wall_end) for s in pc.spans]
    assert all(0 < a <= b for a, b in walls)
    assert [a for a, _ in walls] == sorted(a for a, _ in walls)
    assert detect.wall_start <= ev.wall_t <= replan.wall_start
    assert replan.wall_end <= detect.wall_end
    assert pc.spans[-1].wall_start >= pc.spans[-2].wall_end
    assert all(set(s.to_dict()) == SPAN_KEYS for s in pc.spans)
    assert "wall_t" not in ev.to_dict()


def test_obs_records_with_jax_absent():
    """The no-op path: with jax unimportable, repro.obs still records
    spans on both clocks, and a profiler span does nothing."""
    import os
    import pathlib
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import sys
        sys.modules["jax"] = None            # `import jax` raises
        from repro.obs import trace
        from repro.obs.phases import PhaseClock, validate_spans
        t = [0.0]
        pc = PhaseClock(lambda: t[0])
        inc = pc.incident("failure", ranks=[0])
        with pc.span("detect", inc):
            t[0] = 1.0
        pc.mark("rejoin", inc, rank=0)
        with trace.span("engine.step"):
            pass
        assert validate_spans(pc.spans) == []
        assert all(0 < s.wall_start <= s.wall_end for s in pc.spans)
        assert [s.t_end for s in pc.spans] == [1.0, 1.0]
        print("ok")
    """)
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=dict(os.environ, PYTHONPATH=str(src)),
                         timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


def test_scenario_spans_simclock_and_validation_unchanged(cascade_result):
    """The wall clock leaves the SimClock record alone: a registry scenario
    serializes the same spans on a second run, in the same keys, and they
    validate as before."""
    from repro.runtime.scenario_runner import run_scenario
    again = run_scenario("cascade_mid_recovery")
    assert again.spans == cascade_result.spans
    assert all(set(s) == SPAN_KEYS for s in again.spans)
    assert validate_spans(again.spans) == []
    assert again.phase_totals == cascade_result.phase_totals


def _host_events(trace_dir) -> list:
    """(name, start ns, end ns) of every host event of the trace."""
    import pathlib

    from jax.profiler import ProfileData
    (path,) = pathlib.Path(trace_dir).rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events]


#: how long each slot move of the traced fault is made to take
SLOW_MOVES_S = 0.05


@pytest.fixture(scope="module")
def traced_fault(tmp_path_factory):
    """A tiny elastic instance served under the profiler: requests in
    flight, a two-rank fault, its repair and the ranks' rejoin. Each device
    slot move is slowed by ``SLOW_MOVES_S`` and its wall interval kept,
    with a ``test.slot_moves`` span of its own on the profiler."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core import make_initial_membership
    from repro.core import transitions
    from repro.core.reintegration import WarmupCostModel
    from repro.models import init_params
    from repro.runtime.elastic import ElasticEPRuntime
    from repro.serving.api import ServingFrontend
    from repro.serving.engine import ServingEngine

    moves = []
    real_moves = transitions.apply_slot_moves

    def slow_moves(*a, **kw):
        with jax.profiler.TraceAnnotation("test.slot_moves"):
            t0 = time.perf_counter()
            out = jax.block_until_ready(real_moves(*a, **kw))
            time.sleep(SLOW_MOVES_S)
            moves.append((t0, time.perf_counter()))
        return out

    cfg = get_config("mixtral-8x22b").reduced()
    table = make_initial_membership(8, cfg.moe.num_experts, 1)
    params = init_params(cfg, jax.random.key(0), jnp.float32,
                         table.slot_to_expert, table.num_slots)
    rt = ElasticEPRuntime(cfg, params, table,
                          warmup_model=WarmupCostModel(1, 1, 2, 1))
    eng = ServingEngine(rt, max_batch=4, max_len=64)
    fe = ServingFrontend(eng)
    handles = [fe.submit([3, 1, 4, 1, 5], max_new=30) for _ in range(3)]
    fe.step()                                  # compile outside the trace
    out = tmp_path_factory.mktemp("trace")
    with pytest.MonkeyPatch.context() as mp, jax.profiler.trace(str(out)):
        mp.setattr(transitions, "apply_slot_moves", slow_moves)
        fe.step()
        # both replicas of expert 3: its repair has to move weights
        rt.injector.inject_at(rt.clock.now(), [3, 7])
        for _ in range(2000):
            fe.step()
            if all(h.done for h in handles) and any(
                    s.phase == "rejoin" for s in rt.obs.spans):
                break
    return rt, handles, _host_events(out), moves


def test_engine_step_spans_in_profiler_trace(traced_fault):
    _, handles, events, _ = traced_fault
    names = {n for n, _, _ in events}
    assert all(h.outcome == "FINISHED" for h in handles)
    for stage in ("control", "admit", "inputs", "sync", "bookkeep"):
        assert f"repro.engine.{stage}" in names
    assert {"repro.frontend.step", "repro.frontend.admin"} <= names


def test_recorded_fault_phases_on_both_clocks_and_the_profiler(traced_fault):
    rt, _, events, _ = traced_fault
    names = {n for n, _, _ in events}
    spans = rt.obs.spans
    phases = {s.phase for s in spans}
    assert {"detect", "replan", "repair-transfer", "warmup", "table-patch",
            "rejoin"} <= phases
    assert validate_spans(spans) == []
    for s in spans:
        assert s.t_end >= s.t_start >= 0.0             # SimClock
        assert 0 < s.wall_start <= s.wall_end          # wall clock
    # every synchronous span and marker is on the profiler's timeline; a
    # warmup runs in the background across steps and is not
    for phase in phases - {"warmup"}:
        assert f"repro.phase.{phase}" in names
    assert "repro.phase.warmup" not in names
    assert all(ev.wall_t > 0 for ev in rt.obs.events)


def test_slot_moves_inside_a_phase_span_on_both_clocks(traced_fault):
    """The recovery's real work, the device slot moves of the fault's
    repair and of the rejoin, runs inside a phase span: within the wall
    interval of its ``PhaseSpan`` and under a ``repro.phase.*`` span on
    the profiler's timeline."""
    rt, _, events, moves = traced_fault
    assert moves
    inside = set()
    for t0, t1 in moves:
        (sp,) = [s for s in rt.obs.spans if s.phase != "warmup"
                 and s.wall_start <= t0 and t1 <= s.wall_end]
        inside.add(sp.phase)
    assert {"repair-transfer", "table-patch"} <= inside
    phase_spans = [(s, e) for n, s, e in events
                   if n.startswith("repro.phase.")]
    marked = [(s, e) for n, s, e in events if n == "test.slot_moves"]
    assert len(marked) == len(moves)
    for s, e in marked:
        assert any(ps <= s and e <= pe for ps, pe in phase_spans)
