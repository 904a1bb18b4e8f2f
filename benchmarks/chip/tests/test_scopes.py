"""Program stages in a trace (``scopes.py``): the events ``scopes.read``
takes from the file, the scope of each device op, the stage split of a
step, the host time of the program's own spans and the idle gaps they
name, all over the ``xplane.Reduced`` that ``xplane.reduce`` builds. On
traces recorded on a TPU v5e: the scoped one by ``record_scoped_trace.py``
(three executions of ``jit_scoped_step`` inside ``repro.*`` spans), the
small one by ``record_trace.py``, whose readings by the readers are
pinned, and the serve step's by ``record_serve_trace.py``."""
import pathlib

import pytest

import scopes
import xplane

DATA = pathlib.Path(__file__).resolve().parent / "data"
SCOPED = r"^jit_scoped_step\("
TRACES = ["tpu_small", "tpu_scoped", "tpu_serve"]


def _load(name):
    path = DATA / name
    if not path.is_file():
        pytest.fail(f"missing {path}: record it with the recorder named in "
                    f"the module docstring")
    return path


@pytest.fixture(scope="module")
def scoped():
    return xplane.reduce(_load("tpu_scoped.xplane.pb"))


def test_stage_is_the_innermost_named_scope():
    assert scopes.stage_of(
        "jit(serve_step)/while/body/closed_call/moe.exchange/moe.expert/"
        "pallas_call:") == "moe.expert"
    assert scopes.stage_of(
        "jit(serve_step)/while/body/moe.exchange/mul") == "moe.exchange"
    assert scopes.stage_of("jit(serve_step)/attention/dot_general:") \
        == "attention"
    assert scopes.stage_of("jit(serve_step)/argmax") is None
    assert scopes.stage_of("") is None


def test_scoped_step_split_by_stage(scoped):
    s = scopes.stage_ms(scoped, SCOPED)
    assert s["executions"] == 3
    assert s["attention"] > 0 and s["moe.expert"] > 0
    parts = sum(s[k] for k in scopes.STAGES + ("unscoped",))
    assert parts == pytest.approx(s["total"], rel=1e-12)
    # the op total is the summed op time of the executions
    rx = xplane.Reduced(modules=scoped.modules).module_durations(SCOPED)
    assert len(rx) == 3 and 0 < s["total"] <= 1e3 * max(rx)


def test_pallas_call_inside_a_scope(scoped):
    kernels = [o for o in scoped.ops if "custom-call" in o[0]]
    assert kernels
    assert {scopes.stage_of(o[3]) for o in kernels} == {"moe.expert"}


def test_fusion_takes_the_scope_of_its_own_metadata(scoped):
    fusions = [o for o in scoped.ops if " fusion(" in o[0]]
    assert fusions
    for _, _, _, op_name, _ in fusions:
        assert op_name and scopes.stage_of(op_name) == \
            scopes.stage_of(op_name.split(":")[0])


def test_engine_host_time_per_step(scoped):
    steps = [d for n, _, d in scoped.host if n == scopes.FRONTEND_STEP]
    sync = [d for n, _, d in scoped.host if n == scopes.ENGINE_SYNC]
    assert len(steps) == len(sync) == 3
    host = scopes.host_step_ms(scoped)
    assert host == pytest.approx((sum(steps) - sum(sync)) * 1e-6 / 3)
    # the 2 ms of host work the recorder does in each step
    assert 2.0 <= host < 1e-6 * max(steps)


def test_idle_gaps_named_by_a_program_span(scoped):
    """The gaps the recorder's host work leaves on the device are named by
    ``repro.*`` spans, not ``idle``. The name is the host span over the
    gap's middle on the trace's own clocks, uncorrected: in this recording
    the device plane's timestamps run about 1.9 ms behind the host
    plane's (each execution ends on the device 1.9 ms before its
    ``repro.engine.sync`` returns, and starts 0.8 ms before its dispatch),
    so the 3.3 ms gap that 2 ms of bookkeeping and the next dispatch make
    is named by the ``repro.engine.sync`` span before them."""
    gaps = scoped.idle_gaps(2)
    assert [g[0] for g in gaps] == [scopes.ENGINE_SYNC] * 2
    assert all(g[1] >= 0.002 for g in gaps)
    ends = [s + d for n, s, d in scoped.host if n == scopes.ENGINE_SYNC]
    runs = sorted(s + d for n, s, d in scoped.modules
                  if n.startswith("jit_scoped_step("))
    lag = [e - r for e, r in zip(sorted(ends), runs)]
    assert all(1.5e6 < x < 2.5e6 for x in lag)


def test_no_program_spans_reads_nothing():
    assert scopes.host_step_ms(xplane.Reduced()) is None
    assert scopes.stage_ms(xplane.Reduced(), SCOPED) == {}


# -- the current readers' view of the small trace, pinned -------------------

@pytest.fixture(scope="module")
def small():
    return xplane.reduce(_load("tpu_small.xplane.pb"))


def test_small_trace_readings_unchanged(small):
    assert small.planes == 1
    assert small.busy_s == pytest.approx(5.1876e-05, rel=1e-6)
    assert [round(d * 1e9) for d in small.module_durations(
        r"jit_small_step")] == [18891, 15917, 15770]
    assert [round(d * 1e9) for d in small.op_durations(
        r"tpu_custom_call")] == [1318]
    assert [n for n, _ in small.top_ops(3)] == [
        "%fusion fusion", "%copy-done copy-done", "%_lambda_.1 custom-call"]
    assert [g[0] for g in small.idle_gaps(3)] == ["idle", "bench.step",
                                                   "bench.step"]
    assert small.idle_gaps(1)[0][1] == pytest.approx(918.897e-6, rel=1e-6)


@pytest.mark.parametrize("name", TRACES)
def test_same_events_as_the_harness_reduction(name):
    """``scopes.read`` gives every plane, line and event that
    ``jax.profiler.ProfileData`` gives, with the same names and times."""
    from jax.profiler import ProfileData

    path = _load(f"{name}.xplane.pb")
    mine = scopes.read(path)
    theirs = ProfileData.from_file(str(path)).planes
    assert [p.name for p in mine] == [p.name for p in theirs]
    for p, q in zip(mine, theirs):
        assert [n for n, _ in p.lines] == [ln.name for ln in q.lines]
        for (_, evs), ln in zip(p.lines, q.lines):
            assert [(n, float(s), float(d)) for n, s, d, _ in evs] == [
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in ln.events]


# -- the engine's spans through the harness, on the CPU -----------------------

def test_stages_of_an_open_loop_run_on_the_cpu(tmp_path):
    """The engine's ``repro.*`` spans reach the trace the harness records:
    every stage of the step, and the host time per step less the wait.
    (A CPU trace has no TPU plane, so no device stage is read.)"""
    import stages
    from helpers import TINY_MIX, tiny_tree

    spec, chip, root = tiny_tree(tmp_path, dict(TINY_MIX, rate_rps=30.0))
    out = stages.measure(spec, "tiny", 2**31 + 9, 3.0, chip_dir=chip,
                         root=root)
    assert out["result"]["correct"] is True
    per = out["span_ms_per_step"]
    names = {"repro.frontend.step", "repro.frontend.admin"} | {
        f"repro.engine.{s}" for s in ("control", "admit", "inputs", "sync",
                                      "bookkeep")}
    assert names <= set(per)
    assert 0 < out["engine_host_ms"] < per["repro.frontend.step"]
    assert out["engine_host_ms"] == pytest.approx(
        per["repro.frontend.step"] - per["repro.engine.sync"])
    assert out["stage_ms"] == {}
    assert out["steps_per_s"]["traced"] > 0


def test_current_readers_on_the_small_trace(small):
    """Every accepted per-layer reader, on the small trace as the harness
    reduces it: the idle share reads the same, the serve-step readers find
    no ``jit_serve_step`` there and read nothing."""
    import bench
    run = bench.Run(cell={}, model={}, deployment={}, peak={}, seconds=1.0,
                    t_process=0.0, trace=small, trace_window=(0.0, 1e-3))
    serve_step = ("step_device_ms", "step_mfu", "gmm_roofline",
                  "attention_ms", "moe_route_ms", "moe_exchange_ms",
                  "moe_expert_ms", "lm_head_ms")
    read = {n: bench.load_reader(bench.CHIP_DIR, n)(run)
            for n in ("device_idle_share",) + serve_step}
    assert read["device_idle_share"] == pytest.approx(94.8124, rel=1e-6)
    assert all(read[n] is None for n in serve_step)
