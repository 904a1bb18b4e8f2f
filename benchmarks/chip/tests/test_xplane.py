"""The trace reduction, on hand-made intervals and on traces recorded on
a TPU v5e: the small one by ``record_trace.py`` (three executions of
``jit_small_step`` and one Pallas kernel call, between ``bench.*`` host
spans), the scoped one by ``record_scoped_trace.py`` and the serve step's
by ``record_serve_trace.py`` (three executions of ``jit_serve_step`` of a
small Mixtral-shaped model, three ``gmm`` calls a layer). The reduction
through ``scopes.read`` is held to the one through
``jax.profiler.ProfileData`` it replaced, reader by reader."""
import pathlib

import pytest

import bench
import scopes
import xplane
from record_serve_trace import CONF, POSITION

DATA = pathlib.Path(__file__).resolve().parent / "data"
TRACES = ["tpu_small", "tpu_scoped", "tpu_serve"]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
#: every reader of a device trace that ``BENCHMARK.json`` names
DEVICE_READERS = ("step_device_ms", "step_mfu", "gmm_roofline",
                  "device_idle_share")
SCOPED_READERS = {"attention_ms": "attention", "moe_route_ms": "moe.route",
                  "moe_exchange_ms": "moe.exchange",
                  "moe_expert_ms": "moe.expert", "lm_head_ms": "lm_head"}


def _profiledata_reduce(path, host_prefix: str = "bench.") -> xplane.Reduced:
    """The harness's reduction before it read the file through
    ``scopes.read``, as it stood: op and module events and ``bench.*``
    host spans through ``jax.profiler.ProfileData``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    r = xplane.Reduced()
    busy, spans = [], []
    for plane in pd.planes:
        lines = {ln.name: [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                           for ev in ln.events] for ln in plane.lines}
        if xplane.DEVICE_PLANE.fullmatch(plane.name):
            r.planes += 1
            r.line_names[plane.name] = sorted(lines)
            ops = lines.get(xplane.OPS, [])
            r.ops += ops
            r.modules += lines.get(xplane.MODULES, [])
            if ops:
                busy.append(xplane.busy_ns((s, s + d) for _, s, d in ops))
                spans.append(max(s + d for _, s, d in ops)
                             - min(s for _, s, _ in ops))
        elif plane.name == xplane.HOST_PLANE:
            for evs in lines.values():
                r.host += [e for e in evs if e[0].startswith(host_prefix)]
    if r.planes:
        r.busy_s = sum(busy) / r.planes * 1e-9
        r.span_s = (max(spans) if spans else 0.0) * 1e-9
    return r


def _run(trace) -> bench.Run:
    """A run over ``trace`` of the recorded serve step's model: three steps
    of every row at ``POSITION`` in a 1 ms window."""
    B = CONF["deployment"]["max_batch"]
    return bench.Run(cell={}, model=CONF["model"],
                     deployment=CONF["deployment"], peak=PEAK, seconds=1.0,
                     t_process=0.0, trace=trace, trace_window=(0.0, 1e-3),
                     steps=[(1e-4, 2e-4, B, B * (POSITION + 1))] * 3)


def test_busy_is_the_union_of_intervals():
    assert xplane.busy_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert xplane.busy_ns([(0, 10), (10, 12)]) == 12
    assert xplane.busy_ns([]) == 0


def test_idle_gaps_named_by_the_host_span_over_them():
    r = xplane.Reduced(
        ops=[("a", 0, 10), ("b", 40, 10), ("c", 55, 5)],
        host=[("bench.step", 0, 100), ("bench.stamp", 12, 25)])
    gaps = r.idle_gaps()
    assert [g[0] for g in gaps] == ["bench.stamp", "bench.step"]
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 5e-9])


def test_top_ops_sum_by_name():
    r = xplane.Reduced(ops=[("x", 0, 5), ("y", 5, 20), ("x", 30, 30)])
    assert r.top_ops(1) == [["x", 35e-9]]


def _load(name):
    path = DATA / f"{name}.xplane.pb"
    if not path.is_file():
        pytest.fail(f"missing {path}: record it with the recorder named in "
                    f"the module docstring")
    return path


@pytest.fixture(scope="module")
def recorded():
    return xplane.reduce(_load("tpu_small"))


def test_recorded_trace_has_one_tpu_plane(recorded):
    assert recorded.planes == 1
    lines = next(iter(recorded.line_names.values()))
    assert xplane.MODULES in lines and xplane.OPS in lines


def test_recorded_trace_module_executions(recorded):
    d = recorded.module_durations(r"jit_small_step")
    assert len(d) == 3 and all(0 < x < 0.01 for x in d)


def test_recorded_trace_kernel_call(recorded):
    # a Pallas kernel appears only as a tpu_custom_call
    assert len(recorded.op_durations(r"tpu_custom_call")) == 1
    names = [n for n, _ in recorded.top_ops(10)]
    assert "%_lambda_.1 custom-call" in names
    assert "%fusion fusion" in names


def test_short_names():
    assert xplane.short_name(
        "%closed_call.16 = bf16[256,6144]{1,0} custom-call(s32[17]{0} %a, "
        "bf16[16,16384,6144]{2,1,0:T(8,128)(2,1)} %b), custom_call_target="
        "\"tpu_custom_call\"") == "%closed_call.16 custom-call bf16[16,16384,6144]"
    assert xplane.short_name(
        "%fusion.115 = (f32[128,8,6]{2,1,0:T(8,128)S(1)}, f32[1]{0}) "
        "fusion(f32[128,768]{1,0} %x), kind=kOutput") == "%fusion.115 fusion"


def test_recorded_trace_busy_within_span(recorded):
    assert 0 < recorded.busy_s <= recorded.span_s
    assert any(n == "bench.step" for n, _, _ in recorded.host)


@pytest.mark.parametrize("name", TRACES)
def test_reduction_equals_the_profiledata_one(name):
    """The same busy time, span, lines, module and op events, ``bench.*``
    spans and busiest ops as the reduction through ProfileData, to the
    last bit."""
    path = _load(name)
    new, old = xplane.reduce(path), _profiledata_reduce(path)
    assert (new.planes, new.busy_s, new.span_s, new.line_names) == (
        old.planes, old.busy_s, old.span_s, old.line_names)
    assert new.modules == old.modules
    assert [o[:3] for o in new.ops] == old.ops
    assert [h for h in new.host if h[0].startswith("bench.")] == old.host
    assert new.module_durations(".") == old.module_durations(".")
    assert new.op_durations(".") == old.op_durations(".")
    assert new.top_ops(10) == old.top_ops(10)
    assert xplane.reduce(path, "bench.").idle_gaps(10) == old.idle_gaps(10)


@pytest.mark.parametrize("name", TRACES)
def test_device_readers_equal_the_profiledata_ones(name):
    path = _load(name)
    new, old = _run(xplane.reduce(path)), _run(_profiledata_reduce(path))
    for reader in DEVICE_READERS:
        read = bench.load_reader(bench.CHIP_DIR, reader)
        assert read(new) == read(old), reader
    if name == "tpu_serve":
        # the serve step's readers find their module and kernel there
        assert all(bench.load_reader(bench.CHIP_DIR, r)(new) is not None
                   for r in DEVICE_READERS)


def test_serve_trace_scoped_readers():
    """Each of the five stage readers is its stage's ms per execution of
    ``jit_serve_step``; with the unscoped rest they sum to the op total."""
    trace = xplane.reduce(_load("tpu_serve"))
    split = scopes.stage_ms(trace)
    assert split["executions"] == 3
    run = _run(trace)
    read = {stage: bench.load_reader(bench.CHIP_DIR, name)(run)
            for name, stage in SCOPED_READERS.items()}
    assert read == {stage: split[stage] for stage in read}
    assert all(v > 0 for v in read.values())
    assert sum(read.values()) + split["unscoped"] == pytest.approx(
        split["total"], rel=1e-12)
    # the layers' loop is an op event over its body's ops: left out, the
    # ops add up to less than the step's device time
    assert any(xplane.opcode(o[0]) == "while" for o in trace.ops)
    step_ms = bench.load_reader(bench.CHIP_DIR, "step_device_ms")(run)
    assert 0.9 * step_ms < split["total"] <= step_ms


def test_scoped_trace_readers_equal_stage_ms(monkeypatch):
    """On the scoped trace, whose step is ``jit_scoped_step``: a stage the
    step has reads its ms, one it lacks reads nothing."""
    monkeypatch.setattr(scopes, "SERVE_STEP", r"^jit_scoped_step\(")
    trace = xplane.reduce(_load("tpu_scoped"))
    split = scopes.stage_ms(trace, scopes.SERVE_STEP)
    run = _run(trace)
    for name, stage in SCOPED_READERS.items():
        value = bench.load_reader(bench.CHIP_DIR, name)(run)
        assert value == (split[stage] or None), name
    assert split["moe.expert"] > 0 and split["moe.route"] == 0
