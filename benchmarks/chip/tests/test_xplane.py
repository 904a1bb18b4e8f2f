"""The trace reduction, on hand-made intervals and on a small trace
recorded on a TPU v5e by ``record_trace.py`` (three executions of
``jit_small_step`` and one Pallas kernel call, between ``bench.*`` host
spans)."""
import pathlib

import pytest

import xplane

DATA = pathlib.Path(__file__).resolve().parent / "data"


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


@pytest.fixture(scope="module")
def recorded():
    path = DATA / "tpu_small.xplane.pb"
    if not path.is_file():
        pytest.fail(f"missing {path}: record it with record_trace.py")
    return xplane.reduce(path)


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
