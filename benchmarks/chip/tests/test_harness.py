"""CPU rehearsal: the harness loop end to end on a tiny Mixtral-shaped
model, and the command's refusals."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import bench
from helpers import CHIP, ROOT, TINY_CLOSED, TINY_MIX, tiny_tree

SEED = 2**31 + 5          # larger than 32 signed bits hold


def _run(tmp_path, mix, trace=False, seconds=2.0, **kw):
    spec, chip, root = tiny_tree(tmp_path, mix, **kw)
    cell = bench.CellRun(spec, "tiny", SEED, seconds, trace, chip_dir=chip,
                         root=root)
    return cell, cell.execute()


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    mix = dict(TINY_MIX, prefix={"groups": 2, "len": 64})
    return _run(tmp_path_factory.mktemp("traced"), mix, trace=True)


def test_last_line_has_the_result_keys(tmp_path):
    cell, out = _run(tmp_path, TINY_MIX)
    assert list(out)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert out["attempted"] == len(cell.run.window_requests()) > 0
    assert out["failed"] == 0
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    # no fault in this mix: the fault stall reader finds nothing to read
    assert set(out["metrics"]) == {"ttft_p95_ms", "itl_p95_ms",
                                   "output_tok_s", "setup_s"}
    for m in out["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(out["checks"]["mismatch_pct"]) == {"value", "limit"}
    assert cell.run.compiles_in_window == 0
    json.dumps(out)


def test_traced_run_reports_per_layer_metrics(traced):
    cell, out = traced
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s"} <= set(out["device"])
    # the CPU has no device plane: device metrics are left out, host ones
    # and counters are read
    assert {"gen_lag_p95_ms", "queue_wait_p95_ms",
            "prefix_skip_share"} <= set(out["metrics"])
    assert not {"step_device_ms", "step_mfu", "gmm_roofline",
                "device_idle_share"} & set(out["metrics"])
    assert out["metrics"]["prefix_skip_share"]["value"] > 50


def test_prefix_cell_samples_a_borrower(traced):
    cell, _ = traced
    assert any(r.prefix_skip > 0 for r in cell.picked)


def test_fault_cell_records_commits_stalls_and_rejoins(tmp_path):
    # the rejoin comes some steps after the commit, so on a loaded CPU the
    # faults stand 3 s apart
    mix = dict(TINY_MIX, rate_rps=30.0,
               faults={"kind": "sigkill", "first_s": 0.5, "every_s": 3.0})
    cell, out = _run(tmp_path, mix, seconds=4.0)
    faults = cell.run.faults
    assert len(faults) == 2 and all(f.t_commit > 0 for f in faults)
    # the first rank rejoined before the second fault; the second fault
    # comes 0.5 s before the window closes, too late to rejoin in it
    assert faults[0].t_commit < faults[0].t_rejoin < faults[1].t_inject
    assert sum(len(f.suspended) for f in faults) > 0
    assert cell.run.compiles_in_window == 0
    assert any(f.stalls_s for f in faults)
    assert out["metrics"]["fault_stall_p95_ms"]["value"] > 0


@pytest.mark.parametrize("trace", [False, True], ids=["plain", "traced"])
def test_closed_batch_keeps_every_row_busy(tmp_path, trace):
    cell, out = _run(tmp_path, TINY_CLOSED, trace=trace,
                     end_to_end=("output_tok_s", "setup_s"))
    run = cell.run
    batch = run.deployment["max_batch"]
    assert run.steps and all(fed == batch for _, _, fed, _ in run.steps)
    assert run.compiles_in_window == 0
    done = run.window_requests()
    assert out["attempted"] == len(done) > 0 and out["failed"] == 0
    assert all(len(r.tokens) == r.max_new for r in done)
    assert run.t_window_end - run.t_window >= 2.0
    if trace:
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        # the open loop's host readers find nothing in a closed batch
        assert not {"gen_lag_p95_ms", "queue_wait_p95_ms"} & set(
            out["metrics"])
    else:
        assert set(out["metrics"]) == {"output_tok_s", "setup_s"}
        # the window's tokens over its length, every token once
        n = sum(1 for r in run.requests for t in r.token_t
                if run.t_window < t <= run.t_window_end)
        assert out["metrics"]["output_tok_s"]["value"] == pytest.approx(
            n / (run.t_window_end - run.t_window))


def _command(cwd, *, env=None):
    return subprocess.run(
        [sys.executable, "benchmarks/chip/bench.py", "--workload",
         "m22b-batch", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


def test_command_refuses_the_cpu():
    p = _command(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_command_refuses_a_tree_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
