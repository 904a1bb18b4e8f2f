#!/usr/bin/env python3
"""Where one cell's time goes, by program stage, on the chip.

    python3 benchmarks/chip/stages.py --workload <cell> --seed <n> \\
        --seconds <s>

Runs the cell as ``bench.py --trace 0`` does, records the profiler over
the window's last ``bench.TRACE_S`` seconds into a directory of its own
(from a thread beside the harness's loop), reduces it with
``xplane.reduce`` and prints one JSON line:

- ``result``: the cell's own result (metrics, ``correct``, checks);
- ``stage_ms``: device time of one ``jit_serve_step`` execution by named
  scope, with the time in no scope and the op total (``scopes.stage_ms``);
- ``engine_host_ms``: mean ``repro.frontend.step`` span less its
  ``repro.engine.sync`` child (``scopes.host_step_ms``);
- ``span_ms_per_step``: each ``repro.*`` span's time per frontend step
  (``scopes.span_ms_per_step``);
- ``idle_gaps``: the longest device gaps, named by the innermost
  ``repro.*`` or ``bench.*`` span over them;
- ``steps_per_s``: the steps the harness finished per second in the
  traced slice (profiler on) and in as long a slice just before it
  (profiler off): the cost of tracing.

Exits nonzero with no result off a TPU, as ``bench.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import threading
import time

import bench
import scopes
import xplane


def steps_per_s(run, t0: float, t1: float) -> float:
    return sum(1 for s in run.steps if t0 <= s[1] < t1) / (t1 - t0)


def trace_window_end(run, directory: str, done: threading.Event,
                     window: list) -> None:
    """Record the profiler into ``directory`` over the last
    ``bench.TRACE_S`` seconds of ``run``'s window, appending the start and
    stop times to ``window``. Returns at once, once ``done`` is set."""
    import jax

    while not run.t_window:             # set as the harness opens it
        if done.wait(0.01):
            return
    end = run.t_window + run.seconds
    if done.wait(max(0.0, end - bench.TRACE_S - time.perf_counter())):
        return
    jax.profiler.start_trace(directory)
    window.append(time.perf_counter())
    done.wait(max(0.0, end - time.perf_counter()))
    window.append(time.perf_counter())
    jax.profiler.stop_trace()


def measure(spec: dict, workload: str, seed: int, seconds: float,
            **where) -> dict:
    """Run the cell with the profiler on over the window's last seconds
    and read the stages from its trace (``where``: ``bench.CellRun``'s
    ``chip_dir`` and ``root``)."""
    cell = bench.CellRun(spec, workload, seed, seconds, False, **where)
    done, window = threading.Event(), []
    with tempfile.TemporaryDirectory(prefix="stages-trace-") as directory:
        tracer = threading.Thread(target=trace_window_end,
                                  args=(cell.run, directory, done, window))
        tracer.start()
        try:
            result = cell.execute()
        finally:
            done.set()
            tracer.join()
        tr = xplane.reduce(xplane.find_xplane(directory))
    t0, t1 = window
    return {
        "workload": workload, "seed": seed,
        "result": {k: result[k] for k in ("correct", "attempted", "failed",
                                          "metrics", "checks")},
        "stage_ms": scopes.stage_ms(tr),
        "engine_host_ms": scopes.host_step_ms(tr),
        "span_ms_per_step": scopes.span_ms_per_step(tr),
        "idle_gaps": tr.idle_gaps(),
        "steps_per_s": {"traced": steps_per_s(cell.run, t0, t1),
                        "untraced": steps_per_s(cell.run, 2 * t0 - t1, t0)}}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = bench.checkout_spec()
    cell, _, _ = bench.load_cell(spec, args.workload, bench.CHIP_DIR,
                                 bench.ROOT)
    bench.require_chips(cell["chips"])
    print(json.dumps(measure(spec, args.workload, args.seed, args.seconds)),
          flush=True)


if __name__ == "__main__":
    sys.exit(main())
