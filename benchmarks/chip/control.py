#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, one process for many seeds.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \\
        --seeds <n>,<n>,...

For each seed it builds the cell's instance, offers the cell's own load for
``--seconds`` (long enough to finish the mix's longest requests and to
sample as many as a run does), samples the served requests as a run does,
frees the instance, and prints one JSON line: the gaps of the served
tokens against the float32 reference (the program's readings, among them
the number a run compares) and the gaps of the tokens the float8
reference puts first (the control's readings). The benchmark's own runs never run the
control. Exits nonzero with no result off a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import bench


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    spec = bench.checkout_spec()
    for seed in (int(s) for s in args.seeds.split(",")):
        run = bench.CellRun(spec, args.workload, seed, args.seconds, False)
        bench.require_chips(run.cell["chips"])
        run.measure()
        picked = run.sample()
        run.free()
        t0 = time.perf_counter()
        prog = run.gaps(picked, "float32")
        t1 = time.perf_counter()
        ctrl = run.gaps(picked, "fp8")
        print(json.dumps({
            "seed": seed, "program": bench.gap_stats(prog),
            "control": bench.gap_stats(ctrl),
            "checks": bench.gap_checks(prog, run.mix["check"]),
            "control_checks": bench.gap_checks(ctrl, run.mix["check"]),
            "sample": [[r.rid, len(r.prompt), len(r.tokens)]
                       for r in picked],
            "reference_s": t1 - t0, "control_s": time.perf_counter() - t1,
            "failed": sum(r.outcome != "FINISHED"
                          for r in run.run.window_requests())}),
            flush=True)


if __name__ == "__main__":
    sys.exit(main())
