#!/usr/bin/env python3
"""One rate sweep of a cell on the chip, to place its fixed rate below the
knee: the highest offered rate the instance sustains without a backlog.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \\
        --seconds <s> --rates <r>,<r>,...

Builds and warms the instance once, then for each rate offers the cell's
mix at that rate (its preroll, then ``--seconds``) and prints one JSON line
with the cell's end-to-end metrics, the tokens per second delivered in the
window, and the backlog left when the window closed. Exits nonzero with no result off a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys

import bench
import loadgen


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    spec = bench.checkout_spec()
    cell = bench.CellRun(spec, args.workload, args.seed, args.seconds, False)
    bench.require_chips(cell.cell["chips"])
    cell.prepare()
    names = [m["name"] for m in bench.cell_metrics(spec, args.workload,
                                                    "end_to_end")]
    for rate in (float(r) for r in args.rates.split(",")):
        cell.mix = dict(cell.mix, rate_rps=rate)
        _, arrivals = loadgen.build(cell.mix, args.seed, args.seconds,
                                    cell.cfg.vocab_size)
        cell.fresh_run()
        cell.drive(arrivals)
        run = cell.run
        late = [r for r in run.requests
                if r.t_submit >= 0 and (not r.token_t
                                        or r.token_t[-1] >= run.t_window_end)]
        out = {"rate_rps": rate,
               "backlog_at_close": sum(1 for r in late
                                       if r.t_admit < 0
                                       or r.t_admit >= run.t_window_end),
               "failed": sum(r.outcome != "FINISHED"
                             for r in run.window_requests())}
        for n in names + ["output_tok_s"]:
            if n != "setup_s":
                out[n] = bench.load_reader(cell.chip_dir, n)(run)
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    sys.exit(main())
