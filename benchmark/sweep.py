#!/usr/bin/env python3
"""A sweep of ONE number of a workload file: how a cell's fixed rate (or
window) was found.  Never a cell result: every line it prints starts
with `SWEEP, not a cell result:` and it exits 4.

    python3 benchmark/sweep.py --workload leader.paced --key rate_tps \\
        --values 5000 7000 9000 12000 14000 15000 --seed 1 --seconds 8

Each value is one run of `run.run_cell` with that number laid over the
cell's file, in a process of its own (a run is a new process, as for the
one command).  The knee of an open-loop cell is the highest rate whose
landed rate stays within 1% of the offered one with a lag that does not
rise through the window (the `lag` line's two halves); PERF.md section 6
holds the sweep the committed rate came from.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

EXIT_SWEPT = 4


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--key", required=True)
    ap.add_argument("--values", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if len(args.values) > 1:  # one process a value
        for v in args.values:
            subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--workload",
                 args.workload, "--key", args.key, "--values", repr(v),
                 "--seed", str(args.seed), "--seconds", str(args.seconds)]
                + (["--rehearse"] if args.rehearse else []))
        return EXIT_SWEPT
    from benchmark import run as RUN

    v = args.values[0]
    try:
        res = RUN.run_cell(
            HERE, args.workload, args.seed, args.seconds, False,
            rehearse=args.rehearse, require_chip=not args.rehearse,
            cell_overrides={args.key: int(v) if v == int(v) else v})
    except RUN.Malformed as e:
        print(f"SWEEP, not a cell result: {args.key}={v}: NO RESULT: {e}",
              flush=True)
        return EXIT_SWEPT
    print(f"SWEEP, not a cell result: {args.key}={v}: " + json.dumps(res),
          flush=True)
    return EXIT_SWEPT


if __name__ == "__main__":
    sys.exit(main())
