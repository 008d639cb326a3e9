#!/usr/bin/env python3
"""The control of `correct`: the plain reference put in the program's
place with ONE stated guarantee broken, at a cell's own size.

    python3 benchmark/control.py --workload leader.paced --seconds 20 --seeds 1 2 3

The system states no precision, so the control breaks a guarantee the
configuration states: `verify` off admits the corrupted txns, `dedup`
off executes the byte-for-byte re-sends a second time, and where the
traffic has txns of more than one signer, `verify=lane0` checks each
txn's signature 0 alone (with one signer it IS the strict verifier, and
is no control).  For each seed it prints every number compared beside
its limit; the comparison has to come out NOT correct for each.  Needs
no chip (every comparison is an exact count) and boots nothing; exits 0
only if every control failed.
"""

from __future__ import annotations

import argparse
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import run as RUN  # noqa: E402
from benchmark.lib import corpus as C  # noqa: E402
from benchmark.lib import ledger, reference  # noqa: E402
from benchmark.lib.deploy import load_config  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = RUN.load_cell(HERE, args.workload, False)
    conf = load_config(HERE, cell["config"])
    span = cell["warmup_s"] + args.seconds + cell.get("margin_s", 0)
    n_unique = math.ceil(cell.get("rate_tps", cell.get("corpus_tps")) * span)
    weights = C.signer_weights(conf)
    all_failed = True
    for seed in args.seeds:
        corp = C.make_corpus(n_unique, conf["accounts"], cell["dup_every"],
                             cell["bad_every"], seed, weights=weights)
        n = len(corp["kind"])
        bal = bool(conf.get("balances"))
        exp = reference.outcome(corp, n, balances=bal)
        controls = [("verify", False), ("dedup", False)]
        if (corp["nsig"] > 1).any():
            controls.append(("verify", "lane0"))
        for broken, how in controls:
            out = reference.outcome(corp, n, balances=bal, **{broken: how})
            if not conf.get("siglog_tile"):
                out.pop("tags"), exp.pop("tags", None)
            checks = ledger.compare(ledger.sound_observation(out, n), exp)
            ok = ledger.correct(checks)
            all_failed &= not ok
            print(f"control {args.workload} seed={seed} rows={n} "
                  f"{broken}={how or 'off'} correct={ok} "
                  + " ".join(f"{k}={v}/{lim}" for k, v, lim in checks if v),
                  flush=True)
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
