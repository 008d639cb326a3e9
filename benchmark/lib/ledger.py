"""What decides `correct`: every number the run produced beside what the
plain reference says it must be, each with a limit of its own.

All comparisons are exact (limit 0): counts, balances, loss counters.
The guarantees are part of the result — a txn executed twice, a
corrupted txn admitted, a batch verified on the host instead of the
device, a compile inside the window, a txn lost without a counter that
names it: each is a different result, not a slower or faster one.
"""

from __future__ import annotations

import numpy as np


def compare(observed: dict, expected: dict) -> list[tuple[str, float, float]]:
    """-> [(name, value, limit)]; a check holds when value <= limit.

    observed/expected: landed, rejected, dups (counts); optionally
    balances and tags (arrays).  observed also carries `sent`, `received` and a
    dict `losses` of named drop counters, plus the device-path gauges
    (fallback_batches, device_errors, compiles_in_window, failed_tiles,
    parent_backend).  The value of a count check is the distance from
    the reference's count."""
    checks = [
        (f"{k}_off", abs(observed[k] - expected[k]), 0)
        for k in ("landed", "rejected", "dups")
    ]
    # every sent txn is landed or dropped under a named counter
    unexplained = observed["sent"] - (
        observed["landed"] + observed["rejected"] + observed["dups"]
        + sum(observed["losses"].values()))
    checks.append(("ledger_open", abs(unexplained), 0))
    checks.append(("wire_short", abs(observed["sent"] - observed["received"]), 0))
    checks += [(f"loss_{k}", v, 0) for k, v in observed["losses"].items()]
    if "balances" in expected:
        diff = int((observed["balances"] != expected["balances"]).sum())
        checks.append(("balances_differ", diff, 0))
    if "tags" in observed:
        # which txns came through, one by one: how often each tag was sunk
        # against how often the reference says it must be (0 or 1), summed
        got, want = observed["tags"], expected["tags"]
        _, inv = np.unique(np.concatenate([got, want]), return_inverse=True)
        n = int(inv.max()) + 1 if len(inv) else 0
        wrong = np.abs(np.bincount(inv[:len(got)], minlength=n)
                       - np.bincount(inv[len(got):], minlength=n)).sum()
        checks.append(("tags_differ", int(wrong), 0))
    for k in ("fallback_batches", "device_errors", "compiles_in_window",
              "failed_tiles", "parent_backend"):
        checks.append((k, observed[k], 0))
    # the device did the verifying: at least one device batch landed
    checks.append(("device_batches_missing",
                   int(observed["device_batches"] <= 0), 0))
    return checks


def sound_observation(outcome: dict, n_sent: int) -> dict:
    """An outcome (lib/reference.py) dressed as a run that lost nothing
    and kept to the device path: what the control and the tests hand to
    `compare` in the program's place."""
    return dict(outcome, sent=n_sent, received=n_sent, losses={},
                fallback_batches=0, device_errors=0, device_batches=1,
                compiles_in_window=0, failed_tiles=0, parent_backend=0)


def correct(checks) -> bool:
    return all(value <= limit for _, value, limit in checks)
