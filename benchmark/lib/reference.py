"""The plain reference: what the deployment's guarantees make of a sent
stream, computed from the corpus alone (it imports nothing of the
program and takes nothing the program made).

Strict verification rejects every corrupted copy; dedup drops every
byte-for-byte re-send; each first-seen valid transfer executes once:
the payer loses amount + fee, the recipient gains amount.  `verify` and
`dedup` switch one guarantee off each — that is the CONTROL: the
reference put in the program's place with a guarantee broken, which the
comparison has to fail (lib/ledger.py).
"""

from __future__ import annotations

import numpy as np

from . import corpus as C


def outcome(corp: dict, n_sent: int, *, verify: bool = True,
            dedup: bool = True, balances: bool = True) -> dict:
    """What the first n_sent rows of the stream come to."""
    kind, src = corp["kind"][:n_sent], corp["src"][:n_sent]
    n_kind = [int((kind == k).sum()) for k in range(3)]
    executed = kind == C.KIND_UNIQUE
    if not verify:
        executed = executed | (kind == C.KIND_BAD)
    if not dedup:
        executed = executed | (kind == C.KIND_DUP)
    out = dict(
        landed=int(executed.sum()),
        rejected=n_kind[C.KIND_BAD] if verify else 0,
        dups=n_kind[C.KIND_DUP] if dedup else 0,
    )
    # the dedup tag of every txn that comes through: the first 8 bytes of
    # its signature as they were SENT (a corrupted copy has its own)
    out["tags"] = np.sort(np.ascontiguousarray(
        corp["send"][:n_sent][executed, C.SIG_OFF:C.SIG_OFF + 8]
    ).view("<u8").ravel())
    if balances:
        s = src[executed]
        bal = np.full(len(corp["pubs"]), C.START_LAMPORTS, np.int64)
        amt = corp["amount"][s].astype(np.int64)
        np.subtract.at(bal, corp["payer"][s], amt + C.FEE_PER_SIGNATURE)
        np.add.at(bal, corp["dest"][s], amt)
        out["balances"] = bal.astype(np.uint64)
    return out
