"""The plain reference: what the deployment's guarantees make of a sent
stream, computed from the corpus alone (it imports nothing of the
program and takes nothing the program made).

Strict verification rejects every corrupted txn; dedup drops every
byte-for-byte re-send; each first-seen valid transfer executes once:
the payer loses amount + 5000 per signature, the recipient gains amount.
`verify` and `dedup` switch one guarantee off each — that is the
CONTROL: the reference put in the program's place with a guarantee
broken, which the comparison has to fail (lib/ledger.py).
`verify="lane0"` is a verifier that checks signature 0 alone: it admits
a txn whose bad signature sits in another slot.
"""

from __future__ import annotations

import numpy as np

from . import corpus as C


def outcome(corp: dict, n_sent: int, *, verify: bool | str = True,
            dedup: bool = True, balances: bool = True) -> dict:
    """What the first n_sent rows of the stream come to.  `verify`: True
    (strict), False (off) or "lane0"."""
    kind, src = corp["kind"][:n_sent], corp["src"][:n_sent]
    n_kind = [int((kind == k).sum()) for k in range(3)]
    bad = kind == C.KIND_BAD
    if verify == "lane0":
        admitted = bad & (corp["bad_sig"][:n_sent] > 0)
    else:
        admitted = bad & (not verify)
    executed = (kind == C.KIND_UNIQUE) | admitted
    if not dedup:
        executed = executed | (kind == C.KIND_DUP)
    out = dict(
        landed=int(executed.sum()),
        rejected=n_kind[C.KIND_BAD] - int(admitted.sum()),
        dups=n_kind[C.KIND_DUP] if dedup else 0,
    )
    # the dedup tag of every txn that comes through: the first 8 bytes of
    # its first signature as they were SENT (a corrupted txn has its own)
    at = corp["off"][:n_sent][executed] + C.SIG_OFF
    out["tags"] = np.sort(np.ascontiguousarray(
        corp["buf"][at[:, None] + np.arange(8)]).view("<u8").ravel())
    if balances:
        s = src[executed]
        bal = np.full(len(corp["pubs"]), C.START_LAMPORTS, np.int64)
        amt = corp["amount"][s].astype(np.int64)
        np.subtract.at(bal, corp["payer"][s],
                       amt + C.FEE_PER_SIGNATURE * corp["nsig"][s])
        np.add.at(bal, corp["dest"][s], amt)
        out["balances"] = bal.astype(np.uint64)
    return out
