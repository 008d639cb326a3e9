"""Load-generation corpus (benchg analog).

Reference model: src/app/fddev/bench.c:62-90 — benchg tiles sign a
stream of distinct transfer transactions for benchs to send.  This
build's analog: `make_transfer_pool` mass-signs a distinct-txn corpus
with the TPU batch signer (ops/ed25519/sign.py).

Distinctness matters: every txn has a unique (dest, amount) so dedup
cannot collapse the load and every landed count is a real execution.
"""

from __future__ import annotations

import numpy as np

from firedancer_tpu.ballet import txn as T
from firedancer_tpu.flamenco.accounts import SYSTEM_PROGRAM_ID
from firedancer_tpu.ops.ed25519 import sign as dsign


def make_transfer_pool(
    n_txns: int,
    *,
    n_signers: int = 1024,
    seed: int = 0,
    amount_base: int = 1,
    closed_accounts: bool = False,
) -> tuple[np.ndarray, list[bytes]]:
    """n distinct signed system transfers -> ((n, sz) u8 payload rows,
    payer pubkeys to pre-fund).

    One template txn is built/parsed once; per-txn dest+amount are
    patched into the template body and the signatures come from the
    device batch signer in ONE execution across all keys.

    n_signers matters: pack's conflict-aware scheduler serializes txns
    sharing a writable payer account, so payer diversity IS the
    schedulable parallelism (the reference's benchg funds a whole
    account set for the same reason).

    closed_accounts: every dest is another signer's account (txn i
    pays signer (7i+3) mod n_signers, never itself for even n_signers)
    instead of a fresh random key, so the corpus touches exactly
    n_signers accounts.  That is the account set the bank's shared
    table can hold in full, which is what lets a caller read every
    final balance back out of it under either runtime (chip_smoke.py).
    Txns stay distinct: the amount is the txn's index.
    """
    rng = np.random.default_rng(seed)
    secrets = [
        rng.integers(0, 256, 32, np.uint8).tobytes() for _ in range(n_signers)
    ]
    # one device batch instead of n_signers host scalar muls
    pubs = dsign.public_keys(secrets)
    blockhash = rng.integers(0, 256, 32, np.uint8).tobytes()

    # template: transfer(payer -> dest, amount); offsets recovered once
    dest0 = bytes(range(32))
    data0 = (2).to_bytes(4, "little") + (0).to_bytes(8, "little")
    body0 = T.build(
        [bytes(64)], [pubs[0], dest0, SYSTEM_PROGRAM_ID], blockhash,
        [(2, [0, 1], data0)], readonly_unsigned_cnt=1,
    )
    desc0 = T.parse(body0)
    assert desc0 is not None
    payer_off = desc0.acct_addr_off
    dest_off = payer_off + 32
    amt_off = desc0.instr[0].data_off + 4
    sz = len(body0)

    rows = np.zeros((n_txns, sz), np.uint8)
    rows[:] = np.frombuffer(body0, np.uint8)
    # unique dest per txn; amount = index (both inside the signed message)
    dests = rng.integers(0, 256, (n_txns, 32), np.uint8)
    rows[:, dest_off:dest_off + 32] = dests
    pub_rows = np.stack([np.frombuffer(p, np.uint8) for p in pubs])
    if closed_accounts:
        rows[:, dest_off:dest_off + 32] = pub_rows[
            (7 * np.arange(n_txns) + 3) % n_signers
        ]
    amts = (np.arange(n_txns, dtype=np.uint64) + amount_base)
    rows[:, amt_off:amt_off + 8] = (
        amts[:, None] >> (8 * np.arange(8, dtype=np.uint64))
    ).astype(np.uint8)

    msg_off = 1 + 64 * desc0.signature_cnt
    rows[:, payer_off:payer_off + 32] = pub_rows[
        np.arange(n_txns) % n_signers
    ]
    pairs = [
        (secrets[i % n_signers], rows[i, msg_off:].tobytes())
        for i in range(n_txns)
    ]
    sigs = dsign.sign_many(pairs, pubs=dict(zip(secrets, pubs)))
    for i, sig in enumerate(sigs):
        rows[i, 1:65] = np.frombuffer(sig, np.uint8)
    return rows, pubs
