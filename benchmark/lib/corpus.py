"""Seeded traffic: distinct signed system transfers, their re-sent and
corrupted copies, and where each sits in the stream.

The generator is the benchmark's own (the yardstick may not lean on the
program): the legacy transaction is laid out by hand from the wire
format (fd_txn.h: compact-u16 counts, 3-byte header, account keys,
blockhash, one instruction), and signed on the host by OpenSSL's Ed25519
through `cryptography`, in a pool of processes.  RFC 8032 signatures are
deterministic, so the same seed gives the same bytes.

Every seed yields the same SET of sizes: n_unique transfers of 215 bytes,
n_unique // dup_every byte-for-byte re-sends and n_unique // bad_every
copies with one bit of the signature's first 8 bytes flipped (the dedup
tag is those 8 bytes, so a corrupted copy has a tag of its own and is
VERIFY's to reject, never dedup's).  The seed moves keys, the order and
which txns are copied, not how much work there is.
"""

from __future__ import annotations

import concurrent.futures
import multiprocessing

import numpy as np

#: the system program's id is 32 zero bytes
SYSTEM_PROGRAM = bytes(32)
#: lamports every account starts with
START_LAMPORTS = 1 << 40
#: lamports the fee payer is charged per signature (protocol constant)
FEE_PER_SIGNATURE = 5000

#: offsets inside the one 215-byte shape every transfer has
SIG_OFF, MSG_OFF = 1, 65
PAYER_OFF, DEST_OFF, PROG_OFF = 69, 101, 133
BLOCKHASH_OFF, AMOUNT_OFF, TXN_SZ = 165, 207, 215

KIND_UNIQUE, KIND_DUP, KIND_BAD = 0, 1, 2


def template(blockhash: bytes) -> np.ndarray:
    """One unsigned transfer with zeroed keys and amount."""
    body = (
        bytes([1]) + bytes(64)            # 1 signature
        + bytes([1, 0, 1])                # 1 signer, 0 ro-signed, 1 ro-unsigned
        + bytes([3]) + bytes(64) + SYSTEM_PROGRAM  # payer, dest, program
        + blockhash
        + bytes([1])                      # 1 instruction
        + bytes([2, 2, 0, 1, 12])         # program idx, accounts [0, 1], 12 data bytes
        + (2).to_bytes(4, "little") + bytes(8)     # SystemInstruction::Transfer
    )
    assert len(body) == TXN_SZ
    return np.frombuffer(body, np.uint8)


def _sign_chunk(secrets: np.ndarray, payer: np.ndarray,
                msgs: np.ndarray) -> np.ndarray:
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )

    keys: dict[int, Ed25519PrivateKey] = {}
    out = np.empty((len(msgs), 64), np.uint8)
    for i in range(len(msgs)):
        p = int(payer[i])
        k = keys.get(p)
        if k is None:
            k = keys[p] = Ed25519PrivateKey.from_private_bytes(
                secrets[p].tobytes())
        out[i] = np.frombuffer(k.sign(msgs[i].tobytes()), np.uint8)
    return out


def _public_keys(secrets: np.ndarray) -> np.ndarray:
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )

    return np.stack([
        np.frombuffer(
            Ed25519PrivateKey.from_private_bytes(s.tobytes())
            .public_key().public_bytes_raw(), np.uint8)
        for s in secrets
    ])


def make_keys(n_accounts: int, seed: int):
    """-> (rng, secrets, blockhash, pubs): what a deployment needs before
    it boots (the accounts to fund); `make_corpus` takes it from here."""
    rng = np.random.default_rng(seed)
    secrets = rng.integers(0, 256, (n_accounts, 32), np.uint8)
    blockhash = rng.integers(0, 256, 32, np.uint8).tobytes()
    return rng, secrets, blockhash, _public_keys(secrets)


def make_corpus(n_unique: int, n_accounts: int, dup_every: int,
                bad_every: int, seed: int, workers: int = 8,
                keys=None) -> dict:
    """-> dict(send (n, 215) u8 rows in stream order, kind (n,) u8,
    src (n,) index of the unique txn each row is or copies, pubs
    (n_accounts, 32), payer/dest (n_unique,) account indices, amount
    (n_unique,) lamports).  `keys` = make_keys(n_accounts, seed), when
    the caller already made them."""
    rng, secrets, blockhash, pubs = keys or make_keys(n_accounts, seed)
    idx = np.arange(n_unique)
    payer = idx % n_accounts
    # never the payer itself for an even account count: 6i + 3 is odd
    dest = (7 * idx + 3) % n_accounts
    amount = (idx + 1).astype(np.uint64)  # distinct, so txns are distinct
    rows = np.tile(template(blockhash), (n_unique, 1))
    rows[:, PAYER_OFF:PAYER_OFF + 32] = pubs[payer]
    rows[:, DEST_OFF:DEST_OFF + 32] = pubs[dest]
    rows[:, AMOUNT_OFF:AMOUNT_OFF + 8] = (
        amount[:, None] >> (8 * np.arange(8, dtype=np.uint64))
    ).astype(np.uint8)

    workers = max(1, min(workers, n_unique // 2048 + 1))
    cuts = np.linspace(0, n_unique, workers + 1).astype(int)
    if workers == 1:
        sigs = [_sign_chunk(secrets, payer, rows[:, MSG_OFF:])]
    else:
        with concurrent.futures.ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn")
        ) as pool:
            sigs = list(pool.map(
                _sign_chunk, [secrets] * workers,
                [payer[a:b] for a, b in zip(cuts, cuts[1:])],
                [rows[a:b, MSG_OFF:] for a, b in zip(cuts, cuts[1:])],
            ))
    rows[:, SIG_OFF:SIG_OFF + 64] = np.concatenate(sigs)

    n_dup, n_bad = n_unique // dup_every, n_unique // bad_every
    dup_src = rng.choice(n_unique, n_dup, replace=False)
    bad_src = rng.choice(n_unique, n_bad, replace=False)
    extra = np.concatenate([dup_src, bad_src])
    src = np.concatenate([idx, extra])
    kind = np.concatenate([
        np.full(n_unique, KIND_UNIQUE, np.uint8),
        np.full(n_dup, KIND_DUP, np.uint8),
        np.full(n_bad, KIND_BAD, np.uint8),
    ])
    # a copy lands at a seeded place strictly after its original
    key = np.concatenate([
        idx.astype(np.float64),
        extra + 0.5 + np.floor(rng.random(len(extra)) * (n_unique - extra)),
    ])
    order = np.argsort(key, kind="stable")
    send, kind, src = rows[src[order]], kind[order], src[order]
    bad = np.flatnonzero(kind == KIND_BAD)
    send[bad, SIG_OFF + rng.integers(0, 8, len(bad))] ^= (
        1 << rng.integers(0, 8, len(bad))
    ).astype(np.uint8)
    return dict(send=send, kind=kind, src=src, pubs=pubs, payer=payer,
                dest=dest, amount=amount)
