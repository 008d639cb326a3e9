"""Seeded traffic: distinct signed system transfers, their re-sent and
corrupted copies, and where each sits in the stream.

The generator is the benchmark's own (the yardstick may not lean on the
program): the legacy transaction is laid out by hand from the wire
format (fd_txn.h: compact-u16 counts, 3-byte header, account keys,
blockhash, one instruction), and signed on the host by OpenSSL's Ed25519
through `cryptography`, in a pool of processes.  RFC 8032 signatures are
deterministic, so the same seed gives the same bytes.

A configuration states its transaction shape (`txn_shape`, see
`signer_weights`); without one every transfer has one signer.

One signer (no `txn_shape`): every seed yields the same SET of sizes:
n_unique transfers of 215 bytes, n_unique // dup_every byte-for-byte
re-sends and n_unique // bad_every copies with one bit of the
signature's first 8 bytes flipped (the dedup tag is those 8 bytes, so a
corrupted copy has a tag of its own and is VERIFY's to reject, never
dedup's).

N signers (`txn_shape`): n_unique transfers and n_unique // bad_every
bad txns, each signed by N = 1..MAX_SIGNERS accounts (119 + 96 N bytes),
N counted out of the weights (`apportion`) and dealt in a seeded order.
A bad txn is a transfer of its own (its own amount, so its own tag) with
one bit flipped in signature j, j uniform over its N slots: only verify
can drop it, and a verifier that checks fewer than all N lanes admits it.
Re-sends are byte-for-byte copies, as above, of as many txns of each N
as the weights give.

Either way the seed moves keys, the order and which txns are copied or
corrupted, not how much work there is.  Rows are stored ragged: one flat
byte buffer `buf` and `off`, row i being buf[off[i]:off[i + 1]], so the
corpus's memory follows the bytes sent.
"""

from __future__ import annotations

import concurrent.futures
import multiprocessing
import time

import numpy as np

#: the system program's id is 32 zero bytes
SYSTEM_PROGRAM = bytes(32)
#: lamports every account starts with
START_LAMPORTS = 1 << 40
#: lamports the fee payer is charged per signature (protocol constant)
FEE_PER_SIGNATURE = 5000
#: the most signers a transfer of MTU size (1,232 bytes) carries:
#: 119 + 96 * 11 = 1,175 (fd_txn.h: FD_TXN_ACTUAL_SIG_MAX is 12, and a
#: twelfth signer would take the transfer to 1,271 bytes)
MAX_SIGNERS = 11

#: offsets inside the one-signer, 215-byte transfer
SIG_OFF, MSG_OFF = 1, 65
PAYER_OFF, DEST_OFF, PROG_OFF = 69, 101, 133
BLOCKHASH_OFF, AMOUNT_OFF, TXN_SZ = 165, 207, 215

KIND_UNIQUE, KIND_DUP, KIND_BAD = 0, 1, 2


def txn_size(n_signers):
    """Bytes of an N-signer transfer: 119 + 96 N."""
    return 119 + 96 * n_signers


def template(blockhash: bytes, n_signers: int = 1) -> np.ndarray:
    """One unsigned N-signer transfer with zeroed keys and amount.  Keys:
    the payer (signer 0, writable), N - 1 further signers (read-only
    signed), the destination (writable), the system program."""
    n = n_signers
    body = (
        bytes([n]) + bytes(64 * n)        # n signatures
        + bytes([n, n - 1, 1])            # n signers, n-1 ro-signed, 1 ro-unsigned
        + bytes([n + 2]) + bytes(32 * (n + 1)) + SYSTEM_PROGRAM
        + blockhash
        + bytes([1])                      # 1 instruction
        + bytes([n + 1, 2, 0, n, 12])     # program idx, accounts [0, n], 12 data bytes
        + (2).to_bytes(4, "little") + bytes(8)     # SystemInstruction::Transfer
    )
    assert len(body) == txn_size(n)
    return np.frombuffer(body, np.uint8)


def signer_weights(conf: dict) -> np.ndarray | None:
    """The configuration's `txn_shape`, {"signers": {"1": w1, ...}}, as
    (MAX_SIGNERS + 1,) weights indexed by signer count, or None when the
    configuration states none (one signer).  It is the deployment's, like
    a schema's row widths: a `rehearse` group may not change it."""
    shape = conf.get("txn_shape")
    if "txn_shape" in conf.get("rehearse", {}):
        raise ValueError("a rehearse group may change sizes only, not "
                         "txn_shape")
    if shape is None:
        return None
    w = np.zeros(MAX_SIGNERS + 1, np.float64)
    for k, v in shape["signers"].items():
        if not 1 <= int(k) <= MAX_SIGNERS or v < 0:
            raise ValueError(f"txn_shape: signers {k!r}: {v!r}")
        w[int(k)] = v
    if not w.sum() > 0:
        raise ValueError("txn_shape: no signer count has a weight")
    return w


def apportion(weights: np.ndarray, n: int) -> np.ndarray:
    """-> (n,) signer counts, as many of each as its share of the weights
    (largest remainders; a tie goes to the smaller count), in count order."""
    share = weights / weights.sum() * n
    got = np.floor(share).astype(np.int64)
    rest = n - int(got.sum())
    got[np.argsort(-(share - got), kind="stable")[:rest]] += 1
    return np.repeat(np.arange(len(weights)), got)


def _sign_chunk(secrets: np.ndarray, signers: np.ndarray,
                msgs: np.ndarray) -> np.ndarray:
    """(m, n) account indices and (m, L) messages -> (m, n, 64): each
    message signed by each of its signers."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )

    keys: dict[int, Ed25519PrivateKey] = {}
    out = np.empty(signers.shape + (64,), np.uint8)
    for i in range(len(msgs)):
        msg = msgs[i].tobytes()
        for j, a in enumerate(signers[i].tolist()):
            k = keys.get(a)
            if k is None:
                k = keys[a] = Ed25519PrivateKey.from_private_bytes(
                    secrets[a].tobytes())
            out[i, j] = np.frombuffer(k.sign(msg), np.uint8)
    return out


def _sign(secrets: np.ndarray, jobs: list, workers: int) -> list:
    """jobs: [(signers (m, n), msgs (m, L))] -> [(m, n, 64)], in a pool
    of `workers` processes (none for one), each job cut into as many
    pieces as there are workers."""
    lanes = sum(s.size for s, _ in jobs)
    workers = max(1, min(workers, lanes // 2048 + 1))
    if workers == 1:
        return [_sign_chunk(secrets, s, m) for s, m in jobs]
    pieces = []
    for s, m in jobs:
        cuts = np.linspace(0, len(s), workers + 1).astype(int)
        pieces += [(s[a:b], m[a:b]) for a, b in zip(cuts, cuts[1:])]
    with concurrent.futures.ProcessPoolExecutor(
        workers, mp_context=multiprocessing.get_context("spawn")
    ) as pool:
        sigs = list(pool.map(_sign_chunk, [secrets] * len(pieces),
                             *zip(*pieces)))
    return [np.concatenate(sigs[i * workers:(i + 1) * workers])
            for i in range(len(jobs))]


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


def _le_bytes(v: np.ndarray, n: int) -> np.ndarray:
    return (v.astype(np.uint64)[:, None]
            >> (8 * np.arange(n, dtype=np.uint64))).astype(np.uint8)


def _copy_after(rng, n_unique: int, src: np.ndarray) -> np.ndarray:
    """Order keys that put each copy of txn src[i] at a seeded place
    strictly after its original (which sits at key src[i])."""
    return src + 0.5 + np.floor(rng.random(len(src)) * (n_unique - src))


def _ragged_take(buf: np.ndarray, off: np.ndarray, which: np.ndarray,
                 chunk: int = 1 << 13):
    """Rows `which` of the ragged rows (buf, off), in that order ->
    (buf, off) of their own."""
    lens = np.diff(off)[which]
    out_off = np.zeros(len(which) + 1, np.int64)
    np.cumsum(lens, out=out_off[1:])
    out = np.empty(int(out_off[-1]), np.uint8)
    for a in range(0, len(which), chunk):
        b = min(a + chunk, len(which))
        lo, hi = int(out_off[a]), int(out_off[b])
        # each output byte's source: its row's start there, + its place
        out[lo:hi] = buf[np.repeat(off[which[a:b]] - (out_off[a:b] - lo),
                                   lens[a:b]) + np.arange(hi - lo)]
    return out, out_off


def make_corpus(n_unique: int, n_accounts: int, dup_every: int,
                bad_every: int, seed: int, workers: int = 8,
                keys=None, weights: np.ndarray | None = None) -> dict:
    """-> dict(buf, off: the rows in stream order, ragged (module doc);
    kind (n,) u8; src (n,) the txn each row is or copies; bad_sig (n,)
    the slot of the corrupted signature of a bad row, -1 for the others;
    pubs (n_accounts, 32); payer/dest/amount/nsig, one per txn: account
    indices, lamports, signer count; sign_s, the seconds signing took).
    With one signer (`weights` None) also `send`, the (n, 215) rows.
    `keys` = make_keys(n_accounts, seed), when the caller already made
    them; `weights` = signer_weights(conf)."""
    rng, secrets, blockhash, pubs = keys or make_keys(n_accounts, seed)
    if weights is None:
        return _one_signer(n_unique, n_accounts, dup_every, bad_every,
                           workers, rng, secrets, blockhash, pubs)
    return _n_signers(n_unique, n_accounts, dup_every, bad_every, workers,
                      rng, secrets, blockhash, pubs, weights)


def _one_signer(n_unique, n_accounts, dup_every, bad_every, workers, rng,
                secrets, blockhash, pubs) -> dict:
    idx = np.arange(n_unique)
    payer = idx % n_accounts
    # never the payer itself for an even account count: 6i + 3 is odd
    dest = (7 * idx + 3) % n_accounts
    amount = (idx + 1).astype(np.uint64)  # distinct, so txns are distinct
    rows = np.tile(template(blockhash), (n_unique, 1))
    rows[:, PAYER_OFF:PAYER_OFF + 32] = pubs[payer]
    rows[:, DEST_OFF:DEST_OFF + 32] = pubs[dest]
    rows[:, AMOUNT_OFF:AMOUNT_OFF + 8] = _le_bytes(amount, 8)
    t0 = time.perf_counter()
    (sigs,) = _sign(secrets, [(payer[:, None], rows[:, MSG_OFF:])], workers)
    sign_s = time.perf_counter() - t0
    rows[:, SIG_OFF:SIG_OFF + 64] = sigs[:, 0]

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
    key = np.concatenate([idx.astype(np.float64),
                          _copy_after(rng, n_unique, extra)])
    order = np.argsort(key, kind="stable")
    send, kind, src = rows[src[order]], kind[order], src[order]
    bad = np.flatnonzero(kind == KIND_BAD)
    send[bad, SIG_OFF + rng.integers(0, 8, len(bad))] ^= (
        1 << rng.integers(0, 8, len(bad))
    ).astype(np.uint8)
    return dict(send=send, buf=send.reshape(-1),
                off=np.arange(len(send) + 1, dtype=np.int64) * TXN_SZ,
                kind=kind, src=src,
                bad_sig=np.where(kind == KIND_BAD, 0, -1).astype(np.int8),
                pubs=pubs, payer=payer, dest=dest, amount=amount,
                nsig=np.ones(n_unique, np.int64), sign_s=sign_s)


def _n_signers(n_unique, n_accounts, dup_every, bad_every, workers, rng,
               secrets, blockhash, pubs, weights) -> dict:
    n_dup, n_bad = n_unique // dup_every, n_unique // bad_every
    n_txn = n_unique + n_bad  # txns [n_unique, n_txn) are the bad ones
    if n_accounts < MAX_SIGNERS + 1:
        raise ValueError(f"{n_accounts} accounts cannot give an "
                         f"{MAX_SIGNERS}-signer transfer distinct keys")
    nsig = np.concatenate([rng.permutation(apportion(weights, n_unique)),
                           rng.permutation(apportion(weights, n_bad))])
    idx = np.arange(n_txn)
    payer = idx % n_accounts
    # key k of a txn (0 the payer, 1..N-1 its co-signers, N the
    # destination) is payer + k * stride: distinct for a stride coprime
    # with the account count
    strides = np.flatnonzero(np.gcd(np.arange(n_accounts), n_accounts) == 1)
    stride = rng.choice(strides, n_txn)
    dest = (payer + nsig * stride) % n_accounts
    amount = (idx + 1).astype(np.uint64)  # distinct, so txns are distinct

    # the txns laid out by signer count, one block of rows per count
    txn_off = np.zeros(n_txn + 1, np.int64)
    by_n = np.argsort(nsig, kind="stable")
    np.cumsum(txn_size(nsig[by_n]), out=txn_off[1:])
    store = np.empty(int(txn_off[-1]), np.uint8)
    blocks, jobs = [], []
    for n in range(1, MAX_SIGNERS + 1):
        at = np.flatnonzero(nsig[by_n] == n)
        if not len(at):
            continue
        g = by_n[at]  # the txns of this count, ascending
        sz, msg = txn_size(n), 1 + 64 * n
        rows = store[txn_off[at[0]]:txn_off[at[-1] + 1]].reshape(len(g), sz)
        rows[:] = template(blockhash, n)
        accts = (payer[g, None] + np.arange(n + 1) * stride[g, None]
                 ) % n_accounts
        rows[:, msg + 4:msg + 4 + 32 * (n + 1)] = pubs[accts].reshape(
            len(g), -1)
        rows[:, sz - 8:] = _le_bytes(amount[g], 8)
        blocks.append((n, g, rows))
        jobs.append((accts[:, :n], rows[:, msg:]))
    t0 = time.perf_counter()
    sigs = _sign(secrets, jobs, workers)
    sign_s = time.perf_counter() - t0
    bad_sig = np.full(n_txn, -1, np.int8)
    for (n, g, rows), s in zip(blocks, sigs):
        rows[:, 1:1 + 64 * n] = s.reshape(len(g), -1)
        bad = np.flatnonzero(g >= n_unique)
        j = rng.integers(0, n, len(bad))
        rows[bad, 1 + 64 * j + rng.integers(0, 64, len(bad))] ^= (
            1 << rng.integers(0, 8, len(bad))).astype(np.uint8)
        bad_sig[g[bad]] = j
    # where each txn's bytes are, by txn index
    where = np.empty(n_txn, np.int64)
    where[by_n] = np.arange(n_txn)

    # the stream: the unique txns in order, each re-send at a seeded place
    # after its original, each bad txn at a seeded place of its own; the
    # re-sent txns are drawn N by N, so every seed re-sends the same sizes
    dup_src = np.concatenate([
        rng.choice(np.flatnonzero(nsig[:n_unique] == n), k, replace=False)
        for n, k in enumerate(np.bincount(apportion(weights, n_dup),
                                          minlength=MAX_SIGNERS + 1))])
    src = np.concatenate([idx, dup_src])
    kind = np.concatenate([
        np.full(n_unique, KIND_UNIQUE, np.uint8),
        np.full(n_bad, KIND_BAD, np.uint8),
        np.full(n_dup, KIND_DUP, np.uint8),
    ])
    key = np.concatenate([
        np.arange(n_unique, dtype=np.float64),
        np.floor(rng.random(n_bad) * n_unique) + 0.25,
        _copy_after(rng, n_unique, dup_src),
    ])
    order = np.argsort(key, kind="stable")
    kind, src = kind[order], src[order]
    buf, off = _ragged_take(store, txn_off, where[src])
    return dict(buf=buf, off=off, kind=kind, src=src, bad_sig=bad_sig[src],
                pubs=pubs, payer=payer, dest=dest, amount=amount, nsig=nsig,
                sign_s=sign_s)
