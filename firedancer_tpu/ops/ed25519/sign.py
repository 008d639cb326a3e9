"""Batched Ed25519 signing — the load-generator's corpus factory.

The reference's `fddev bench` spreads transaction signing across benchg
tiles on CPU cores (src/app/fddev/bench.c:62-90 topology).  The TPU-first
analog puts the one expensive step — the fixed-base scalar mul [r]B —
on the device as a batched (NLIMB, B) program over the existing point
ops, and keeps the cheap scalar/hash bookkeeping (RFC 8032 steps) on the
host: one device execution signs a whole corpus.

This path exists for the bench/load-gen surface (mass-producing DISTINCT
signed txns so dedup cannot collapse the load); single signatures keep
using golden.sign.
"""

from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from firedancer_tpu.utils.hotpath import hot_path

from . import golden
from . import point as PT
from . import scalar as SC


@functools.partial(jax.jit)
@hot_path
def _base_mul_compress(r_bytes):
    """(B, 32) uint8 little-endian scalars (< L) -> (B, 32) compressed
    [r]B encodings (point.scalar_mul_base; plain XLA — corpus prep is a
    one-time cost, not the verify hot path)."""
    return PT.compress(
        PT.scalar_mul_base(SC.to_signed_digits(SC.from_bytes(r_bytes)))
    )


def public_keys(secrets: list[bytes]) -> list[bytes]:
    """Batch [a]B public-key derivation on device (one execution)."""
    n = len(secrets)
    a_arr = np.zeros((n, 32), np.uint8)
    for i, s in enumerate(secrets):
        a_int, _ = golden.secret_expand(s)
        # clamped scalars exceed L; the digit recode expects canonical
        # scalars, and [a mod l]B == [a]B (l divides B's order)
        a_int %= golden.L
        a_arr[i] = np.frombuffer(a_int.to_bytes(32, "little"), np.uint8)
    A = np.asarray(_base_mul_compress(jnp.asarray(a_arr)))
    return [A[i].tobytes() for i in range(n)]


def sign_many(pairs: list[tuple[bytes, bytes]],
              pubs: dict[bytes, bytes] | None = None) -> list[bytes]:
    """Sign (secret, msg) pairs — keys may all differ; the [r]B fixed-
    base mul runs as ONE device execution over every lane.

    pubs: optional secret->pubkey map; missing keys are derived as one
    device batch rather than per-key host scalar muls.

    RFC 8032: r = SHA512(prefix || M) mod L; R = [r]B;
    S = (r + SHA512(R || A || M) * a) mod L.  Returns 64-byte sigs.
    """
    n = len(pairs)
    pubs = dict(pubs or {})
    unique = []
    for secret, _ in pairs:
        if secret not in pubs and secret not in unique:
            unique.append(secret)
    if unique:
        for s, pk in zip(unique, public_keys(unique)):
            pubs[s] = pk
    expanded = {}
    for secret, _ in pairs:
        if secret not in expanded:
            a_int, prefix = golden.secret_expand(secret)
            expanded[secret] = (a_int, prefix, pubs[secret])
    rs = []
    r_arr = np.zeros((n, 32), np.uint8)
    for i, (secret, m) in enumerate(pairs):
        _, prefix, _ = expanded[secret]
        r = int.from_bytes(
            hashlib.sha512(prefix + m).digest(), "little"
        ) % golden.L
        rs.append(r)
        r_arr[i] = np.frombuffer(r.to_bytes(32, "little"), np.uint8)
    R = np.asarray(_base_mul_compress(jnp.asarray(r_arr)))
    sigs = []
    for i, (secret, m) in enumerate(pairs):
        a_int, _, pub = expanded[secret]
        Rb = R[i].tobytes()
        k = int.from_bytes(
            hashlib.sha512(Rb + pub + m).digest(), "little"
        ) % golden.L
        S = (rs[i] + k * a_int) % golden.L
        sigs.append(Rb + S.to_bytes(32, "little"))
    return sigs


def sign_batch(secret: bytes, msgs: list[bytes]) -> list[bytes]:
    """Sign every message with one key (see sign_many)."""
    return sign_many(
        [(secret, m) for m in msgs],
        pubs={secret: golden.public_from_secret(secret)},
    )
