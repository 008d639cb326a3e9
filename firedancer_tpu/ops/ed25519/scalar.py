"""Arithmetic mod the Ed25519 group order L, batch-last int32 limbs.

L = 2^252 + c with c = 27742317777372353535851937790883648493 (~2^125).

The reference reduces 512-bit SHA-512 digests mod L with 64-bit limb code
(/root/reference/src/ballet/ed25519/ref/fd_curve25519_scalar.c, behavior
contract only).  Here scalars use the same radix-2^13 / 20-limb layout as the
field (see field.py for why that radix fits TPU int32 lanes), and the 512-bit
reduction folds high limbs through a precomputed table of 2^(13*i) mod L.

All functions are shape-polymorphic over the trailing batch axis.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from . import field as F
from .golden import L

RADIX = F.RADIX
NLIMB = F.NLIMB
MASK = F.MASK

_C = L - (1 << 252)  # the "c" in L = 2^252 + c
_L_LIMBS = F.int_to_limbs(L).reshape(NLIMB, 1)
_C_LIMBS = F.int_to_limbs(_C).reshape(NLIMB, 1)
# _R_POW[i] = 2^(13*(NLIMB+i)) mod L for i in 0..20, canonical limbs (21, 20)
_R_POW = np.stack(
    [F.int_to_limbs(pow(2, RADIX * (NLIMB + i), L)) for i in range(NLIMB + 1)]
)


_ripple = F.ripple  # shared exact sequential carry (field.py)


def from_bytes(b):
    """(B, 32) uint8 little-endian -> (NLIMB, B) limbs (value < 2^256)."""
    return F.from_bytes(b)


def is_canonical(s):
    """(NLIMB, B) canonical-shaped limbs -> (B,) bool: s < L."""
    _, borrow = _ripple(s - _L_LIMBS)  # borrow: (1, B)
    return jnp.squeeze(borrow, axis=0) < 0


def _fold_once(lo, hi):
    """value = lo + sum_i hi[i] * 2^(13*(NLIMB+i))  ->  smaller equivalent.

    lo: (NLIMB, B) 13-bit limbs; hi: (nh, B) 13-bit limbs, nh <= NLIMB+1.
    Each output column accumulates <= nh products of 13-bit values plus the
    lo limb: < (NLIMB+1) * 2^26 + 2^13 < 2^31.  Exact in int32.
    """
    nh = hi.shape[0]
    r = jnp.asarray(_R_POW[:nh])  # (nh, NLIMB)
    contrib = jnp.einsum("ib,ik->kb", hi, r, preferred_element_type=jnp.int32)
    return lo + contrib


def _reduce_wide(x):
    """(n, B) 13-bit limbs, NLIMB < n <= 2*NLIMB+1 -> canonical scalar
    (NLIMB, B): fold high limbs through _R_POW, ripple the folded carries
    out, then split at bit 252 (L = 2^252 + c).
    """
    v = _fold_once(x[:NLIMB], x[NLIMB:])
    for _ in range(5):
        v, co = _ripple(v)  # co: (1, B)
        v = _fold_once(v, co)
    v, co = _ripple(v)  # co == 0 now (value < 2^260)

    # Final: value < 2^260.  Split at bit 252 (bit 5 of limb 19):
    # value = hi * 2^252 + lo252  ===  lo252 - hi * c  (mod L), |result| small.
    hi = v[NLIMB - 1] >> 5
    lo = v.at[NLIMB - 1].set(v[NLIMB - 1] & 31)
    w = lo - hi[None, :] * _C_LIMBS  # products <= 2^8 * 2^13 = 2^21
    w, carry = _ripple(w)  # carry: (1, B)
    # carry in {-1, 0}: negative means w < 0 -> add L once (w > -2^134).
    neg = carry < 0
    w_fixed, _ = _ripple(w + _L_LIMBS)
    return jnp.where(neg, w_fixed, w)


def reduce512(digest):
    """(B, 64) uint8 little-endian 512-bit -> canonical scalar (NLIMB, B).

    This is the `k = SHA512(R||A||M) mod L` step of verify.
    """
    b = digest.astype(jnp.int32)
    padded = jnp.concatenate(
        [b, jnp.zeros(b.shape[:-1] + (2,), jnp.int32)], axis=-1
    )
    limbs = []
    for k in range(2 * NLIMB):  # 40 limbs cover 520 >= 512 bits
        o = RADIX * k
        byte0, shift = o >> 3, o & 7
        window = (
            padded[..., byte0]
            | (padded[..., byte0 + 1] << 8)
            | (padded[..., byte0 + 2] << 16)
        )
        limbs.append((window >> shift) & MASK)
    x = jnp.stack(limbs, axis=0)  # (40, B)
    return _reduce_wide(x)


def to_nibbles(s):
    """Canonical-shaped (NLIMB, B) limbs -> (64, B) radix-16 digits, LSB first.

    Covers 256 bits, so any s < 2^256 (even non-canonical, for uniformity of
    the rejected-lane data path) digitizes exactly.
    """
    padded = jnp.concatenate([s, jnp.zeros_like(s[:1])], axis=0)
    out = []
    for j in range(64):
        o = 4 * j
        l0, sh = o // RADIX, o % RADIX
        window = padded[l0] + (padded[l0 + 1] << RADIX)
        out.append((window >> sh) & 15)
    return jnp.stack(out, axis=0)


# sum_i 8 * 16^i for i in 0..63: adding this value makes every nibble of the
# sum equal (original nibble + 8 + incoming carry), so signed digits fall out
# of one limb add + ripple + nibble extract (no 64-step sequential recode).
_EIGHTS = F.int_to_limbs(sum(8 << (4 * i) for i in range(64))).reshape(
    NLIMB, 1
)


def to_signed_digits(s):
    """Canonical-shaped (NLIMB, B) limbs -> (64, B) digits in [-8, 7] with
    s == sum_i d_i 16^i.  Exact for s < 2^253 (any canonical scalar); lanes
    with larger non-canonical s produce garbage digits but those lanes are
    already rejected by is_canonical.
    """
    t, _ = _ripple(s + jnp.asarray(_EIGHTS))
    return to_nibbles(t) - 8
