"""Ed25519 group ops on extended twisted-Edwards coordinates, batched.

A point is a 4-tuple (X, Y, Z, T) of field elements (field.py limb arrays,
batch axis last) with x = X/Z, y = Y/Z, T = XY/Z.  The addition law is the
unified a=-1 formula set (add-2008-hwcd-3 / dbl-2008-hwcd), which is COMPLETE
on curve25519 because a = -1 is a square mod p and d is not -- so one
branch-free formula covers identity, doubling, and small-order inputs alike.
That completeness is what makes the whole verify data path a straight-line
vector program (no lax.cond per lane), unlike the reference's table-driven
scalar code (/root/reference/src/ballet/ed25519/ref/fd_curve25519.c, behavior
contract only).

Scalar multiplication is a Strauss/Shamir interleaved double-scalar-mul with
SIGNED 4-bit windows (digits in [-8, 7], scalar.to_signed_digits): 64
iterations of (4 doublings + 2 table additions) against 9-entry tables in
"niels" form (Y+X, Y-X, 2dT, 2Z) -- negation of a niels point is a
swap + T negate, so the signed window halves table size and build cost.
The T coordinate is only produced where the next op consumes it (3 of 4
doublings and the second add per iteration skip it).

Carry discipline: operands are kept inside the machine-checked interval
contract of field.mul_rr (tests/test_field_bounds.py); F.carry1 one-pass
normalizations are inserted exactly where that analysis requires.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import field as F
from . import golden

# ---------------------------------------------------------------------------
# Core formulas
# ---------------------------------------------------------------------------


def identity(batch: int):
    z = jnp.zeros((F.NLIMB, batch), jnp.int32)
    one = jnp.broadcast_to(F.c("ONE"), (F.NLIMB, batch))
    return (z, one, one, z)


def negate(p):
    x, y, z, t = p
    return (F.neg(x), y, z, F.neg(t))


def double(p, with_t: bool = True):
    """Unified extended doubling (dbl-2008-hwcd, a=-1).

    Input coords must be carried (mul outputs / canonical limbs).  When
    with_t is False the T output is zeros (1 mul saved); only valid when
    the consumer ignores T (another doubling, or the final eq check).
    """
    x, y, z, _ = p
    a = F.sqr_rr(x)
    b = F.sqr_rr(y)
    c2 = F.sqr_rr(z)
    e = F.carry1(F.sqr_rr(F.carry1(x + y)) - a - b)
    g = b - a
    f = F.carry1(g - c2 - c2)
    h = F.carry1(-(a + b))
    t3 = F.mul_rr(e, h) if with_t else jnp.zeros_like(a)
    return (F.mul_rr(e, f), F.mul_rr(g, h), F.mul_rr(f, g), t3)


def add(p, q):
    """Unified extended addition (add-2008-hwcd-3, a=-1, k=2d) of two full
    extended points.  Used for table building and generic composition; the
    dsm hot loop uses add_niels/add_niels_affine instead."""
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = F.mul_rr(y1 - x1, F.carry1(y2 - x2))
    b = F.mul_rr(F.carry1(y1 + x1), F.carry1(y2 + x2))
    c = F.mul_rr(F.mul_rr(t1, F.c("D2")), t2)
    zz = F.mul_rr(z1, z2)
    e = F.carry1(b - a)
    f = F.carry1(zz + zz - c)
    g = F.carry1(zz + zz + c)
    h = F.carry1(b + a)
    return (F.mul_rr(e, f), F.mul_rr(g, h), F.mul_rr(f, g), F.mul_rr(e, h))


# ---------------------------------------------------------------------------
# Niels-form table entries
# ---------------------------------------------------------------------------


def to_niels(p):
    """Extended point -> (Y+X, Y-X, 2dT, 2Z), all carried."""
    x, y, z, t = p
    return (
        F.carry(y + x),
        F.carry(y - x),
        F.mul_rr(t, F.c("D2")),
        F.carry(z + z),
    )


def identity_niels(batch: int):
    one = jnp.broadcast_to(F.c("ONE"), (F.NLIMB, batch))
    return (one, one, jnp.zeros_like(one), one + one)


def to_niels_affine(p):
    """Extended point with Z == 1 (a decompress output) ->
    (y+x, y-x, 2dxy) affine niels, all carried."""
    x, y, _, t = p
    return (F.carry(y + x), F.carry(y - x), F.mul_rr(t, F.c("D2")))


def add_niels(p, e, with_t: bool = True):
    """p + e where e = (Y+X, Y-X, 2dT, 2Z) niels form (projective)."""
    x1, y1, z1, t1 = p
    ypx, ymx, t2d, z2e = e
    a = F.mul_rr(y1 - x1, ymx)
    b = F.mul_rr(F.carry1(y1 + x1), ypx)
    c = F.mul_rr(t1, t2d)
    d2 = F.mul_rr(z1, z2e)
    ec = F.carry1(b - a)
    f = d2 - c
    g = F.carry1(d2 + c)
    h = F.carry1(b + a)
    t3 = F.mul_rr(ec, h) if with_t else jnp.zeros_like(a)
    return (F.mul_rr(ec, f), F.mul_rr(g, h), F.mul_rr(f, g), t3)


def add_niels_affine(p, e, with_t: bool = False):
    """p + e where e = (y+x, y-x, 2dxy) affine niels (Z == 1 implicit)."""
    x1, y1, z1, t1 = p
    ypx, ymx, t2d = e
    a = F.mul_rr(y1 - x1, ymx)
    b = F.mul_rr(F.carry1(y1 + x1), ypx)
    c = F.mul_rr(t1, t2d)
    ec = F.carry1(b - a)
    f = F.carry1(z1 + z1 - c)
    g = F.carry1(z1 + z1 + c)
    h = F.carry1(b + a)
    t3 = F.mul_rr(ec, h) if with_t else jnp.zeros_like(a)
    return (F.mul_rr(ec, f), F.mul_rr(g, h), F.mul_rr(f, g), t3)


# ---------------------------------------------------------------------------
# Decompress / compress / predicates
# ---------------------------------------------------------------------------


def decompress_bytes(b):
    """(B, 32) uint8 -> (y limbs (NLIMB, B), sign (1, B)) — the byte
    parsing half of decompress (XLA side; byte gathers don't lower under
    Mosaic)."""
    sign = (b[..., 31:32] >> 7).astype(jnp.int32).T
    b_masked = b.at[..., 31].set(b[..., 31] & 0x7F)
    return F.from_bytes(b_masked), sign


def decompress_limbs(y, sign):
    """(y limbs, sign (1, B)) -> (point, ok (B,)) — the field-math half of
    decompress; Mosaic-safe, runs inside the Pallas verify kernel.

    Matches the reference verify rules: non-canonical y (>= p) accepted,
    sqrt failure rejected, x == 0 with sign bit set ("negative zero")
    rejected.  Lanes with ok == False carry garbage coordinates; callers
    mask them out of the final verdict.
    """
    one = F.c("ONE")
    ysq = F.sqr_rr(y)
    u = ysq - one
    v = F.carry1(F.mul_rr(F.c("D"), ysq) + one)
    # candidate root x = u v^3 (u v^7)^((p-5)/8)   (ref10 trick)
    v3 = F.mul_rr(F.sqr_rr(v), v)
    v7 = F.mul_rr(F.sqr_rr(v3), v)
    t = F.pow_p58(F.mul_rr(F.carry1(u), v7))
    x = F.mul_rr(F.mul_rr(F.carry1(u), v3), t)
    vxx = F.mul_rr(v, F.sqr_rr(x))
    ok_direct = F.eq(vxx, u)
    ok_flip = F.eq(vxx, F.neg(u))
    x = jnp.where(ok_flip[None], F.mul_rr(x, F.c("SQRT_M1")), x)
    ok = ok_direct | ok_flip
    # negative zero: x == 0 with sign bit set is not a valid encoding
    x_is_zero = F.is_zero(x)
    ok = ok & ~(x_is_zero & jnp.squeeze(sign == 1, axis=0))
    # choose the root with matching parity
    flip = (F.parity(x)[None] != sign) & ~x_is_zero[None]
    x = jnp.where(flip, F.neg(x), x)
    # x is carried up to sign; negation keeps |limb| bounds symmetric, and
    # carry1 restores the carried contract for downstream raw muls
    x = F.carry1(x)
    z = jnp.broadcast_to(jnp.asarray(one), x.shape)
    return (x, y, z, F.mul_rr(x, F.carry1(y))), ok


def decompress(b):
    """(B, 32) uint8 -> (point, ok).  See decompress_limbs for rules."""
    y, sign = decompress_bytes(b)
    return decompress_limbs(y, sign)


def compress(p):
    """Point -> (B, 32) uint8 canonical encoding (via one inversion)."""
    x, y, z, _ = p
    zinv = F.invert(F.carry1(z))
    xa = F.canonical(F.mul_rr(F.carry1(x), zinv))
    yb = F.to_bytes(F.mul_rr(F.carry1(y), zinv))
    return yb.at[..., 31].set(yb[..., 31] | ((xa[0] & 1) << 7).astype(jnp.uint8))


def is_small_order(p):
    """(B,) bool: the point's order divides 8 ([8]P == identity).

    The verify path rejects small-order A/R by byte blocklist in the
    prologue instead (golden.small_order_blocklist); this point-math form
    remains for generic use and tests.
    """
    q = double(double(double(p, with_t=False), with_t=False), with_t=False)
    x8, y8, z8, _ = q
    return F.is_zero(x8) & F.eq(y8, z8)


def eq_external(acc, r):
    """Projective acc == affine-decompressed r (Z_r == 1), no inversion.

    The cross-multiply equality the reference uses (behavior of
    fd_ed25519_point_eq_z1, /root/reference/src/ballet/ed25519/
    fd_ed25519_user.c:224-228).
    """
    xa, ya, za, _ = acc
    xr, yr, _, _ = r
    zc = F.carry1(za)
    return F.eq(F.mul_rr(F.carry1(xr), zc), xa) & F.eq(
        F.mul_rr(F.carry1(yr), zc), ya
    )


# ---------------------------------------------------------------------------
# Tables + double scalar mul
# ---------------------------------------------------------------------------


def _build_base_table9() -> np.ndarray:
    """(9, 3, NLIMB, 1): affine niels (y+x, y-x, 2dxy) of i*B, i in 0..8,
    host-computed via the golden oracle (canonical limbs)."""
    rows = []
    acc = (0, 1)  # identity
    for i in range(9):
        x, y = acc
        rows.append(
            np.stack(
                [
                    F.int_to_limbs((y + x) % golden.P).reshape(F.NLIMB, 1),
                    F.int_to_limbs((y - x) % golden.P).reshape(F.NLIMB, 1),
                    F.int_to_limbs(
                        2 * golden.D * x % golden.P * y % golden.P
                    ).reshape(F.NLIMB, 1),
                ]
            )
        )
        acc = golden.point_add(acc, golden.B)
    return np.stack(rows)


B_TABLE9 = _build_base_table9()
F.register_const("B_TABLE9", B_TABLE9)


def build_neg_table9(a_pt):
    """Device table (9, 4, NLIMB, B): niels form of i*(-A) for i in 0..8."""
    na = negate(a_pt)
    pts = [na]  # 1
    pts.append(double(pts[0]))  # 2
    pts.append(add(pts[1], na))  # 3
    pts.append(double(pts[1]))  # 4
    pts.append(add(pts[3], na))  # 5
    pts.append(double(pts[2]))  # 6
    pts.append(add(pts[5], na))  # 7
    pts.append(double(pts[3]))  # 8
    batch = a_pt[0].shape[-1]
    entries = [identity_niels(batch)] + [to_niels(p) for p in pts]
    return jnp.stack([jnp.stack(e) for e in entries])


def _select9(table, absd):
    """table (9, C, NLIMB, B), absd (B,) in [0, 8] -> (C, NLIMB, B) entry.

    Branchless 4-level select tree keyed on the bits of absd: 8 wheres at
    the VPU cheap-op rate, replacing the masked-sum gather (9 multiplies +
    8 adds at the multiply-issue rate) — the table lookup is half of the
    dsm loop's non-curve-op overhead."""
    b0 = ((absd & 1) != 0)[None, None, :]
    b1 = ((absd & 2) != 0)[None, None, :]
    b2 = ((absd & 4) != 0)[None, None, :]
    b3 = (absd >= 8)[None, None, :]
    s0 = jnp.where(b0, table[1], table[0])
    s2 = jnp.where(b0, table[3], table[2])
    s4 = jnp.where(b0, table[5], table[4])
    s6 = jnp.where(b0, table[7], table[6])
    t0 = jnp.where(b1, s2, s0)
    t4 = jnp.where(b1, s6, s4)
    return jnp.where(b3, table[8], jnp.where(b2, t4, t0))


def lookup9(table, digit):
    """table (9, 4, NLIMB, B), digit (B,) in [-8, 8] -> niels entry tuple.

    Signed window: entry |digit| is selected by a branchless bit tree,
    negation (swap Y+X <-> Y-X, negate 2dT) applied where digit < 0."""
    coords = _select9(table, jnp.abs(digit))  # (4, NLIMB, B)
    ypx, ymx, t2d, z2e = (
        jnp.squeeze(v, axis=0) for v in jnp.split(coords, 4, axis=0)
    )
    neg = (digit < 0)[None, :]
    return (
        jnp.where(neg, ymx, ypx),
        jnp.where(neg, ypx, ymx),
        jnp.where(neg, -t2d, t2d),
        z2e,
    )


def lookup9_affine(table, digit):
    """table (9, 3, NLIMB, B or 1), digit (B,) -> affine niels tuple."""
    batch = digit.shape[-1]
    if table.shape[-1] == 1:  # shared table: lanes-only broadcast first
        table = jnp.broadcast_to(table, table.shape[:-1] + (batch,))
    coords = _select9(table, jnp.abs(digit))  # (3, NLIMB, B)
    ypx, ymx, t2d = (
        jnp.squeeze(v, axis=0) for v in jnp.split(coords, 3, axis=0)
    )
    neg = (digit < 0)[None, :]
    return (
        jnp.where(neg, ymx, ypx),
        jnp.where(neg, ypx, ymx),
        jnp.where(neg, -t2d, t2d),
    )


def scalar_mul_base(s_digits):
    """[s]B from (64, B) signed digits — fixed-base Strauss over the
    shared affine B-table.  sign.py's [r]B (the load generator's corpus
    signer) runs through it."""
    batch = s_digits.shape[-1]
    b_table = F.c("B_TABLE9")

    def body(j, acc):
        idx = 63 - j
        d = jax.lax.dynamic_slice_in_dim(s_digits, idx, 1, axis=0)[0]
        acc = double(acc, with_t=False)
        acc = double(acc, with_t=False)
        acc = double(acc, with_t=False)
        acc = double(acc, with_t=True)
        return add_niels_affine(acc, lookup9_affine(b_table, d), with_t=True)

    return jax.lax.fori_loop(0, 64, body, identity(batch))


def double_scalar_mul(k_digits, neg_a_table9, s_digits):
    """[k](-A) + [s]B with signed 4-bit interleaved windows.

    k_digits, s_digits: (64, B) int32 digits in [-8, 7], LSB first (from
    scalar.to_signed_digits).  Behavior contract:
    fd_ed25519_double_scalar_mul_base (/root/reference/src/ballet/ed25519/
    fd_ed25519_user.c:210-214).
    """
    batch = k_digits.shape[-1]
    b_table = F.c("B_TABLE9")

    def body(j, acc):
        idx = 63 - j
        acc = double(acc, with_t=False)
        acc = double(acc, with_t=False)
        acc = double(acc, with_t=False)
        acc = double(acc, with_t=True)
        acc = add_niels(acc, lookup9(neg_a_table9, k_digits[idx]), with_t=True)
        acc = add_niels_affine(
            acc, lookup9_affine(b_table, s_digits[idx]), with_t=False
        )
        return acc

    return jax.lax.fori_loop(0, 64, body, identity(batch))
