"""Batched Ed25519 verification -- the TPU analog of the reference's
verify hot spot and of the wiredancer FPGA offload.

Behavior contract (independently re-implemented from RFC 8032 + the golden
oracle; reference parity target: fd_ed25519_verify,
/root/reference/src/ballet/ed25519/fd_ed25519_user.c:134-229):

  1. reject non-canonical s (s >= L)
  2. decompress A (pubkey) and R (sig[0:32]); non-canonical y accepted,
     "negative zero" rejected
  3. reject small-order A or R -- done by comparing the raw 32-byte
     encodings against the derived 11-entry blocklist
     (golden.small_order_blocklist), which covers every encoding our
     decompress accepts that decodes to 8-torsion, including
     non-canonical-y forms.  Equivalent to the reference's point-math
     check but free of the 3 extra doublings per input.
  4. k = SHA512(R || A || M) mod L
  5. accept iff [k](-A) + [s]B == R   (cofactorless)

The whole batch runs as one straight-line SPMD program: every lane pays the
worst-case cost and per-lane validity is a boolean mask, never control flow.
This is the opposite of the reference's early-return scalar code and is what
lets XLA map the batch onto the VPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from firedancer_tpu.utils.hotpath import hot_path

from .. import sha512 as _sha
from . import field as F
from . import golden
from . import point as PT
from . import scalar as SC

_BLOCKLIST = np.stack(
    [np.frombuffer(e, np.uint8) for e in golden.small_order_blocklist()]
)  # (11, 32)


def _is_small_order_enc(b):
    """(B, 32) uint8 -> (B,) bool: encoding is on the small-order blocklist."""
    bl = jnp.asarray(_BLOCKLIST)
    return jnp.any(
        jnp.all(b[:, None, :] == bl[None, :, :], axis=-1), axis=1
    )


def _use_pallas() -> bool:
    """The fused Pallas kernel runs the dsm hot loop on TPU; elsewhere the
    plain XLA path is used (Pallas interpret mode is for tests only).
    The choice is silent by design — it is what lets the CPU tests run
    the same entry points — so whatever must prove the chip did the
    work asserts the outcome instead: chip_smoke.py looks for the Mosaic
    call (`tpu_custom_call`) in the compiled program's text."""
    import os

    env = os.environ.get("FDT_VERIFY_PALLAS")
    if env is not None:
        return env.strip().lower() not in ("", "0", "false", "no", "off")
    return jax.default_backend() == "tpu"


@hot_path(static=("use_pallas",))
def _verify_from_digest(digest, sigs, pubs, use_pallas, n_lanes):
    """Steps 1-3 and 5 shared by the message and digest entry points;
    `digest` is SHA512(R || A || M) per lane (step 4, from either the
    device SHA or the host's fdt_sha512_rpm).

    n_lanes: int32 scalar, the lanes whose verdict the caller reads (the
    batch size where it reads all).  A lane at or past it reads False on
    either path; the Pallas kernel also skips the tiles that hold none
    before it (pallas_kernel.verify_core).  The prologue here runs on
    every lane."""
    # 1. canonical s
    s_limbs = SC.from_bytes(sigs[:, 32:])
    ok = SC.is_canonical(s_limbs)

    # 3. small order A/R by encoding blocklist
    ok = ok & ~_is_small_order_enc(pubs) & ~_is_small_order_enc(sigs[:, :32])

    k_limbs = SC.reduce512(digest)
    k_digits = SC.to_signed_digits(k_limbs)
    s_digits = SC.to_signed_digits(s_limbs)

    if use_pallas:
        # steps 2+5 run fused in one Pallas kernel per batch tile
        from . import pallas_kernel

        a_y, a_sign = PT.decompress_bytes(pubs)
        r_y, r_sign = PT.decompress_bytes(sigs[:, :32])
        return ok & pallas_kernel.verify_core(
            k_digits, s_digits, a_y, a_sign, r_y, r_sign, n_lanes
        )

    # 2. decompress
    a_pt, a_ok = PT.decompress(pubs)
    r_pt, r_ok = PT.decompress(sigs[:, :32])
    ok = ok & a_ok & r_ok

    # 5. [k](-A) + [s]B == R
    neg_a_table = PT.build_neg_table9(a_pt)
    acc = PT.double_scalar_mul(k_digits, neg_a_table, s_digits)
    return ok & PT.eq_external(acc, r_pt) & (jnp.arange(ok.shape[0]) < n_lanes)


@functools.partial(jax.jit, static_argnames=("msg_len", "use_pallas"))
@hot_path(static=("msg_len", "use_pallas"))
def _verify_impl(msgs, lens, sigs, pubs, msg_len, use_pallas=False):
    del msg_len  # captured statically via msgs.shape
    # 4. k = SHA512(R || A || M) mod L, on device
    cat = jnp.concatenate([sigs[:, :32], pubs, msgs], axis=1)
    digest = _sha.sha512(cat, lens.astype(jnp.int32) + 64)
    return _verify_from_digest(digest, sigs, pubs, use_pallas, sigs.shape[0])


def verify_batch(msgs, lens, sigs, pubs):
    """Verify a batch of Ed25519 signatures.

    msgs: (B, max_len) uint8, zero-padded; lens: (B,) int byte counts;
    sigs: (B, 64) uint8; pubs: (B, 32) uint8.  Returns (B,) bool.
    """
    msgs = jnp.asarray(msgs, jnp.uint8)
    sigs = jnp.asarray(sigs, jnp.uint8)
    pubs = jnp.asarray(pubs, jnp.uint8)
    lens = jnp.asarray(lens, jnp.int32)
    return _verify_impl(
        msgs, lens, sigs, pubs, msgs.shape[1], use_pallas=_use_pallas()
    )


def _z_limbs(zbytes):
    """(B, 16) uint8 random z -> (10, B) 13-bit limbs (128 -> 130 bits)."""
    padded = jnp.concatenate(
        [zbytes, jnp.zeros(zbytes.shape[:-1] + (16,), zbytes.dtype)], axis=-1
    )
    return F.from_bytes(padded)[:10]


def _signed_digits_of_int(n: int) -> np.ndarray:
    """Host-side signed radix-16 recode (the plain-int analog of
    scalar.to_signed_digits) for compile-time scalar constants."""
    digs = []
    for _ in range(64):
        d = n & 15
        n >>= 4
        if d >= 8:
            d -= 16
            n += 1
        digs.append(d)
    assert n == 0, "scalar exceeds 64 signed radix-16 digits"
    return np.array(digs, np.int32).reshape(64, 1)


_L_DIGITS = _signed_digits_of_int(golden.L)
#: 1/2 mod p: recovers x = (n0-n1)/2, y = (n0+n1)/2 from an affine niels
#: triple (y+x, y-x, 2dxy) without re-running the decompress sqrt chain
_INV2_LIMBS = F.int_to_limbs((golden.P + 1) // 2).reshape(F.NLIMB, 1)


def _torsion_free(pts):
    """(N,) bool: each point lies in the prime-order subgroup
    ([L]P == identity), batched as one [L](-P) + [0]B dsm over
    already-decompressed extended coords.

    Why the RLC path needs this (ADVICE.md round 5, msm_kernel.py): the
    batch equation weights each R_i directly by its odd z_i, and odd
    weights can NEVER separate order-2 torsion components — two
    signatures built on R' = R + T2 have residual T2 each, and
    z1*T2 + z2*T2 = (odd+odd)*T2 = identity for EVERY z pair, so the
    bare equation deterministically accepts both (A-side torsion is
    weighted by (z*k mod L) mod 2 instead: randomized by the mod-L
    reduction, still a coin-flip accept).  Mixed-order points are the
    only source of torsion residuals; restricting the accept path to
    subgroup points removes the component entirely, after which
    random-z soundness is the standard prime-order argument.
    """
    n = pts[0].shape[-1]
    ldig = jnp.broadcast_to(jnp.asarray(_L_DIGITS), (64, n))
    acc = PT.double_scalar_mul(
        ldig, PT.build_neg_table9(pts), jnp.zeros((64, n), jnp.int32)
    )
    return PT.eq_points(acc, PT.identity(n))


def _torsion_free_pair(a_pt, r_pt):
    """(B,) bool: BOTH A_i and R_i subgroup-checked in one dsm over the
    2B stacked points.  See _torsion_free."""
    both = tuple(
        jnp.concatenate([a, r], axis=-1) for a, r in zip(a_pt, r_pt)
    )
    tf = _torsion_free(both)
    b = a_pt[0].shape[-1]
    return tf[:b] & tf[b:]


@functools.partial(jax.jit, static_argnames=("interpret",))
@hot_path(static=("interpret",))
def _verify_digest_rlc_impl(digests, sigs, pubs, zbytes, interpret=False):
    """Batch (RLC) verification: returns (lane_ok (B,), batch_ok ()).

    lane_ok is the per-lane prologue verdict (canonical s, small-order
    blocklist, decompress); batch_ok is the one RLC group equation over
    the lanes that passed the prologue AND a per-lane prime-order
    subgroup check on every included A/R ([L]P == identity,
    _torsion_free_pair).  Accept lane i iff batch_ok & lane_ok[i]; on
    !batch_ok the caller falls back to the strict per-sig kernel, so a
    mixed-order point anywhere in the batch routes the WHOLE batch to
    the strict path and the RLC accept can never diverge from it.  See
    msm_kernel.py for semantics.
    """
    from . import msm_kernel as MSM

    # prologue checks, shared with the per-sig path.  Decompress + niels
    # conversion run in a fused Pallas pass: the sqrt chain is ~250
    # sequential field ops, and one kernel keeps their intermediates in
    # VMEM instead of leaving the fusion boundaries to XLA
    s_limbs = SC.from_bytes(sigs[:, 32:])
    ok = SC.is_canonical(s_limbs)
    ok = ok & ~_is_small_order_enc(pubs) & ~_is_small_order_enc(sigs[:, :32])
    a_y, a_sign = PT.decompress_bytes(pubs)
    r_y, r_sign = PT.decompress_bytes(sigs[:, :32])
    an3_raw, rn3_raw, dc_ok = MSM.decompress_niels(
        a_y, a_sign, r_y, r_sign, interpret=interpret
    )
    ok = ok & dc_ok
    okm = ok[None, :]

    k_limbs = SC.reduce512(digests)
    z10 = _z_limbs(zbytes)
    c_limbs = SC.mulmod(z10, k_limbs)  # z*k mod L
    z20 = jnp.concatenate([z10, jnp.zeros_like(z10)], axis=0)
    cdig = jnp.where(okm, SC.to_signed_digits(c_limbs), 0)
    zdig = jnp.where(okm, SC.to_signed_digits(z20)[:33], 0)

    su = jnp.where(okm, SC.mulmod(z10, s_limbs), 0)
    u = SC.summod(su)  # sum z_i s_i mod L over included lanes
    udig = SC.to_signed_digits(u)  # (64, 1)

    def mask_niels(n3):
        ident = jnp.concatenate(
            PT.identity_niels_affine(n3.shape[-1]), axis=0
        )
        return jnp.where(okm, n3, ident)

    batch_ok = MSM.msm_check(
        cdig, zdig, mask_niels(an3_raw), mask_niels(rn3_raw), udig,
        interpret=interpret,
    )
    # cofactor-gap closure: the batch accept is only sound over the
    # prime-order subgroup; a mixed-order A or R on any included lane
    # fails the batch so the caller's strict per-sig fallback decides.
    # (Excluded lanes — !ok — are already masked to the identity and
    # cannot poison the equation, so their torsion is irrelevant.)
    # The gate's extended coords are RECONSTRUCTED from the niels forms
    # the fused Pallas pass already computed — affine niels is
    # (y+x, y-x, 2dxy), so x = (n0-n1)/2 and y = (n0+n1)/2, two constant
    # muls per point — rather than re-running the decompress sqrt chain
    # (~250 sequential field ops, the dominant prologue cost) over the
    # 2B points.  Garbage on !dc_ok lanes is fine: masked via ~ok below.
    n3 = jnp.concatenate([an3_raw, rn3_raw], axis=-1)  # (3*NL, 2B)
    ypx, ymx = n3[: F.NLIMB], n3[F.NLIMB : 2 * F.NLIMB]
    inv2 = jnp.asarray(_INV2_LIMBS)
    x = F.carry1(F.mul_rr(inv2, F.carry1(ypx - ymx)))
    y = F.carry1(F.mul_rr(inv2, F.carry1(ypx + ymx)))
    z = jnp.broadcast_to(jnp.asarray(F.c("ONE")), x.shape).astype(x.dtype)
    tf2 = _torsion_free((x, y, z, F.mul_rr(x, y)))
    b = ok.shape[0]
    batch_ok = batch_ok & jnp.all((tf2[:b] & tf2[b:]) | ~ok)
    return ok, batch_ok


def _use_rlc() -> bool:
    """Opt-in (FDT_VERIFY_RLC=1).  The bucket-MSM batch path saves curve
    operations but pays a bucket read-modify-write per update, and in
    its one A/B against the per-sig Strauss kernel it lost — so per-sig
    stays the default.  Not measured on this installation; ROADMAP
    S3(b)/D2 decide whether the path stays."""
    import os

    env = os.environ.get("FDT_VERIFY_RLC")
    if env is not None:
        return env.strip().lower() not in ("", "0", "false", "no", "off")
    return False


def verify_batch_digest_rlc(digests, sigs, pubs, zbytes=None):
    """Batch-verify from precomputed k-digests: RLC accept fast path with
    strict per-sig fallback whenever the batch equation fails.

    zbytes: (B, 16) uint8 per-batch secret randomness (odd z enforced
    here); defaults to os.urandom.  Returns (B,) bool.
    """
    import os

    digests = jnp.asarray(digests, jnp.uint8)
    sigs = jnp.asarray(sigs, jnp.uint8)
    pubs = jnp.asarray(pubs, jnp.uint8)
    B = sigs.shape[0]
    if zbytes is None:
        zbytes = np.frombuffer(os.urandom(16 * B), np.uint8).reshape(B, 16)
    zbytes = np.asarray(zbytes).copy()
    zbytes[:, 0] |= 1  # odd z: no 8-torsion residual survives one lane
    lane_ok, batch_ok = _verify_digest_rlc_impl(
        digests, sigs, pubs, jnp.asarray(zbytes),
        # Pallas interpret mode off-TPU (tests); Mosaic on TPU
        interpret=jax.default_backend() != "tpu",
    )
    if bool(np.asarray(batch_ok)):
        return lane_ok
    return verify_batch_digest(digests, sigs, pubs)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
@hot_path(static=("use_pallas",))
def _verify_digest_impl(digests, sigs, pubs, n_lanes, use_pallas=False):
    # step 4's SHA512 was done on the host (fdt_sha512_rpm inside
    # fdt_verify_expand); everything else is shared
    return _verify_from_digest(digests, sigs, pubs, use_pallas, n_lanes)


def verify_batch_digest(digests, sigs, pubs, n_lanes=None):
    """Verify from precomputed k-digests = SHA512(R || A || M).

    The host computes the digests during lane expansion so the device is
    shipped 64 bytes per lane instead of the whole message — the right
    trade whenever host→device bandwidth, not device compute, bounds the
    pipeline (which of the two bounds it on this installation is not
    measured; ROADMAP D4).  digests: (B, 64); sigs: (B, 64);
    pubs: (B, 32).  Returns (B,) bool.

    n_lanes: the rows before it are the batch, the rest padding: those
    read False, and on the chip the kernel skips the tiles they fill.  It
    is an operand of the one program, absent or not.  A caller that jits
    this function passes it as an int32 ARRAY every time
    (`np.asarray(n, np.int32)`): a Python int is weakly typed and would
    trace a second program."""
    digests = jnp.asarray(digests, jnp.uint8)
    sigs = jnp.asarray(sigs, jnp.uint8)
    pubs = jnp.asarray(pubs, jnp.uint8)
    if n_lanes is None:
        n_lanes = sigs.shape[0]
    return _verify_digest_impl(
        digests, sigs, pubs, jnp.asarray(n_lanes, jnp.int32),
        use_pallas=_use_pallas(),
    )


def verify_batch_digest_on(device):
    """verify_batch_digest pinned to one local device: a per-domain
    executable for the verify tile's device pool (tiles/verify.py).

    Inputs are committed to `device` with an explicit device_put and the
    jitted kernel follows their placement, so each pool domain compiles
    and runs on its own accelerator.  The explicit put is also what buys
    the pool its transfer/compute overlap: a put onto one device can
    progress while another device (or this one's previous batch)
    executes — the premise the scale-out design rests on (ROADMAP R4
    measures it).  jax.jit caches per placement, and so does the
    persistent cache (its key covers the device assignment): every device
    pays its own lowering and, cold, its own compile: four v5e devices warmed
    in 346.7 s cold, one in 56.2 s (PERF.md section 6, my chip runs, PR 28)."""
    use_pallas = _use_pallas()

    def fn(digests, sigs, pubs, n_lanes=None):
        d = jax.device_put(jnp.asarray(digests, jnp.uint8), device)
        s = jax.device_put(jnp.asarray(sigs, jnp.uint8), device)
        p = jax.device_put(jnp.asarray(pubs, jnp.uint8), device)
        if n_lanes is None:
            n_lanes = s.shape[0]
        n = jax.device_put(np.asarray(n_lanes, np.int32), device)
        return _verify_digest_impl(d, s, p, n, use_pallas=use_pallas)

    fn.device = device
    #: the jit object whose cache holds this fn's compiled programs
    #: (tiles/verify.py counts them: VerifyTile._program_count)
    fn.jitted = _verify_digest_impl
    return fn
