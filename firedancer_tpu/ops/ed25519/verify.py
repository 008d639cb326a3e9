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
