"""Pallas TPU kernel for batch (random-linear-combination) verification.

Batch verification checks ONE group equation for a whole batch instead of
B double-scalar-muls:

    [sum_i z_i s_i] B  ==  sum_i [z_i k_i] A_i  +  sum_i [z_i] R_i

with per-batch secret random 128-bit odd z_i.  The right-hand side is a
2B-point multi-scalar multiplication (MSM); this module computes it
Pippenger-style, which is what makes batch verification 2-3x cheaper per
signature than the per-sig Strauss loop: bucket accumulation spends ~1
point addition per window digit and NO per-signature doublings (the
per-sig path pays 4 doublings per window — pallas_kernel.py).

TPU mapping (the part that is nothing like a CPU Pippenger):
  * Each of the TILE vector lanes owns a private 9-bucket set per window;
    a "bucket add" is one SPMD add_niels_affine plus a branchless 9-way
    gather/scatter select tree keyed on the lane's digit.  Data-dependent
    scatter becomes masked select — no serialization, no atomics.
  * The grid is (window-blocks, batch-tiles) with batch-tiles innermost:
    bucket state for WPB windows lives in the VMEM-resident output block
    across all batch tiles (TPU grids run sequentially on a core), and is
    flushed to HBM once per window-block — B/TILE revisits amortize to
    one DMA.  The A/R niels points re-stream from HBM once per
    window-block, which is what bounds VMEM instead of batch size.
  * Cross-lane reduction (sum 9*64 bucket sets over TILE lanes), the
    bucket->window combine, the Horner spine over windows, and the [u]B
    comparison are O(B^0) work and run as plain XLA on the (tiny)
    kernel output.

Verification semantics vs the per-sig path (fd_ed25519_verify parity,
/root/reference/src/ballet/ed25519/fd_ed25519_user.c:134-229): a batch
that PASSES here is accepted without per-sig dsm; any batch that fails
falls back to the strict per-sig kernel (verify.py), so honest traffic
pays ~1 bucket-add per window and adversarial traffic degrades to the
per-sig rate.  The reference's own batch API
(fd_ed25519_verify_batch_single_msg, same file :231-310) establishes
batch-with-fallback as an acceptable verify shape.

Torsion soundness: with odd z a single invalid signature always fails
the batch (odd z annihilates no 8-torsion residual), but MULTIPLE
signatures whose residuals are small-order torsion can craft residuals
that cancel in the sum — two identical order-2 residuals always do,
since odd z1 + odd z2 is even.  Such residuals require mixed-order A or
R, so the RLC accept path additionally requires every included A/R to
lie in the prime-order subgroup ([L]P == identity —
verify._torsion_free_pair); any mixed-order point fails the batch and
routes it to the strict per-sig path.  With all points subgroup-checked,
residuals live in the prime-order group and random odd 128-bit z gives
the standard soundness bound.  Regression: tests/test_msm_rlc.py
crafts the order-2 cancellation pair and asserts batch rejection.
"""

from __future__ import annotations

import functools
import os as _os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from firedancer_tpu.utils.hotpath import hot_path

from . import field as F
from . import point as PT

NL = F.NLIMB
TILE = int(_os.environ.get("FDT_MSM_TILE", "256"))
#: windows per grid block: amortizes per-grid-step overhead over 2*WPB
#: bucket adds while keeping the VMEM-resident bucket block (WPB, 720,
#: TILE) inside the scoped limit
WPB = 4
NWIN = 64  # 4-bit signed windows covering a 252-bit scalar + carry
ZWIN = 36  # windows covering a 128-bit z (33 used; padded to a WPB multiple)
ROWS = 9 * 4 * NL  # 9 buckets x extended point (X, Y, Z, T)


def _select9_rows(stack9, v):
    """stack9 (9, R, TILE), v (TILE,) in [0, 8] -> (R, TILE) selected row.

    Same branchless bit tree as point._select9, shaped for flat rows."""
    b0 = ((v & 1) != 0)[None, :]
    b1 = ((v & 2) != 0)[None, :]
    b2 = ((v & 4) != 0)[None, :]
    b3 = (v >= 8)[None, :]
    s0 = jnp.where(b0, stack9[1], stack9[0])
    s2 = jnp.where(b0, stack9[3], stack9[2])
    s4 = jnp.where(b0, stack9[5], stack9[4])
    s6 = jnp.where(b0, stack9[7], stack9[6])
    t0 = jnp.where(b1, s2, s0)
    t4 = jnp.where(b1, s6, s4)
    return jnp.where(b3, stack9[8], jnp.where(b2, t4, t0))


_DC_CONST_NAMES = ("ONE", "D2", "D", "SQRT_M1", "P32", "P")


def _pack_dc_consts():
    import numpy as np

    parts = [
        np.tile(F._CONST_TABLE[n].reshape(-1, 1), (1, TILE))
        for n in _DC_CONST_NAMES
    ]
    return np.ascontiguousarray(np.concatenate(parts, axis=0), np.int32)


def _unpack_dc_consts(c_ref):
    out = {}
    off = 0
    for n in _DC_CONST_NAMES:
        out[n] = c_ref[off : off + NL, :]
        off += NL
    return out


def _decompress_niels_kernel(c_ref, ay_ref, ry_ref, an_ref, rn_ref, ok_ref):
    """Per batch tile: decompress A and R and emit affine-niels forms +
    per-lane ok.  The sqrt exponentiation chain (~250 sequential field
    ops) is why this runs fused in Pallas: under plain XLA every
    intermediate of the chain round-trips through HBM and the prologue
    dominates the whole batch-verify path (measured round 5: 3.0 s of a
    5.3 s batch).  Same decompress math the per-sig kernel fuses
    (pallas_kernel.py)."""
    with F.const_scope(_unpack_dc_consts(c_ref)):
        a_pt, a_ok = PT.decompress_limbs(
            ay_ref[:NL, :], ay_ref[NL : NL + 1, :]
        )
        r_pt, r_ok = PT.decompress_limbs(
            ry_ref[:NL, :], ry_ref[NL : NL + 1, :]
        )
        an_ref[...] = jnp.concatenate(PT.to_niels_affine(a_pt), axis=0)
        rn_ref[...] = jnp.concatenate(PT.to_niels_affine(r_pt), axis=0)
        ok_ref[0, :] = (a_ok & r_ok).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
@hot_path(static=("interpret",))
def decompress_niels(a_y, a_sign, r_y, r_sign, *, interpret=False):
    """(y limbs, sign) x2 -> (an3 (3NL, B), rn3 (3NL, B), ok (B,)).

    Garbage niels values on !ok lanes; the caller masks them to the
    identity before the MSM (msm_check pads the same way)."""
    B = a_y.shape[-1]
    Bp = ((B + TILE - 1) // TILE) * TILE

    def pad(x):
        return jnp.pad(x, ((0, 0), (0, Bp - B))) if Bp != B else x

    a_cat = pad(jnp.concatenate([a_y, a_sign], axis=0))
    r_cat = pad(jnp.concatenate([r_y, r_sign], axis=0))
    consts = jnp.asarray(_pack_dc_consts())
    spec = lambda rows: pl.BlockSpec(  # noqa: E731
        (rows, TILE), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    an3, rn3, ok = pl.pallas_call(
        _decompress_niels_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((3 * NL, Bp), jnp.int32),
            jax.ShapeDtypeStruct((3 * NL, Bp), jnp.int32),
            jax.ShapeDtypeStruct((1, Bp), jnp.int32),
        ],
        grid=(Bp // TILE,),
        in_specs=[
            pl.BlockSpec(consts.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            spec(NL + 1),
            spec(NL + 1),
        ],
        out_specs=[spec(3 * NL), spec(3 * NL), spec(1)],
        interpret=interpret,
    )(consts, a_cat, r_cat)
    return an3[:, :B], rn3[:, :B], ok[0, :B] != 0


def _msm_kernel(one_ref, cd_ref, zd_ref, an_ref, rn_ref, out_ref):
    """One grid step: fold TILE signatures' digits for WPB windows into
    the lane-private buckets.

    out_ref block (WPB, ROWS, TILE): WPB windows x 9 buckets x (X,Y,Z,T).
    cd_ref/zd_ref (WPB, TILE) digits; an_ref/rn_ref (3*NL, TILE) affine
    niels of A_i / R_i (identity for masked lanes).
    """
    wb = pl.program_id(0)
    t = pl.program_id(1)
    w0 = wb * WPB

    one = one_ref[...]  # (NL, TILE)
    zero = jnp.zeros_like(one)

    @pl.when(t == 0)
    def _init():
        ident = jnp.concatenate([zero, one, one, zero], axis=0)  # (4NL,T)
        blk = jnp.concatenate([ident] * 9, axis=0)  # (ROWS, TILE)
        for j in range(WPB):
            out_ref[j, :, :] = blk

    def update(j, digit, niels3):
        v = jnp.abs(digit)  # (TILE,)
        neg = (digit < 0)[None, :]
        ypx = niels3[0:NL]
        ymx = niels3[NL : 2 * NL]
        t2d = niels3[2 * NL : 3 * NL]
        e = (
            jnp.where(neg, ymx, ypx),
            jnp.where(neg, ypx, ymx),
            jnp.where(neg, -t2d, t2d),
        )
        stack9 = out_ref[j, :, :].reshape(9, 4 * NL, TILE)
        cur = _select9_rows(stack9, v)  # (4NL, TILE)
        p = (
            cur[0:NL],
            cur[NL : 2 * NL],
            cur[2 * NL : 3 * NL],
            cur[3 * NL : 4 * NL],
        )
        newp = PT.add_niels_affine(p, e, with_t=True)
        new_flat = jnp.concatenate(newp, axis=0)
        # scatter-by-select: bucket 0 is the trash bucket for digit 0
        # (the add result is discarded), so masked/padded lanes cost one
        # wasted add instead of a branch
        for b in range(1, 9):
            m = (v == b)[None, :]
            old = out_ref[j, b * 4 * NL : (b + 1) * 4 * NL, :]
            out_ref[j, b * 4 * NL : (b + 1) * 4 * NL, :] = jnp.where(
                m, new_flat, old
            )

    # digit rows are read by dynamic index from the full (NWIN, TILE)
    # column block: a dynamic sublane read costs the kernel nothing it
    # would not pay for a static one, and a full-column block satisfies the
    # Mosaic (8, 128) tiling constraint where a (WPB, TILE) block cannot
    for j in range(WPB):
        d = jnp.squeeze(cd_ref[pl.ds(w0 + j, 1), :], axis=0)
        update(j, d, an_ref[...])

    @pl.when(wb < ZWIN // WPB)
    def _():
        for j in range(WPB):
            d = jnp.squeeze(zd_ref[pl.ds(w0 + j, 1), :], axis=0)
            update(j, d, rn_ref[...])


def _tree_reduce_lanes(coords):
    """Point coords (NL, W, 9, LANES) -> (NL, W, 9) by pairwise adds.

    Point/field ops broadcast their (NL, 1) constants over ONE trailing
    batch axis, so each level flattens (W, 9, half) to a single batch dim
    for the add and restores the shape after."""
    shape = coords[0].shape[1:3]
    while coords[0].shape[-1] > 1:
        half = coords[0].shape[-1] // 2
        a = tuple(c[..., :half].reshape(NL, -1) for c in coords)
        b = tuple(c[..., half:].reshape(NL, -1) for c in coords)
        coords = tuple(
            c.reshape((NL,) + shape + (half,)) for c in PT.add(a, b)
        )
    return tuple(jnp.squeeze(c, axis=-1) for c in coords)


@functools.partial(jax.jit, static_argnames=("interpret",))
@hot_path(static=("interpret",))
def msm_check(cdig, zdig, an3, rn3, u_digits, *, interpret=False):
    """Does  sum [c_i]A_i + sum [z_i]R_i  ==  [u]B ?  -> () bool.

    cdig (64, B) signed digits of c_i = z_i k_i mod L; zdig (<=ZWIN, B)
    signed digits of z_i; an3/rn3 (3*NL, B) affine niels of A_i/R_i
    (identity niels + zero digits for lanes excluded from the batch);
    u_digits (64, 1) digits of u = sum z_i s_i mod L.
    """
    B = cdig.shape[-1]
    Bp = ((B + TILE - 1) // TILE) * TILE
    nt = Bp // TILE

    def padd(x):  # digit arrays: zero digits are harmless (trash bucket)
        return jnp.pad(x, ((0, 0), (0, Bp - B))) if Bp != B else x

    def padn(x):  # niels arrays: pad with the identity (1, 1, 0)
        if Bp == B:
            return x
        one = jnp.broadcast_to(F.c("ONE"), (NL, Bp - B)).astype(x.dtype)
        z = jnp.zeros((NL, Bp - B), x.dtype)
        return jnp.concatenate(
            [x, jnp.concatenate([one, one, z], axis=0)], axis=-1
        )

    zdig = jnp.pad(zdig, ((0, ZWIN - zdig.shape[0]), (0, 0)))
    cdig, zdig = padd(cdig), padd(zdig)
    an3, rn3 = padn(an3), padn(rn3)

    one_tile = jnp.broadcast_to(F.c("ONE"), (NL, TILE)).astype(jnp.int32)
    grid = (NWIN // WPB, nt)
    buckets = pl.pallas_call(
        _msm_kernel,
        out_shape=jax.ShapeDtypeStruct((NWIN, ROWS, TILE), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((NL, TILE), lambda w, t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((NWIN, TILE), lambda w, t: (0, t),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((ZWIN, TILE), lambda w, t: (0, t),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3 * NL, TILE), lambda w, t: (0, t),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3 * NL, TILE), lambda w, t: (0, t),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((WPB, ROWS, TILE), lambda w, t: (w, 0, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(one_tile, cdig, zdig, an3, rn3)

    # ---- XLA finalization: O(windows * buckets) point ops ----
    bk = buckets.reshape(NWIN, 9, 4, NL, TILE)
    coords = tuple(
        jnp.transpose(bk[:, :, c, :, :], (2, 0, 1, 3)) for c in range(4)
    )  # each (NL, NWIN, 9, TILE)
    coords = _tree_reduce_lanes(coords)  # (NL, NWIN, 9)

    # bucket combine: sum_v v * bucket_v  ==  descending running sums
    s = tuple(c[:, :, 8] for c in coords)
    t = s
    for v in range(7, 0, -1):
        s = PT.add(s, tuple(c[:, :, v] for c in coords))
        t = PT.add(t, s)
    # t: window sums W_w, batch axis (NWIN,)

    # Horner over windows, high to low: acc = 16*acc + W_w
    def body(j, acc):
        idx = NWIN - 1 - j
        acc = PT.double(acc, with_t=False)
        acc = PT.double(acc, with_t=False)
        acc = PT.double(acc, with_t=False)
        acc = PT.double(acc, with_t=True)
        w = tuple(
            jax.lax.dynamic_slice_in_dim(c, idx, 1, axis=1) for c in t
        )
        return PT.add(acc, w)

    acc = jax.lax.fori_loop(0, NWIN, body, PT.identity(1))
    ub = PT.scalar_mul_base(u_digits)
    return jnp.squeeze(PT.eq_points(acc, ub), axis=0)
