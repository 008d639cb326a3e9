"""Pallas TPU kernel for the Ed25519 verify hot loop.

The double-scalar-mul [k](-A) + [s]B is ~90% of verify time: a 64-iteration
loop of field multiplies over (NLIMB, B) int32 limb arrays.  Under plain XLA
each step's intermediates round-trip through HBM scheduling; here the whole
loop runs in ONE kernel per batch tile with the accumulator, the per-lane
signed-window table for -A, and every temporary resident in VMEM — the
memory locality the reference gets from AVX-512 register blocking
(avx512/fd_r43x6_ge.c) and wiredancer gets from on-die BRAM, done the TPU
way.

The kernel body simply calls the existing point.py/field.py batch code on
VMEM-resident values: the math is written once and runs under XLA (tests,
CPU interpret mode) or Mosaic (TPU) unchanged.

Grid = batch tiles; Pallas pipelines each tile's HBM→VMEM input DMA behind
the previous tile's compute.  The tiles run one after the other on the
chip's one core, so a batch costs its tile count; the number of real lanes
rides along as a traced scalar (`n_lanes`) and bounds the grid, which ends
with the last tile that holds a real lane: one compiled program whatever
the count, and a batch padded to the compiled shape costs only the tiles
that hold a real lane.  The op-count choices in point.py follow one
cost model: the kernel is int32 VPU work and is bound by how fast the VPU
issues multiplies, so multiplies are what every variant counts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from firedancer_tpu.utils.hotpath import hot_path

from . import TILE  # lanes per grid step
from . import field as F
from . import point as PT

NL = F.NLIMB

# array constants the kernel math needs, packed into one (rows, TILE) input
# (Pallas kernels cannot capture array constants; batch-dim-1 elements would
# force (1,1)->(sublane,lane) broadcasts Mosaic can't lower, so every
# constant arrives already lane-wide)
_CONST_NAMES = ("ONE", "D2", "D", "SQRT_M1", "P32", "P")


def _pack_consts():
    import numpy as np

    parts = [
        np.tile(F._CONST_TABLE[n].reshape(-1, 1), (1, TILE))
        for n in _CONST_NAMES
    ]
    parts.append(
        np.tile(F._CONST_TABLE["B_TABLE9"].reshape(-1, 1), (1, TILE))
    )
    return np.ascontiguousarray(np.concatenate(parts, axis=0), dtype=np.int32)


def _unpack_consts(c_ref):
    out = {}
    off = 0
    for n in _CONST_NAMES:
        out[n] = c_ref[off : off + NL, :]
        off += NL
    out["B_TABLE9"] = c_ref[off : off + 9 * 3 * NL, :].reshape(9, 3, NL, TILE)
    return out


def _verify_core_kernel(c_ref, k_ref, s_ref, ay_ref, ry_ref, ok_ref):
    """Decompress A and R, run the signed-window Strauss double-scalar-mul,
    and compare against R — the entire verify hot path after byte
    parsing/hashing/small-order blocklisting, fused over one VMEM-resident
    batch tile.

    ay_ref/ry_ref rows: NL y-limbs then 1 sign row.  k_ref/s_ref: (64, B)
    signed digits in [-8, 7]."""
    with F.const_scope(_unpack_consts(c_ref)):
        a_pt, a_ok = PT.decompress_limbs(ay_ref[:NL, :], ay_ref[NL : NL + 1, :])
        r_pt, r_ok = PT.decompress_limbs(ry_ref[:NL, :], ry_ref[NL : NL + 1, :])
        ok = a_ok & r_ok

        neg_a_table = PT.build_neg_table9(a_pt)
        b_table = F.c("B_TABLE9")

        # the double_scalar_mul loop, 8-way unrolled: one aligned (8, B)
        # digit-chunk read per outer step, then 8 statically-sliced body
        # copies.  Measured in round 4 (script in git history): the
        # per-iteration loop boundary costs ~5.5 ns/iter/lane (spill +
        # scheduling barrier); unrolling 8x removes 7/8 of it (1.12x),
        # and 16x/32x measure the same — 8x keeps Mosaic compile ~74 s.
        # The dynamic digit reads themselves are free (noread == base).
        def outer(c, acc):
            base = pl.multiple_of(56 - 8 * c, 8)  # chunks from the top
            k8 = k_ref[pl.ds(base, 8), :]
            s8 = s_ref[pl.ds(base, 8), :]
            for r in range(7, -1, -1):
                kd = jnp.squeeze(k8[r:r + 1, :], axis=0)
                sd = jnp.squeeze(s8[r:r + 1, :], axis=0)
                acc = PT.double(acc, with_t=False)
                acc = PT.double(acc, with_t=False)
                acc = PT.double(acc, with_t=False)
                acc = PT.double(acc, with_t=True)
                acc = PT.add_niels(
                    acc, PT.lookup9(neg_a_table, kd), with_t=True
                )
                acc = PT.add_niels_affine(
                    acc, PT.lookup9_affine(b_table, sd), with_t=False
                )
            return acc

        acc = jax.lax.fori_loop(0, 8, outer, PT.identity(TILE))
        ok = ok & PT.eq_external(acc, r_pt)
        ok_ref[0, :] = ok.astype(jnp.int32)


def _lane_count(n_lanes, b):
    """The count as an int32 scalar operand; absent, `b`."""
    return jnp.asarray(b if n_lanes is None else n_lanes, jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
@hot_path(static=("interpret",))
def verify_core(
    k_digits, s_digits, a_y, a_sign, r_y, r_sign, n_lanes=None, *,
    interpret=False,
):
    """Fused decompress + ([k](-A) + [s]B == R).

    k_digits, s_digits: (64, B) int32 signed radix-16 digits in [-8, 7]
    (scalar.to_signed_digits); a_y, r_y: (NL, B) y limbs; a_sign, r_sign:
    (1, B) sign bits (from point.decompress_bytes).  B is padded to a TILE
    multiple internally.  Small-order rejection happens in the caller's
    prologue (byte blocklist).  Returns (B,) bool.

    n_lanes: int32 scalar, the lanes whose verdict the caller reads (the
    rest is padding); absent, B.  It is an operand, never a static
    argument, so its value picks no program.  A lane before it gets the
    verdict it gets without the count, bit for bit; a lane at or past it
    reads False, and a whole tile of such lanes is never started (at
    least one tile runs).
    """
    B = k_digits.shape[-1]
    Bp = ((B + TILE - 1) // TILE) * TILE
    n = _lane_count(n_lanes, B)

    def pad(x):
        return jnp.pad(x, ((0, 0), (0, Bp - B))) if Bp != B else x

    a_cat = pad(jnp.concatenate([a_y, a_sign], axis=0))
    r_cat = pad(jnp.concatenate([r_y, r_sign], axis=0))
    k_n = pad(k_digits)
    s_n = pad(s_digits)

    consts = jnp.asarray(_pack_consts())
    # the grid ends with the last tile that holds a real lane.  Its bound
    # is a traced scalar: one program, whatever the count.  (The
    # interpreter takes no traced bound and walks every tile.)
    tiles = Bp // TILE
    grid = tiles if interpret else jnp.clip((n + TILE - 1) // TILE, 1, tiles)
    spec = lambda rows: pl.BlockSpec(  # noqa: E731
        (rows, TILE), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    const_spec = pl.BlockSpec(
        consts.shape, lambda i: (0, 0), memory_space=pltpu.VMEM
    )
    ok = pl.pallas_call(
        _verify_core_kernel,
        out_shape=jax.ShapeDtypeStruct((1, Bp), jnp.int32),
        grid=(grid,),
        in_specs=[const_spec, spec(64), spec(64), spec(NL + 1), spec(NL + 1)],
        out_specs=spec(1),
        interpret=interpret,
    )(consts, k_n, s_n, a_cat, r_cat)
    # a tile past the grid's end was never written, and padding lanes that
    # share the last tile with real ones were computed: the count, not the
    # memory or the arithmetic, is what makes a lane at or past it False
    return (ok[0, :B] != 0) & (jnp.arange(B) < n)
