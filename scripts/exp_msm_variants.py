"""A/B experiments on the batch-verification MSM kernel.

Methodology follows scripts/exp_dsm_variants.py (round 4): compare
whole-kernel deltas at large B, where per-execution overhead is a small
share; sync is np.asarray.  Each variant rebuilds the kernel with one lever changed:

  base      production msm_kernel.msm_check
  noscatter every update adds into bucket 1 (no gather/scatter selects)
  noadd     gather/scatter only, accumulator add skipped
  wpbN      windows-per-block sweep (per-grid-step overhead share)
  nozd      A updates only (R/z stream disabled) — isolates stream cost

Run: python scripts/exp_msm_variants.py [B_log2]
"""

from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from firedancer_tpu.ops.ed25519 import field as F
from firedancer_tpu.ops.ed25519 import msm_kernel as M
from firedancer_tpu.ops.ed25519 import point as PT
from firedancer_tpu.utils.hostdev import enable_compilation_cache

NL = F.NLIMB
TILE = M.TILE
NWIN = M.NWIN
ZWIN = M.ZWIN
ROWS = M.ROWS


def make_kernel_nocat(wpb: int, tile: int):
    """Coordinate-wise update: no reshape/concatenate of the bucket
    stack — gather/scatter run per coord on (9, NL, tile) slices, so
    Mosaic never materializes an (80, tile) flat copy."""

    def kernel(one_ref, cd_ref, zd_ref, an_ref, rn_ref, out_ref):
        wb = pl.program_id(0)
        t = pl.program_id(1)
        w0 = wb * wpb
        one = one_ref[...]
        zero = jnp.zeros_like(one)

        @pl.when(t == 0)
        def _init():
            for j in range(wpb):
                for b in range(9):
                    base = b * 4 * NL
                    out_ref[j, base : base + NL, :] = zero
                    out_ref[j, base + NL : base + 2 * NL, :] = one
                    out_ref[j, base + 2 * NL : base + 3 * NL, :] = one
                    out_ref[j, base + 3 * NL : base + 4 * NL, :] = zero

        def sel(j, coord, v):
            """Gather coord c of the v-selected bucket: tree over 9."""
            ent = [
                out_ref[j, b * 4 * NL + coord * NL :
                        b * 4 * NL + (coord + 1) * NL, :]
                for b in range(9)
            ]
            b0 = ((v & 1) != 0)[None, :]
            b1 = ((v & 2) != 0)[None, :]
            b2 = ((v & 4) != 0)[None, :]
            b3 = (v >= 8)[None, :]
            s0 = jnp.where(b0, ent[1], ent[0])
            s2 = jnp.where(b0, ent[3], ent[2])
            s4 = jnp.where(b0, ent[5], ent[4])
            s6 = jnp.where(b0, ent[7], ent[6])
            t0 = jnp.where(b1, s2, s0)
            t4 = jnp.where(b1, s6, s4)
            return jnp.where(b3, ent[8], jnp.where(b2, t4, t0))

        def update(j, digit, niels3):
            v = jnp.abs(digit)
            neg = (digit < 0)[None, :]
            ypx = niels3[0:NL]
            ymx = niels3[NL : 2 * NL]
            t2d = niels3[2 * NL : 3 * NL]
            e = (
                jnp.where(neg, ymx, ypx),
                jnp.where(neg, ypx, ymx),
                jnp.where(neg, -t2d, t2d),
            )
            p = tuple(sel(j, c, v) for c in range(4))
            newp = PT.add_niels_affine(p, e, with_t=True)
            for b in range(1, 9):
                m = (v == b)[None, :]
                for c in range(4):
                    base = b * 4 * NL + c * NL
                    old = out_ref[j, base : base + NL, :]
                    out_ref[j, base : base + NL, :] = jnp.where(
                        m, newp[c], old
                    )

        for j in range(wpb):
            d = jnp.squeeze(cd_ref[pl.ds(w0 + j, 1), :], axis=0)
            update(j, d, an_ref[...])

        @pl.when(wb < ZWIN // wpb)
        def _():
            for j in range(wpb):
                d = jnp.squeeze(zd_ref[pl.ds(w0 + j, 1), :], axis=0)
                update(j, d, rn_ref[...])

    @functools.partial(jax.jit, static_argnames=())
    def run(cdig, zdig, an3, rn3):
        B = cdig.shape[-1]
        nt = B // tile
        one_tile = jnp.broadcast_to(F.c("ONE"), (NL, tile)).astype(
            jnp.int32
        )
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((NWIN, ROWS, tile), jnp.int32),
            grid=(NWIN // wpb, nt),
            in_specs=[
                pl.BlockSpec((NL, tile), lambda w, t: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((NWIN, tile), lambda w, t: (0, t),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((ZWIN, tile), lambda w, t: (0, t),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((3 * NL, tile), lambda w, t: (0, t),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((3 * NL, tile), lambda w, t: (0, t),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(
                (wpb, ROWS, tile), lambda w, t: (w, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            interpret=False,
        )(one_tile, cdig, zdig, an3, rn3)

    return run


def make_kernel(wpb: int, scatter: bool, do_add: bool, with_z: bool):
    def kernel(one_ref, cd_ref, zd_ref, an_ref, rn_ref, out_ref):
        wb = pl.program_id(0)
        t = pl.program_id(1)
        w0 = wb * wpb
        one = one_ref[...]
        zero = jnp.zeros_like(one)

        @pl.when(t == 0)
        def _init():
            ident = jnp.concatenate([zero, one, one, zero], axis=0)
            blk = jnp.concatenate([ident] * 9, axis=0)
            for j in range(wpb):
                out_ref[j, :, :] = blk

        def update(j, digit, niels3):
            v = jnp.abs(digit)
            neg = (digit < 0)[None, :]
            ypx = niels3[0:NL]
            ymx = niels3[NL : 2 * NL]
            t2d = niels3[2 * NL : 3 * NL]
            e = (
                jnp.where(neg, ymx, ypx),
                jnp.where(neg, ypx, ymx),
                jnp.where(neg, -t2d, t2d),
            )
            if scatter:
                stack9 = out_ref[j, :, :].reshape(9, 4 * NL, TILE)
                cur = M._select9_rows(stack9, v)
            else:
                cur = out_ref[j, 4 * NL : 8 * NL, :]
            p = (
                cur[0:NL],
                cur[NL : 2 * NL],
                cur[2 * NL : 3 * NL],
                cur[3 * NL : 4 * NL],
            )
            if do_add:
                newp = PT.add_niels_affine(p, e, with_t=True)
            else:
                newp = (p[0] + e[0], p[1] + e[1], p[2] + e[2], p[3])
            new_flat = jnp.concatenate(newp, axis=0)
            if scatter:
                for b in range(1, 9):
                    m = (v == b)[None, :]
                    old = out_ref[j, b * 4 * NL : (b + 1) * 4 * NL, :]
                    out_ref[j, b * 4 * NL : (b + 1) * 4 * NL, :] = (
                        jnp.where(m, new_flat, old)
                    )
            else:
                out_ref[j, 4 * NL : 8 * NL, :] = new_flat

        for j in range(wpb):
            d = jnp.squeeze(cd_ref[pl.ds(w0 + j, 1), :], axis=0)
            update(j, d, an_ref[...])

        if with_z:
            @pl.when(wb < ZWIN // wpb)
            def _():
                for j in range(wpb):
                    d = jnp.squeeze(zd_ref[pl.ds(w0 + j, 1), :], axis=0)
                    update(j, d, rn_ref[...])

    @functools.partial(jax.jit, static_argnames=())
    def run(cdig, zdig, an3, rn3):
        B = cdig.shape[-1]
        nt = B // TILE
        one_tile = jnp.broadcast_to(F.c("ONE"), (NL, TILE)).astype(
            jnp.int32
        )
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((NWIN, ROWS, TILE), jnp.int32),
            grid=(NWIN // wpb, nt),
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
            out_specs=pl.BlockSpec(
                (wpb, ROWS, TILE), lambda w, t: (w, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            interpret=False,
        )(one_tile, cdig, zdig, an3, rn3)

    return run


def main() -> None:
    enable_compilation_cache()
    blog = int(sys.argv[1]) if len(sys.argv) > 1 else 17
    B = 1 << blog
    rng = np.random.default_rng(0)
    cdig = rng.integers(-8, 8, (NWIN, B)).astype(np.int32)
    zdig = rng.integers(-8, 8, (ZWIN, B)).astype(np.int32)
    # valid points: identity niels everywhere keeps the field math honest
    one = np.asarray(F.ONE).reshape(NL, 1).astype(np.int32)
    ident = np.concatenate(
        [np.tile(one, (1, B)), np.tile(one, (1, B)),
         np.zeros((NL, B), np.int32)], axis=0,
    )
    args = tuple(
        jax.device_put(x) for x in (cdig, zdig, ident, ident.copy())
    )

    import os

    names = os.environ.get(
        "FDT_MSM_VARIANTS", "base,noscatter,noadd,nozd,wpb1,wpb2,wpb8"
    ).split(",")
    all_variants = {
        "base": dict(wpb=4, scatter=True, do_add=True, with_z=True),
        "noscatter": dict(wpb=4, scatter=False, do_add=True, with_z=True),
        "noadd": dict(wpb=4, scatter=True, do_add=False, with_z=True),
        "nozd": dict(wpb=4, scatter=True, do_add=True, with_z=False),
        "wpb1": dict(wpb=1, scatter=True, do_add=True, with_z=True),
        "wpb2": dict(wpb=2, scatter=True, do_add=True, with_z=True),
        "wpb8": dict(wpb=8, scatter=True, do_add=True, with_z=True),
        "wpb16": dict(wpb=16, scatter=True, do_add=True, with_z=True),
    }
    special = {
        "nocat": lambda: make_kernel_nocat(4, 256),
        "nocat512": lambda: make_kernel_nocat(2, 512),
        "nocat512w4": lambda: make_kernel_nocat(4, 512),
    }
    for name in names:
        try:
            if name in special:
                cfg = {"wpb": 0}
                fn = special[name]()
            else:
                cfg = all_variants[name]
                fn = make_kernel(**cfg)
            t0 = time.perf_counter()
            out = fn(*args)
            np.asarray(out[:1, :1, :1])
            compile_s = time.perf_counter() - t0
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                out = fn(*args)
                np.asarray(out[:1, :1, :1])
                best = min(best, time.perf_counter() - t0)
            print(
                f"{name:10s} wpb={cfg['wpb']:2d} best={best*1e3:8.1f} ms"
                f"  ({best/B*1e9:6.1f} ns/sig)  compile={compile_s:.0f}s",
                flush=True,
            )
        except Exception as e:  # noqa: BLE001 — survey must survive OOMs
            print(f"{name:10s} FAILED: {str(e)[:160]}", flush=True)


if __name__ == "__main__":
    main()
