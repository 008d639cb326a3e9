"""A/B experiments on the verify kernel's dsm loop (round 4).

Round-3 profile: dsm loop measures ~35 ns/iter/lane vs ~27 predicted from
component microbenches.  Suspects: the two dynamic VMEM digit reads per
iteration (k_ref[pl.ds(idx,1),:]), the table lookups, loop overhead.

Variants (all run the REAL verify math over many grid tiles so the ~110 ms
fixed execution overhead is amortized; `ok` lanes verify correctness):
  base      — current kernel body (dynamic per-iteration digit reads)
  noread    — digits derived from the loop counter (no VMEM read at all;
              still loop-variant so lookups can't be hoisted).  ok is
              garbage by construction; timing-only.
  packed    — digits packed 8-per-int32-nibble in (8,B) rows, read ONCE
              into registers; per-iteration extraction = 3-level
              scalar-conditioned row select + shift + mask
  chunk8    — one dynamic (8,B) read per 8 iterations, inner 8 rows static

Timing rules: np.asarray sync on a scalar reduction, distinct
(lane-rolled) buffers per rep.
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from firedancer_tpu.ops.ed25519 import field as F
from firedancer_tpu.ops.ed25519 import point as PT
from firedancer_tpu.ops.ed25519 import scalar as SC
from firedancer_tpu.ops.ed25519 import golden
from firedancer_tpu.ops.ed25519.pallas_kernel import (
    TILE, _pack_consts, _unpack_consts, NL,
)

BTOT = int(__import__("os").environ.get("FDT_EXP_B", str(128 * 1024)))


def sync(x):
    return np.asarray(jnp.max(x))


# ---------------------------------------------------------------------------
# digit packing helpers
# ---------------------------------------------------------------------------


def pack_digits(d):
    """(64, B) int32 in [-8,7] -> (8, B) int32; digit j sits in bits
    4*(j%8) of row j//8."""
    nib = (d & 0xF).astype(np.uint64)
    rows = []
    for r in range(8):
        w = np.zeros(d.shape[1], np.uint64)
        for j in range(8):
            w |= nib[8 * r + j] << (4 * j)
        rows.append(w)
    return np.stack(rows).astype(np.uint32).view(np.int32)


def unpack_digit(packed_rows, idx):
    """packed_rows: list of 8 (1,B) int32 values; idx: traced scalar in
    [0,64) -> (B,) digit in [-8,7]."""
    r = idx // 8
    row = packed_rows[0]
    for i in range(1, 8):
        row = jnp.where(r == i, packed_rows[i], row)
    sh = (4 * (idx % 8)).astype(jnp.int32)
    nib = jax.lax.shift_right_logical(
        row, jnp.broadcast_to(sh, row.shape)
    ) & 0xF
    d = ((nib + 8) & 0xF) - 8
    return jnp.squeeze(d, axis=0)


# ---------------------------------------------------------------------------
# kernel variants
# ---------------------------------------------------------------------------


def _body(acc, kd, sd, neg_a_table, b_table):
    acc = PT.double(acc, with_t=False)
    acc = PT.double(acc, with_t=False)
    acc = PT.double(acc, with_t=False)
    acc = PT.double(acc, with_t=True)
    acc = PT.add_niels(acc, PT.lookup9(neg_a_table, kd), with_t=True)
    acc = PT.add_niels_affine(acc, PT.lookup9_affine(b_table, sd), with_t=False)
    return acc


def make_kernel(variant):
    def kern(c_ref, k_ref, s_ref, ay_ref, ry_ref, ok_ref):
        with F.const_scope(_unpack_consts(c_ref)):
            a_pt, a_ok = PT.decompress_limbs(ay_ref[:NL, :], ay_ref[NL:NL + 1, :])
            r_pt, r_ok = PT.decompress_limbs(ry_ref[:NL, :], ry_ref[NL:NL + 1, :])
            ok = a_ok & r_ok
            neg_a_table = PT.build_neg_table9(a_pt)
            b_table = F.c("B_TABLE9")

            if variant == "base":
                def body(j, acc):
                    idx = 63 - j
                    kd = jnp.squeeze(k_ref[pl.ds(idx, 1), :], axis=0)
                    sd = jnp.squeeze(s_ref[pl.ds(idx, 1), :], axis=0)
                    return _body(acc, kd, sd, neg_a_table, b_table)
                acc = jax.lax.fori_loop(0, 64, body, PT.identity(TILE))

            elif variant == "noread":
                k0 = jnp.squeeze(k_ref[0:1, :], axis=0)
                def body(j, acc):
                    kd = jnp.clip(k0 + j % 16 - 8, -8, 7)
                    sd = jnp.clip(k0 + (j + 5) % 16 - 8, -8, 7)
                    return _body(acc, kd, sd, neg_a_table, b_table)
                acc = jax.lax.fori_loop(0, 64, body, PT.identity(TILE))

            elif variant == "packed":
                krows = [k_ref[i:i + 1, :] for i in range(8)]
                srows = [s_ref[i:i + 1, :] for i in range(8)]
                def body(j, acc):
                    idx = 63 - j
                    kd = unpack_digit(krows, idx)
                    sd = unpack_digit(srows, idx)
                    return _body(acc, kd, sd, neg_a_table, b_table)
                acc = jax.lax.fori_loop(0, 64, body, PT.identity(TILE))

            elif variant.startswith("chunk"):
                n = int(variant[5:])
                def outer(c, acc):
                    base = pl.multiple_of(64 - n - n * c, 8)  # top-down
                    k8 = k_ref[pl.ds(base, n), :]
                    s8 = s_ref[pl.ds(base, n), :]
                    for r in range(n - 1, -1, -1):
                        kd = jnp.squeeze(k8[r:r + 1, :], axis=0)
                        sd = jnp.squeeze(s8[r:r + 1, :], axis=0)
                        acc = _body(acc, kd, sd, neg_a_table, b_table)
                    return acc
                acc = jax.lax.fori_loop(0, 64 // n, outer, PT.identity(TILE))

            elif variant == "unroll64":
                acc = PT.identity(TILE)
                for idx in range(63, -1, -1):
                    kd = jnp.squeeze(k_ref[idx:idx + 1, :], axis=0)
                    sd = jnp.squeeze(s_ref[idx:idx + 1, :], axis=0)
                    acc = _body(acc, kd, sd, neg_a_table, b_table)
            else:
                raise ValueError(variant)

            ok = ok & PT.eq_external(acc, r_pt)
            ok_ref[0, :] = ok.astype(jnp.int32)
    return kern


def build_fn(variant, krows):
    consts = jnp.asarray(_pack_consts())
    spec = lambda rows: pl.BlockSpec((rows, TILE), lambda i: (0, i),
                                     memory_space=pltpu.VMEM)
    const_spec = pl.BlockSpec(consts.shape, lambda i: (0, 0),
                              memory_space=pltpu.VMEM)
    def fn(k, s, a, r):
        return pl.pallas_call(
            make_kernel(variant),
            out_shape=jax.ShapeDtypeStruct((1, k.shape[1]), jnp.int32),
            grid=(k.shape[1] // TILE,),
            in_specs=[const_spec, spec(krows), spec(krows),
                      spec(NL + 1), spec(NL + 1)],
            out_specs=spec(1),
        )(consts, k, s, a, r)
    return jax.jit(fn)


def main():
    print(f"devices: {jax.devices()}  TILE={TILE}  BTOT={BTOT}", flush=True)
    rng = np.random.default_rng(42)
    B0 = TILE
    reps = BTOT // B0

    msgs = rng.integers(0, 256, (B0, 32), np.uint8)
    pubs = np.zeros((B0, 32), np.uint8)
    sigs = np.zeros((B0, 64), np.uint8)
    for i in range(B0):
        sk = rng.integers(0, 256, 32, np.uint8).tobytes()
        pubs[i] = np.frombuffer(golden.public_from_secret(sk), np.uint8)
        sigs[i] = np.frombuffer(golden.sign(sk, msgs[i].tobytes()), np.uint8)

    import hashlib
    digests = np.stack([
        np.frombuffer(hashlib.sha512(
            sigs[i, :32].tobytes() + pubs[i].tobytes() + msgs[i].tobytes()
        ).digest(), np.uint8) for i in range(B0)
    ])

    # tile out to BTOT lanes (tiles inside one execution are not deduped)
    digests = np.tile(digests, (reps, 1))
    pubs_t = np.tile(pubs, (reps, 1))
    sigs_t = np.tile(sigs, (reps, 1))

    k_limbs = SC.reduce512(jnp.asarray(digests))
    s_limbs = SC.from_bytes(jnp.asarray(sigs_t[:, 32:]))
    k_dig = np.asarray(SC.to_signed_digits(k_limbs), np.int32)
    s_dig = np.asarray(SC.to_signed_digits(s_limbs), np.int32)

    a_y, a_sign = PT.decompress_bytes(jnp.asarray(pubs_t))
    r_y, r_sign = PT.decompress_bytes(jnp.asarray(sigs_t[:, :32]))
    a_cat = np.asarray(jnp.concatenate([a_y, a_sign], axis=0), np.int32)
    r_cat = np.asarray(jnp.concatenate([r_y, r_sign], axis=0), np.int32)

    arrays = {"packed": (pack_digits(k_dig), pack_digits(s_dig))}

    results = {}
    order = sys.argv[1:] or ["base", "chunk8", "packed", "noread"]
    for variant in order:
        pair = arrays.get(variant, (k_dig, s_dig))
        kk = jnp.asarray(pair[0])
        ss = jnp.asarray(pair[1])
        aa = jnp.asarray(a_cat)
        rr = jnp.asarray(r_cat)
        fn = build_fn(variant, kk.shape[0])
        t0 = time.perf_counter()
        out = np.asarray(fn(kk, ss, aa, rr))
        compile_s = time.perf_counter() - t0
        n_ok = int((out[0] != 0).sum())
        if variant != "noread":
            assert n_ok == BTOT, f"{variant}: {n_ok}/{BTOT} verified"
        best = float("inf")
        for r in range(1, 4):
            kk2, ss2 = jnp.roll(kk, r, axis=1), jnp.roll(ss, r, axis=1)
            aa2, rr2 = jnp.roll(aa, r, axis=1), jnp.roll(rr, r, axis=1)
            sync(kk2); sync(ss2); sync(aa2); sync(rr2)
            t0 = time.perf_counter()
            o = fn(kk2, ss2, aa2, rr2)
            sync(o)
            best = min(best, time.perf_counter() - t0)
        results[variant] = best
        print(f"{variant:8s}: {best*1e3:8.2f} ms  "
              f"({best/64/BTOT*1e9:6.3f} ns/iter/lane)  "
              f"compile {compile_s:5.1f}s  ok={n_ok}/{BTOT}", flush=True)

    if "base" in results:
        base = results["base"]
        for v, t in results.items():
            print(f"  {v:8s} vs base: {base/t:5.2f}x  "
                  f"delta {(t-base)/64/BTOT*1e9:+6.3f} ns/iter/lane")


if __name__ == "__main__":
    main()
