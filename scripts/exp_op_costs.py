"""Per-op in-kernel microbenches for the verify kernel (round 4).

Measures the marginal per-lane cost of each point/field op this session:
mul_rr, sqr_rr, carry1, double(noT), double(T), add_niels, add_niels_affine,
lookup9, and one full dsm iteration — so the dsm loop total can be
reconciled against its parts.  Methodology as scripts/exp_dsm_variants.py.
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
from firedancer_tpu.ops.ed25519.pallas_kernel import (
    TILE, _pack_consts, _unpack_consts, NL,
)

B = TILE
GRID = int(__import__("os").environ.get("FDT_EXP_GRID", "64"))
ITERS = int(__import__("os").environ.get("FDT_EXP_ITERS", "128"))


def sync(x):
    return np.asarray(jnp.max(x))


def bench_op(name, niters_pair):
    """Times a kernel running `op` niters times vs 2*niters times; the
    marginal difference isolates the op cost from fixed overhead."""
    n1, n2 = niters_pair

    def make(niters):
        def kern(c_ref, x_ref, d_ref, o_ref):
            with F.const_scope(_unpack_consts(c_ref)):
                x = x_ref[:NL, :]
                y = x_ref[NL:2 * NL, :]
                z = x_ref[2 * NL:3 * NL, :]
                dig = jnp.squeeze(d_ref[0:1, :], axis=0)
                pt = (x, y, z, F.mul_rr(x, F.carry1(y)))
                table = PT.build_neg_table9(pt)
                b_table = F.c("B_TABLE9")

                def body(j, st):
                    a, b, c = st
                    if name == "mul_rr":
                        r = F.mul_rr(a, b)
                        return (r, a, c)
                    if name == "sqr_rr":
                        return (F.sqr_rr(a), a, c)
                    if name == "carry1":
                        return (F.carry1(a + b), a, c)
                    if name == "double_noT":
                        p = PT.double((a, b, c, None), with_t=False)
                        return (p[0], p[1], p[2])
                    if name == "double_T":
                        p = PT.double((a, b, c, None), with_t=True)
                        return (p[0], p[1], p[2])
                    if name == "add_niels":
                        t = F.mul_rr(a, F.carry1(b))
                        p = PT.add_niels(
                            (a, b, c, t), PT.lookup9(table, dig + j % 3),
                            with_t=True,
                        )
                        return (p[0], p[1], p[2])
                    if name == "add_affine":
                        t = F.mul_rr(a, F.carry1(b))
                        p = PT.add_niels_affine(
                            (a, b, c, t),
                            PT.lookup9_affine(b_table, dig + j % 3),
                            with_t=False,
                        )
                        return (p[0], p[1], p[2])
                    if name == "lookup9":
                        e = PT.lookup9(table, dig + j % 3)
                        return (a + e[0], b + e[1], c + e[2])
                    if name == "dsm_iter":
                        acc = (a, b, c, F.mul_rr(a, F.carry1(b)))
                        acc = PT.double(acc, with_t=False)
                        acc = PT.double(acc, with_t=False)
                        acc = PT.double(acc, with_t=False)
                        acc = PT.double(acc, with_t=True)
                        acc = PT.add_niels(
                            acc, PT.lookup9(table, dig + j % 3), with_t=True
                        )
                        acc = PT.add_niels_affine(
                            acc, PT.lookup9_affine(b_table, dig + (j + 1) % 3),
                            with_t=False,
                        )
                        return (acc[0], acc[1], acc[2])
                    raise ValueError(name)

                a, b, c = jax.lax.fori_loop(0, niters, body, (x, y, z))
                o_ref[...] = (a + b + c)[:1, :]
        return kern

    consts = jnp.asarray(_pack_consts())
    spec = lambda rows: pl.BlockSpec((rows, TILE), lambda i: (0, i),
                                     memory_space=pltpu.VMEM)
    const_spec = pl.BlockSpec(consts.shape, lambda i: (0, 0),
                              memory_space=pltpu.VMEM)
    rng = np.random.default_rng(3)
    X = jnp.asarray(rng.integers(0, 8192, (3 * NL, B * GRID)), jnp.int32)
    D = jnp.asarray(rng.integers(-8, 8, (1, B * GRID)), jnp.int32)

    times = []
    for niters in (n1, n2):
        fn = jax.jit(lambda x, d, n=niters: pl.pallas_call(
            make(n),
            out_shape=jax.ShapeDtypeStruct((1, B * GRID), jnp.int32),
            grid=(GRID,),
            in_specs=[const_spec, spec(3 * NL), spec(1)],
            out_specs=spec(1),
        )(consts, x, d))
        sync(fn(X, D))  # compile+warm
        best = float("inf")
        for r in range(1, 4):
            X2 = jnp.roll(X, r, axis=1)
            D2 = jnp.roll(D, r, axis=1)
            sync(X2); sync(D2)
            t0 = time.perf_counter()
            sync(fn(X2, D2))
            best = min(best, time.perf_counter() - t0)
        times.append(best)
    t1, t2 = times
    per = (t2 - t1) / (n2 - n1) / (B * GRID)
    print(f"{name:12s}: {per*1e9:7.3f} ns/lane  "
          f"(t{n1}={t1*1e3:.1f}ms t{n2}={t2*1e3:.1f}ms)", flush=True)
    return per


def main():
    print(f"devices: {jax.devices()}  TILE={TILE} GRID={GRID}", flush=True)
    names = sys.argv[1:] or [
        "mul_rr", "sqr_rr", "carry1", "double_noT", "double_T",
        "add_niels", "add_affine", "lookup9", "dsm_iter",
    ]
    res = {}
    for n in names:
        res[n] = bench_op(n, (ITERS, 2 * ITERS))
    if all(k in res for k in
           ("double_noT", "double_T", "add_niels", "add_affine")):
        pred = (3 * res["double_noT"] + res["double_T"]
                + res["add_niels"] + res["add_affine"])
        print(f"sum-of-parts dsm iter: {pred*1e9:.2f} ns/lane "
              f"(add_niels/add_affine include their lookup+T-mul overhead)")


if __name__ == "__main__":
    main()
