"""Pallas-level microbenchmarks for the verify kernel cost model.

All timing syncs via np.asarray: the device-to-host copy of the result is
the end of the timed region.  Usage: python scripts/profile_kernel.py
"""

import functools
import sys
import time

import numpy as np

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def timeit(fn, *args, variants=3):
    """Each timed call gets perturbed input buffers, so no layer between
    the caller and the device can answer a timed repeat from a result it
    already holds."""
    np.asarray(fn(*args))  # warmup (excluded from timing)
    best = float("inf")
    for k in range(1, variants + 1):
        fresh = tuple(a + k if hasattr(a, "dtype") else a for a in args)
        t0 = time.perf_counter()
        out = fn(*fresh)
        np.asarray(out)
        best = min(best, time.perf_counter() - t0)
    return best


def _chain_kernel(op, iters, a_ref, b_ref, o_ref):
    a = a_ref[...]
    b = b_ref[...]

    def body(i, v):
        if op == "mul":
            return (v * b) & 0x7FFFFFF
        if op == "add":
            return (v + b) ^ a
        if op == "fma":
            return v * b + a
        raise ValueError(op)

    o_ref[...] = jax.lax.fori_loop(0, iters, body, a)


def chain_rate(op, dtype, rows=24, lanes=1024, iters=4096):
    """Returns elementwise ops/s for a dependent op chain in one kernel."""
    shape = (rows, lanes)
    a = jnp.asarray(np.random.default_rng(0).integers(1, 127, shape), dtype)
    b = jnp.asarray(np.random.default_rng(1).integers(1, 127, shape), dtype)
    fn = jax.jit(
        lambda a, b: pl.pallas_call(
            functools.partial(_chain_kernel, op, iters),
            out_shape=jax.ShapeDtypeStruct(shape, dtype),
        )(a, b)
    )
    t = timeit(fn, a, b)
    # ops per element-chain (mul/add count 2 for mul+mask / add+xor, fma 2)
    per = 2
    return rows * lanes * iters * per / t, t


def field_mul_rate(batch=1024, iters=256):
    """Cost of one F.mul per lane, measured inside a Pallas kernel."""
    from firedancer_tpu.ops.ed25519 import field as F

    consts = {
        n: jnp.asarray(np.tile(F._CONST_TABLE[n].reshape(-1, 1), (1, batch)))
        for n in ("ONE", "P32", "P")
    }

    def kern(a_ref, b_ref, o_ref):
        a = a_ref[...]
        b = b_ref[...]

        def body(i, v):
            return F.mul(v, b)

        with F.const_scope(consts):
            o_ref[...] = jax.lax.fori_loop(0, iters, body, a)

    shape = (F.NLIMB, batch)
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, 8192, shape), jnp.int32)
    b = jnp.asarray(rng.integers(0, 8192, shape), jnp.int32)
    fn = jax.jit(
        lambda a, b: pl.pallas_call(
            kern, out_shape=jax.ShapeDtypeStruct(shape, jnp.int32)
        )(a, b)
    )
    t = timeit(fn, a, b)
    return t / iters, batch


def main():
    print(f"devices: {jax.devices()}")
    for op, dt in [("add", jnp.int32), ("mul", jnp.int32), ("fma", jnp.float32)]:
        rate, t = chain_rate(op, dt)
        print(f"chain {op:4s} {dt.__name__}: {rate/1e12:6.2f} Tops/s ({t*1e3:.2f} ms)")
    per_mul, batch = field_mul_rate()
    print(f"F.mul in-kernel: {per_mul*1e6:8.2f} us per mul @ B={batch}"
          f"  ({per_mul/batch*1e9:.2f} ns/lane)")
    # dsm cost model: ~50 muls/iter * 64 iters
    est = per_mul / batch * 50 * 64
    print(f"  -> dsm est {est*1e6:.1f} us/lane-serial, {1/est:,.0f} verifies/s-equiv")


if __name__ == "__main__":
    main()
