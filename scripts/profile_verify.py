"""Stage-by-stage profile of the Ed25519 verify kernel on the real chip.

All timings sync via np.asarray (the device-to-host copy of the result
ends the timed region) and report MARGINAL cost between two batch sizes,
so whatever each execution costs regardless of size cancels.

Usage: python scripts/profile_verify.py
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")


def t_of(fn, argsets):
    """Min wall time of fn over DISTINCT input sets; a separate set warms.

    Every timed call uses fresh buffers (argsets[0] is warmup-only), so
    no layer between the caller and the device can answer a timed repeat
    from a result it already holds."""
    np.asarray(jax_tree_first(fn(*argsets[0])))
    best = float("inf")
    for args in argsets[1:]:
        t0 = time.perf_counter()
        out = fn(*args)
        np.asarray(jax_tree_first(out))
        best = min(best, time.perf_counter() - t0)
    return best


def jax_tree_first(x):
    import jax

    return jax.tree.leaves(x)[0]


def main():
    import jax
    import jax.numpy as jnp

    from firedancer_tpu.ops import sha512 as _sha
    from firedancer_tpu.ops.ed25519 import point as PT
    from firedancer_tpu.ops.ed25519 import scalar as SC
    from firedancer_tpu.ops.ed25519 import verify as fver

    print(f"devices={jax.devices()}")
    rng = np.random.default_rng(0)
    sizes = (65536, 262144)
    rows = {}

    @jax.jit
    def prologue(msgs, lens, sigs, pubs):
        s_limbs = SC.from_bytes(sigs[:, 32:])
        ok = SC.is_canonical(s_limbs)
        ok = (
            ok
            & ~fver._is_small_order_enc(pubs)
            & ~fver._is_small_order_enc(sigs[:, :32])
        )
        digest = _sha.sha512(
            jnp.concatenate([sigs[:, :32], pubs, msgs], axis=1),
            lens.astype(jnp.int32) + 64,
        )
        kd = SC.to_signed_digits(SC.reduce512(digest))
        sd = SC.to_signed_digits(s_limbs)
        a_y, a_s = PT.decompress_bytes(pubs)
        r_y, r_s = PT.decompress_bytes(sigs[:, :32])
        # tiny reduction forces compute without a big D2H transfer
        return (
            ok.sum()
            + kd.sum()
            + sd.sum()
            + a_y.sum()
            + a_s.sum()
            + r_y.sum()
            + r_s.sum()
        )

    full = jax.jit(fver.verify_batch)
    for B in sizes:
        argsets = []
        for _ in range(3):
            argsets.append((
                jax.device_put(rng.integers(0, 256, (B, 128), np.uint8)),
                jax.device_put(np.full(B, 128, np.int32)),
                jax.device_put(rng.integers(0, 256, (B, 64), np.uint8)),
                jax.device_put(rng.integers(0, 256, (B, 32), np.uint8)),
            ))
        tp = t_of(prologue, argsets)
        tv = t_of(full, argsets)
        rows[B] = (tp, tv)
        print(f"B={B}: prologue {tp*1e3:8.1f} ms | full {tv*1e3:8.1f} ms"
              f"  ({B/tv:,.0f}/s)")
    (b1, (tp1, tv1)), (b2, (tp2, tv2)) = rows.items()
    print(f"marginal prologue: {(tp2-tp1)/(b2-b1)*1e9:7.0f} ns/verify")
    print(f"marginal full:     {(tv2-tv1)/(b2-b1)*1e9:7.0f} ns/verify")


if __name__ == "__main__":
    main()
