"""Scalar (mod L) arithmetic vs exact python ints, and the fixed-base
scalar mul [s]B that sign.py's corpus signer runs through."""

import numpy as np
import pytest

import jax.numpy as jnp

from firedancer_tpu.ops.ed25519 import field as F
from firedancer_tpu.ops.ed25519 import golden
from firedancer_tpu.ops.ed25519 import point as PT
from firedancer_tpu.ops.ed25519 import scalar as SC
from firedancer_tpu.ops.ed25519.golden import L


@pytest.mark.slow
def test_is_canonical():
    vals = [0, 1, L - 1, L, L + 1, 2**256 - 1, 2**252, L + 2**200]
    raw = np.stack(
        [np.frombuffer(int(v).to_bytes(32, "little"), np.uint8) for v in vals]
    )
    got = np.asarray(SC.is_canonical(SC.from_bytes(jnp.asarray(raw))))
    assert list(got) == [v < L for v in vals]


@pytest.mark.slow
def test_reduce512_vs_int():
    rng = np.random.default_rng(7)
    vals = [0, 1, L, L - 1, 2**512 - 1, 2**252, (L - 1) * L] + [
        int.from_bytes(rng.bytes(64), "little") for _ in range(29)
    ]
    raw = np.stack(
        [np.frombuffer(int(v).to_bytes(64, "little"), np.uint8) for v in vals]
    )
    got = np.asarray(SC.reduce512(jnp.asarray(raw)))
    for j, v in enumerate(vals):
        assert F.limbs_to_int(got[:, j]) == v % L, f"lane {j}"


@pytest.mark.slow
def test_to_nibbles():
    rng = np.random.default_rng(8)
    vals = [int.from_bytes(rng.bytes(32), "little") for _ in range(8)]
    raw = np.stack(
        [np.frombuffer(int(v).to_bytes(32, "little"), np.uint8) for v in vals]
    )
    nib = np.asarray(SC.to_nibbles(SC.from_bytes(jnp.asarray(raw))))
    assert nib.shape == (64, 8)
    for j, v in enumerate(vals):
        for d in range(64):
            assert nib[d, j] == (v >> (4 * d)) & 15


def test_scalar_mul_base():
    rng = np.random.default_rng(3)
    s = int.from_bytes(rng.bytes(32), "little") % L
    limbs = F.int_to_limbs(s).reshape(F.NLIMB, 1)
    pt = PT.scalar_mul_base(np.asarray(SC.to_signed_digits(limbs)))
    enc = np.asarray(PT.compress(pt))[0].tobytes()
    assert enc == golden.point_compress(golden.scalar_mul(s, golden.B))


def test_sign_public_keys_run_through_scalar_mul_base():
    """sign.py's [a]B is point.scalar_mul_base, compressed: a batch of
    four derived on the device equals the golden oracle's keys."""
    from firedancer_tpu.ops.ed25519 import sign

    rng = np.random.default_rng(4)
    secrets = [rng.integers(0, 256, 32, np.uint8).tobytes() for _ in range(4)]
    assert sign.public_keys(secrets) == [
        golden.public_from_secret(s) for s in secrets
    ]


if __name__ == "__main__":
    pytest.main([__file__, "-x", "-q"])
