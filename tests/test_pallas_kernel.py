"""Pallas verify-core kernel vs the plain XLA path (interpret mode on CPU).

The TPU runs the Mosaic-compiled kernel; CI cross-checks the identical
kernel body through the Pallas interpreter against both the XLA data path
and the golden oracle."""

import numpy as np

from firedancer_tpu.ops.ed25519 import golden
from firedancer_tpu.ops.ed25519 import pallas_kernel as PK
from firedancer_tpu.ops.ed25519 import point as PT
from firedancer_tpu.ops.ed25519 import scalar as SC
from firedancer_tpu.ops.ed25519 import verify as V
import pytest

pytestmark = pytest.mark.slow


def test_verify_core_interpret_matches_xla():
    B = 12  # intentionally not a TILE multiple: exercises padding
    rng = np.random.default_rng(3)
    sk = rng.integers(0, 256, 32, np.uint8).tobytes()
    pk = golden.public_from_secret(sk)
    msgs = np.zeros((B, 96), np.uint8)
    lens = np.full(B, 96, np.int32)
    sigs = np.zeros((B, 64), np.uint8)
    pubs = np.zeros((B, 32), np.uint8)
    for i in range(B):
        m = rng.integers(0, 256, 96, np.uint8)
        s = golden.sign(sk, m.tobytes())
        msgs[i] = m
        sigs[i] = np.frombuffer(s, np.uint8)
        pubs[i] = np.frombuffer(pk, np.uint8)
    # corrupt some lanes across failure modes
    sigs[1, 3] ^= 0xFF  # bad R
    sigs[2, 40] ^= 0x01  # bad s
    pubs[3] = rng.integers(0, 256, 32, np.uint8)  # wrong key
    msgs[4, 0] ^= 0x80  # bad msg
    pubs[5] = np.zeros(32, np.uint8)
    pubs[5][0] = 1  # identity point: small order -> reject

    want = np.asarray(V.verify_batch(msgs, lens, sigs, pubs))
    for i in range(B):
        g = golden.verify(bytes(msgs[i]), bytes(sigs[i]), bytes(pubs[i]))
        assert bool(want[i]) == (g == 0), f"xla lane {i}"

    # now the kernel body through the interpreter
    import jax.numpy as jnp

    from firedancer_tpu.ops import sha512 as _sha

    s_limbs = SC.from_bytes(sigs[:, 32:])
    cat = np.concatenate([sigs[:, :32], pubs, msgs], axis=1)
    digest = _sha.sha512(cat, lens + 64)
    k_limbs = SC.reduce512(digest)
    a_y, a_sign = PT.decompress_bytes(jnp.asarray(pubs))
    r_y, r_sign = PT.decompress_bytes(jnp.asarray(sigs[:, :32]))
    ok_core = np.asarray(
        PK.verify_core(
            SC.to_signed_digits(k_limbs),
            SC.to_signed_digits(s_limbs),
            a_y, a_sign, r_y, r_sign,
            interpret=True,
        )
    )
    ok = (
        np.asarray(SC.is_canonical(s_limbs))
        & ok_core
        & ~np.asarray(V._is_small_order_enc(jnp.asarray(pubs)))
        & ~np.asarray(V._is_small_order_enc(jnp.asarray(sigs[:, :32])))
    )
    assert (ok == want).all()


@pytest.fixture(scope="module")
def two_tiles():
    """Kernel inputs for a batch of two tiles with valid signatures in
    BOTH and a reject in each, and the XLA path's verdicts for them."""
    import hashlib

    import jax.numpy as jnp

    B = 2 * PK.TILE
    rng = np.random.default_rng(31)
    sk = rng.integers(0, 256, 32, np.uint8).tobytes()
    pk = golden.public_from_secret(sk)
    # a few signatures, tiled over the batch: signing is the slow part
    pool = []
    for _ in range(4):
        m = rng.integers(0, 256, 48, np.uint8).tobytes()
        s = golden.sign(sk, m)
        pool.append((s, hashlib.sha512(s[:32] + pk + m).digest()))
    sigs = np.zeros((B, 64), np.uint8)
    digests = np.zeros((B, 64), np.uint8)
    pubs = np.tile(np.frombuffer(pk, np.uint8), (B, 1))
    for i in range(B):
        s, d = pool[i % len(pool)]
        sigs[i] = np.frombuffer(s, np.uint8)
        digests[i] = np.frombuffer(d, np.uint8)
    sigs[3, 2] ^= 0x10  # bad R in the first tile
    digests[PK.TILE + 7, 0] ^= 0x01  # wrong k in the second
    want = np.asarray(
        V._verify_digest_impl(digests, sigs, pubs, np.asarray(B, np.int32))
    )
    assert want[: PK.TILE].sum() == PK.TILE - 1
    assert want[PK.TILE :].sum() == PK.TILE - 1
    s_limbs = SC.from_bytes(sigs[:, 32:])
    a_y, a_sign = PT.decompress_bytes(jnp.asarray(pubs))
    r_y, r_sign = PT.decompress_bytes(jnp.asarray(sigs[:, :32]))
    args = (
        SC.to_signed_digits(SC.reduce512(digests)),
        SC.to_signed_digits(s_limbs),
        a_y, a_sign, r_y, r_sign,
    )
    return args, want, PK.verify_core._cache_size()


@pytest.mark.parametrize(
    "n_lanes", [0, 1, PK.TILE - 1, PK.TILE, PK.TILE + 1, 2 * PK.TILE]
)
def test_verify_core_stops_at_the_lane_count(two_tiles, n_lanes):
    """A lane before `n_lanes` gets the XLA path's verdict; one at or past
    it reads False, valid signature or not.  The count is an operand: one
    program.  (The interpreter walks every tile; on the chip the grid ends
    with the last tile that holds a real lane: `chip_smoke.py` and
    PERF.md's table hold that path to the same contract.)"""
    args, want, programs = two_tiles
    got = np.asarray(
        PK.verify_core(*args, np.asarray(n_lanes, np.int32), interpret=True)
    )
    assert (got[:n_lanes] == want[:n_lanes]).all()
    assert not got[n_lanes:].any()
    assert PK.verify_core._cache_size() == programs + 1
