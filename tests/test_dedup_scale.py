"""Production-scale dedup filter: false-positive rate at reference tcache
depth, aging rotation semantics, and the scatter-free OR insertion.

Round-1 review, item 3: ">=4M-tag history with measured FP rate < 1e-3".
"""

import numpy as np
import pytest

from firedancer_tpu.models import pipeline as PL

BITS = PL.BLOOM_BITS
MASK = np.uint32(BITS - 1)


def _mix_np(x):
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


def _tag_bits_np(tags2):
    """Numpy mirror of pipeline._tag_bits — asserted identical below."""
    lo = tags2[:, 0].astype(np.uint32)
    hi = tags2[:, 1].astype(np.uint32)
    h1 = _mix_np(lo ^ _mix_np(hi))
    h2 = _mix_np(hi + np.uint32(0x9E3779B9)) | np.uint32(1)
    i = np.arange(PL.N_HASH, dtype=np.uint32)[:, None]
    return ((h1[None, :] + i * h2[None, :]) & MASK).astype(np.int64)


def test_hash_mirror_matches_device():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    tags = rng.integers(0, 2**32, (512, 2), dtype=np.uint64).astype(np.uint32)
    dev = np.asarray(PL._tag_bits(jnp.asarray(tags)))
    assert (dev.astype(np.int64) == _tag_bits_np(tags)).all()


def test_false_positive_rate_at_capacity():
    """Worst case: current AND previous both at AGE_CAPACITY (the state
    just before a rotation) — membership consults their OR.  Probe 1M
    fresh tags against the pair."""
    rng = np.random.default_rng(1)
    n = 2 * PL.AGE_CAPACITY  # cur + prev, each at capacity
    filt = np.zeros(BITS // 32, np.uint32)
    # insert in chunks to bound memory
    for lo in range(0, n, 1 << 20):
        m = min(1 << 20, n - lo)
        tags = rng.integers(0, 2**32, (m, 2), dtype=np.uint64).astype(
            np.uint32
        )
        bits = _tag_bits_np(tags).reshape(-1)
        np.bitwise_or.at(
            filt, bits >> 5, np.uint32(1) << (bits & 31).astype(np.uint32)
        )
    probe = rng.integers(0, 2**32, (1 << 20, 2), dtype=np.uint64).astype(
        np.uint32
    )
    bits = _tag_bits_np(probe)  # (N_HASH, 1M)
    hit = np.ones(probe.shape[0], bool)
    for k in range(PL.N_HASH):
        b = bits[k]
        hit &= ((filt[b >> 5] >> (b & 31).astype(np.uint32)) & 1) == 1
    fp = hit.mean()
    assert fp < 1e-3, f"false positive rate {fp:.2e} >= 1e-3"
    # sanity: inserted tags all report present (no false negatives, ever)
    tags = rng.integers(0, 2**32, (4096, 2), dtype=np.uint64).astype(
        np.uint32
    )
    bits = _tag_bits_np(tags).reshape(-1)
    np.bitwise_or.at(
        filt, bits >> 5, np.uint32(1) << (bits & 31).astype(np.uint32)
    )
    bits = _tag_bits_np(tags)
    present = np.ones(4096, bool)
    for k in range(PL.N_HASH):
        b = bits[k]
        present &= ((filt[b >> 5] >> (b & 31).astype(np.uint32)) & 1) == 1
    assert present.all()


def test_aging_rotation():
    """AgingBloom rotates at capacity and retains the previous epoch."""
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()[:1]
    mesh = Mesh(np.array(devs).reshape(1, 1), axis_names=("dp", "mp"))
    bloom = PL.AgingBloom(mesh)
    cur0 = bloom.cur
    fake_metrics = np.array([0, 0, 0, PL.AGE_CAPACITY], np.int32)
    marked = jax.device_put(
        np.ones(BITS // 32, np.uint32), bloom._sharding
    )
    bloom.update(marked, fake_metrics)
    assert bloom.rotations == 1 and bloom.inserted == 0
    # previous epoch is the marked filter; current is fresh zeros
    assert np.asarray(bloom.prev).any()
    assert not np.asarray(bloom.cur).any()
    del cur0
