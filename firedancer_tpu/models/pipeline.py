"""The flagship multi-chip pipeline step: verify → dedup → pack prefilter
over a 2-axis device mesh.

This is the framework's "training step" analog — the unit the driver
dry-runs over an n-device mesh.  Axes:

  dp — data parallel: the transaction batch is sharded across chips;
       each chip verifies its shard with the same kernel the single-chip
       path uses (ops/ed25519).
  mp — state parallel: the dedup membership filter (a bloom-style bitmask,
       the device analog of the reference's tcache,
       /root/reference/src/tango/tcache/fd_tcache.h) is sharded bitwise
       across chips.

Collectives (all under shard_map, riding ICI on real hardware):
  * all_gather(tags, 'dp')  — every chip sees the full batch's dedup tags
  * psum(hits, 'mp')        — membership answers combined across the
                              bloom's shards
  * psum(metrics, 'dp')     — global counters

Deliberate divergence from the reference documented here: the reference's
tcache is an exact evicting ring+map; the device filter is a k-hash bloom
pair — false positives drop a valid txn (never admit a duplicate), and
aging is a DOUBLE-BUFFER: membership consults current|previous, inserts go
to current only, and when current has absorbed ~the reference's tcache
depth (4,194,302 sigs, default.toml:760) of MISSES the host rotates
previous<-current and zeroes current (AgingBloom).  The worst case for
false positives is just before rotation, when current|previous holds up
to 2*AGE_CAPACITY tags; BLOOM_BITS = 2^28 with N_HASH = 4 keeps even that
peak at ~2e-4 (measured on the full pair in tests/test_dedup_scale.py),
against the <1e-3 budget.  The host tcache (tango) remains the exact
authority on the host path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from firedancer_tpu.ops import pack_select
from firedancer_tpu.ops.ed25519 import verify as fver
from firedancer_tpu.utils.hotpath import hot_path

#: bloom filter size in bits (power of two; must divide across mp); sized
#: for the pre-rotation worst case of 2*AGE_CAPACITY resident tags
BLOOM_BITS = 1 << 28
#: hash probes per tag
N_HASH = 4
#: inserts before the host rotates the double buffer (reference tcache
#: depth, src/app/fdctl/config/default.toml:760)
AGE_CAPACITY = 4_194_302


def fresh_bloom() -> np.ndarray:
    """A zeroed dedup filter (full, unsharded).  Callers device_put it
    mp-sharded; AgingBloom handles the epoch rotation."""
    return np.zeros(BLOOM_BITS // 32, np.uint32)


def _mix(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _tag_bits(tags2):
    """(B, 2) u32 tag words -> (N_HASH, B) int32 bit indices via double
    hashing: bit_i = (h1 + i*h2) mod BLOOM_BITS (h2 odd)."""
    lo = tags2[:, 0].astype(jnp.uint32)
    hi = tags2[:, 1].astype(jnp.uint32)
    h1 = _mix(lo ^ _mix(hi))
    h2 = _mix(hi + jnp.uint32(0x9E3779B9)) | jnp.uint32(1)
    i = jnp.arange(N_HASH, dtype=jnp.uint32)[:, None]
    idx = (h1[None, :] + i * h2[None, :]) & jnp.uint32(BLOOM_BITS - 1)
    return idx.astype(jnp.int32)


def make_step(mesh: Mesh):
    """Build the jitted pipeline step for `mesh` (axes 'dp', 'mp')."""
    mp = mesh.shape["mp"]
    assert BLOOM_BITS % (32 * mp) == 0
    words_per_shard = BLOOM_BITS // 32 // mp

    @hot_path
    def step(msgs, lens, sigs, pubs, tags2, cur, prev):
        """One ingress step on local shards.

        msgs (Bl, W) u8, lens (Bl,), sigs (Bl, 64), pubs (Bl, 32),
        tags2 (Bl, 2) u32 dedup tag words — all dp-sharded;
        cur/prev (words_per_shard,) u32 — mp-sharded aging bloom pair.

        Returns (keep (Bl,) bool, new current shard, metrics (4,):
        [verified, failed, dup_hits, inserted]).
        """
        ok = fver.verify_batch(msgs, lens, sigs, pubs)

        # ---- dedup: N_HASH-probe membership across current|previous ----
        all_tags = jax.lax.all_gather(tags2, "dp", tiled=True)  # (Bg, 2)
        all_ok = jax.lax.all_gather(ok, "dp", tiled=True)  # (Bg,)
        bits = _tag_bits(all_tags)  # (N_HASH, Bg)
        word, off = bits >> 5, (bits & 31).astype(jnp.uint32)
        shard_lo = jax.lax.axis_index("mp") * words_per_shard
        local = word - shard_lo
        in_shard = (local >= 0) & (local < words_per_shard)
        lw = jnp.where(in_shard, local, 0)
        both = cur | prev
        probe = jnp.where(in_shard, (both[lw] >> off) & 1, 0)
        probe = jax.lax.psum(probe, "mp")  # (N_HASH, Bg): each bit 0/1
        hits = jnp.min(probe, axis=0)  # bloom hit iff ALL probes set

        # within-batch duplicates: membership above reads the PRE-insert
        # filter, so repeats inside one batch need their own first-
        # occurrence mask (the reference's query+insert is sequential and
        # gets this for free).  Stable sort on the combined 64-bit tag
        # groups equal tags with original order preserved.
        Bg = all_tags.shape[0]
        # exact 64-bit grouping with 32-bit sorts: two-pass stable lexsort
        # (sort by lo, then stably by hi) puts equal (hi, lo) tags adjacent
        order1 = jnp.argsort(all_tags[:, 0], stable=True)
        order = order1[jnp.argsort(all_tags[order1, 1], stable=True)]
        st = all_tags[order]
        same = jnp.all(st[1:] == st[:-1], axis=1)
        head = jnp.concatenate([jnp.ones(1, bool), ~same])
        first_occurrence = jnp.zeros(Bg, bool).at[order].set(head)

        # insert into CURRENT only: VERIFIED first-occurrence tags — a
        # failed signature must not be able to censor a later valid txn
        # with the same tag (the reference dedups post-verify only).
        # Scatter-free OR: flatten the probe bit indices, drop entries
        # outside this shard / not insertable, dedup exact bit repeats by
        # sort, then segment-sum single-bit words (sum == OR once each
        # (word, bit) pair is unique).
        insertable = all_ok & first_occurrence
        lbit = jnp.where(
            in_shard & insertable[None, :],
            (lw << 5) | off.astype(jnp.int32),
            jnp.int32(words_per_shard * 32),  # sentinel: sorts last
        ).reshape(-1)
        sl = jnp.sort(lbit)
        uniq = jnp.concatenate([jnp.ones(1, bool), sl[1:] != sl[:-1]])
        valid = uniq & (sl < words_per_shard * 32)
        vals = jnp.where(
            valid, jnp.uint32(1) << (sl & 31).astype(jnp.uint32), 0
        )
        seg = jnp.where(valid, sl >> 5, 0)
        delta = jax.ops.segment_sum(
            vals, seg, num_segments=words_per_shard
        ).astype(jnp.uint32)
        new_cur = cur | delta

        # my dp slice of the global keep vector
        keep_g = all_ok & (hits == 0) & first_occurrence
        bl = tags2.shape[0]
        dp_i = jax.lax.axis_index("dp")
        my_keep = jax.lax.dynamic_slice(keep_g, (dp_i * bl,), (bl,))
        my_hits = jax.lax.dynamic_slice(hits, (dp_i * bl,), (bl,))
        keep = my_keep

        # ---- metrics: [verified, failed, dup_hits] psum'd over dp;
        # inserted counts only MISSES (tags not already present) so
        # duplicate-heavy traffic does not rotate the aging buffer early
        # (the reference tcache likewise inserts only on miss); computed
        # from all-gathered values, already identical on every device
        m = jnp.stack(
            [
                jnp.sum(ok.astype(jnp.int32)),
                jnp.sum((~ok).astype(jnp.int32)),
                jnp.sum((ok & (my_hits != 0)).astype(jnp.int32)),
            ]
        )
        new_tags = insertable & (hits == 0)
        metrics = jnp.concatenate(
            [
                jax.lax.psum(m, "dp"),
                jnp.sum(new_tags.astype(jnp.int32))[None],
            ]
        )
        return keep, new_cur, metrics

    return jax.jit(
        jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(
                P("dp", None), P("dp"), P("dp", None), P("dp", None),
                P("dp", None), P("mp"), P("mp"),
            ),
            out_specs=(P("dp"), P("mp"), P()),
        )
    )


class AgingBloom:
    """Host-side owner of the double-buffered device filter.

    Rotation mirrors the reference's bounded tcache history: once `cur`
    has absorbed AGE_CAPACITY tags, previous <- current and current is
    zeroed, so the filter always remembers between AGE_CAPACITY and
    2*AGE_CAPACITY of the most recent tags."""

    def __init__(self, mesh: Mesh, capacity: int = AGE_CAPACITY):
        self._sharding = NamedSharding(mesh, P("mp"))
        self.capacity = capacity
        self.cur = jax.device_put(fresh_bloom(), self._sharding)
        self.prev = jax.device_put(fresh_bloom(), self._sharding)
        self.inserted = 0
        self.rotations = 0

    def buffers(self):
        return self.cur, self.prev

    def update(self, new_cur, metrics) -> None:
        """Adopt the step's output filter + account inserts; rotate at
        capacity."""
        self.cur = new_cur
        self.inserted += int(np.asarray(metrics)[3])
        if self.inserted >= self.capacity:
            self.prev = self.cur
            self.cur = jax.device_put(fresh_bloom(), self._sharding)
            self.inserted = 0
            self.rotations += 1


def pack_prefilter(cand_rw32, cand_w32, in_use_rw32, in_use_w32, costs,
                   cu_limit, txn_limit):
    """Device pack-candidate selection (replicated; the greedy scan is a
    tiny sequential program — see ops/pack_select.py).  Same int32 budget
    validation as the public select_noconflict entry point."""
    if int(cu_limit) > pack_select.CU_LIMIT_MAX:
        raise ValueError(
            f"cu_limit {cu_limit} exceeds CU_LIMIT_MAX {pack_select.CU_LIMIT_MAX}"
        )
    # _select_impl is already jitted; no extra jit wrapper needed
    return pack_select._select_impl(
        cand_rw32, cand_w32, in_use_rw32, in_use_w32,
        jnp.asarray(costs, jnp.int32), jnp.int32(int(cu_limit)), txn_limit,
    )


# ---------------------------------------------------------------------------
# dry run (driver entry: __graft_entry__.dryrun_multichip)
# ---------------------------------------------------------------------------


def dryrun_step(mesh: Mesh, msgs: np.ndarray, lens: np.ndarray) -> None:
    """Jit + execute one full pipeline step over `mesh` on tiny shapes,
    with real dp/mp shardings, plus the device pack prefilter."""
    from firedancer_tpu.ops.ed25519 import golden

    B = msgs.shape[0]
    rng = np.random.default_rng(7)
    sk = rng.integers(0, 256, 32, np.uint8).tobytes()
    pk = golden.public_from_secret(sk)
    sigs = np.zeros((B, 64), np.uint8)
    pubs = np.tile(np.frombuffer(pk, np.uint8), (B, 1))
    for i in range(B):
        s = golden.sign(sk, msgs[i, : lens[i]].tobytes())
        sigs[i] = np.frombuffer(s, np.uint8)
    # lane 1 is an exact within-batch duplicate of lane 0: the step must
    # keep only the first occurrence
    msgs[1], sigs[1] = msgs[0], sigs[0]
    tags2 = sigs[:, :8].copy().view(np.uint32).reshape(B, 2).astype(np.uint32)

    bloom = AgingBloom(mesh)  # production filter size (BLOOM_BITS = 2^28)

    step = make_step(mesh)
    sh = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    args = (
        jax.device_put(msgs, sh(P("dp", None))),
        jax.device_put(lens, sh(P("dp"))),
        jax.device_put(sigs, sh(P("dp", None))),
        jax.device_put(pubs, sh(P("dp", None))),
        jax.device_put(tags2, sh(P("dp", None))),
    )
    keep, cur1, metrics = step(*args, *bloom.buffers())
    jax.block_until_ready((keep, cur1, metrics))
    k0 = np.asarray(keep)
    m0 = np.asarray(metrics)
    assert k0[0] and not k0[1], "within-batch duplicate must be dropped"
    assert k0[2:].all(), "fresh valid txns must pass verify+dedup"
    assert m0[0] == B and m0[1] == 0, m0
    assert m0[3] == B - 1  # B txns, one within-batch duplicate
    bloom.update(cur1, metrics)

    # second step with the SAME tags: the filter must now reject all of
    # them (membership consults current|previous either side of rotation)
    keep2, _, metrics2 = step(*args, *bloom.buffers())
    jax.block_until_ready((keep2, metrics2))
    assert not np.asarray(keep2).any(), "duplicates must be dropped"
    assert np.asarray(metrics2)[2] == B  # every tag now hits the filter

    # pack prefilter on the mesh (replicated inputs)
    K, W2 = 16, 8
    cand_rw = rng.integers(0, 2**31, (K, W2)).astype(np.uint32)
    cand_w = cand_rw & rng.integers(0, 2**31, (K, W2)).astype(np.uint32)
    take = pack_prefilter(
        jnp.asarray(cand_rw), jnp.asarray(cand_w),
        jnp.zeros(W2, jnp.uint32), jnp.zeros(W2, jnp.uint32),
        jnp.full(K, 1000, jnp.int32), jnp.int32(1 << 20), 8,
    )
    jax.block_until_ready(take)
    assert np.asarray(take).any()


def dryrun_sustained(mesh: Mesh, steps: int = 6) -> None:
    """Multi-step sustained run: drives AgingBloom across TWO rotation
    boundaries (capacity = one batch), checks per-step metrics
    consistency, exercises an uneven (padded) final dp batch, and
    verifies the aging semantics end-to-end: tags are remembered for
    one full epoch after rotation and forgotten after two.
    """
    from firedancer_tpu.ops.ed25519 import golden

    dp = mesh.shape["dp"]
    B, W = 8 * dp, 64
    rng = np.random.default_rng(13)
    sk = rng.integers(0, 256, 32, np.uint8).tobytes()
    pk = golden.public_from_secret(sk)
    pubs = np.tile(np.frombuffer(pk, np.uint8), (B, 1))

    def batch(seed, n_real=B):
        r = np.random.default_rng(seed)
        msgs = r.integers(0, 256, size=(B, W), dtype=np.uint8)
        lens = np.full(B, W, np.int32)
        sigs = np.zeros((B, 64), np.uint8)
        for i in range(n_real):
            sigs[i] = np.frombuffer(
                golden.sign(sk, msgs[i].tobytes()), np.uint8
            )
        # lanes past n_real model an uneven final dp batch: zero-padded
        # (zero sig fails verify; metrics must count them as failed)
        tags2 = sigs[:, :8].copy().view(np.uint32).reshape(B, 2)
        return msgs, lens, sigs, pubs.copy(), tags2

    step = make_step(mesh)
    sh = lambda spec: NamedSharding(mesh, spec)  # noqa: E731

    def put(b):
        m, l, s, p, t = b
        return (
            jax.device_put(m, sh(P("dp", None))),
            jax.device_put(l, sh(P("dp"))),
            jax.device_put(s, sh(P("dp", None))),
            jax.device_put(p, sh(P("dp", None))),
            jax.device_put(t, sh(P("dp", None))),
        )

    bloom = AgingBloom(mesh, capacity=1)  # rotate after every batch
    first = put(batch(100))
    keep, cur, metrics = step(*first, *bloom.buffers())
    m = np.asarray(metrics)
    assert m[0] == B and m[1] == 0 and m[3] == B, m
    assert np.asarray(keep).all()
    bloom.update(cur, metrics)
    assert bloom.rotations == 1

    # epoch 1: fresh batch; epoch-0 tags must STILL be remembered (the
    # membership consults current|previous across the rotation boundary)
    keep_r, cur, metrics_r = step(*first, *bloom.buffers())
    assert not np.asarray(keep_r).any(), "post-rotation recall failed"
    bloom.update(cur, metrics_r)  # inserts 0 (all hits): no rotation
    assert bloom.rotations == 1

    for k in range(steps - 2):
        b = put(batch(200 + k))
        keep, cur, metrics = step(*b, *bloom.buffers())
        m = np.asarray(metrics)
        assert m[0] + m[1] == B, m  # every lane accounted each step
        assert m[0] == B and m[3] == B
        bloom.update(cur, metrics)
    assert bloom.rotations >= 3

    # two full epochs later the first batch's tags must be FORGOTTEN
    keep_f, cur, metrics_f = step(*first, *bloom.buffers())
    assert np.asarray(keep_f).all(), "aged-out tags must be admitted again"
    bloom.update(cur, metrics_f)

    # uneven final batch: only half the lanes carry real signed txns
    half = B // 2
    b = put(batch(999, n_real=half))
    keep, cur, metrics = step(*b, *bloom.buffers())
    m = np.asarray(metrics)
    k = np.asarray(keep)
    assert m[0] == half and m[1] == B - half, m
    assert k[:half].all() and not k[half:].any()
    print(f"dryrun_sustained ok: {steps} steps, rotations={bloom.rotations}")
