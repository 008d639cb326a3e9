"""End-to-end Ed25519 batch verification vs the golden oracle, and the
verify tile's own account of where a batch's time goes.

Kernel (slow tier; covers the reference's verify rules,
fd_ed25519_user.c:134-229 behavior): valid sigs, corrupted
sig/msg/pubkey, non-canonical s, small-order A/R, zero-length and
varying-length messages.  Every lane's verdict is cross-checked against
golden.verify.

Tile (tier-1, JAX-free: a host `device_fn` stub, the tile's hooks driven
by hand on one thread so every burst and batch is countable): the five
lifecycle stamps and the four batch_*_us hists, the mux thread's phase
counters, and that host spans are built per BATCH, never per burst.
"""

import threading
import time

import numpy as np
import pytest

from firedancer_tpu.disco import Topology, ts_diff
from firedancer_tpu.disco import trace as T
from firedancer_tpu.ops.ed25519 import golden
from firedancer_tpu.ops.ed25519 import verify as V
from firedancer_tpu.ops.ed25519.golden import L
from firedancer_tpu.tiles import verify as VT
from firedancer_tpu.tiles import wire
from firedancer_tpu.tiles.sink import SinkTile
from firedancer_tpu.tiles.synth import make_txn_pool


def _torsion_encoding():
    """A nontrivial small-order point encoding, derived via the oracle."""
    y = 2
    while True:
        cand = golden.point_decompress(int(y).to_bytes(32, "little"))
        if cand is not None:
            t = golden.scalar_mul(L, cand)
            if t != golden.IDENT:
                return golden.point_compress(t)
        y += 1


def _build_cases():
    rng = np.random.default_rng(21)
    max_len = 96
    cases = []  # (msg bytes, sig bytes, pub bytes, label)

    keys = [rng.integers(0, 256, 32, dtype=np.uint8).tobytes() for _ in range(3)]
    pubs = [golden.public_from_secret(k) for k in keys]

    for i, mlen in enumerate([0, 1, 32, 64, 95, 96]):
        sk, pk = keys[i % 3], pubs[i % 3]
        m = rng.integers(0, 256, mlen, dtype=np.uint8).tobytes()
        cases.append((m, golden.sign(sk, m), pk, f"valid len={mlen}"))

    m = rng.integers(0, 256, 48, dtype=np.uint8).tobytes()
    sig = golden.sign(keys[0], m)

    bad_sig = bytearray(sig)
    bad_sig[5] ^= 1
    cases.append((m, bytes(bad_sig), pubs[0], "corrupt R"))

    bad_s = bytearray(sig)
    bad_s[40] ^= 1
    cases.append((m, bytes(bad_s), pubs[0], "corrupt s"))

    bad_m = bytearray(m)
    bad_m[0] ^= 1
    cases.append((bytes(bad_m), sig, pubs[0], "corrupt msg"))

    cases.append((m, sig, pubs[1], "wrong pubkey"))

    # non-canonical s: s' = s + L (same residue => would verify if allowed)
    s_int = int.from_bytes(sig[32:], "little")
    sig_noncanon = sig[:32] + int(s_int + L).to_bytes(32, "little")
    cases.append((m, sig_noncanon, pubs[0], "s + L rejected"))

    tors = _torsion_encoding()
    cases.append((m, sig, tors, "small-order A"))
    cases.append((m, tors + sig[32:], pubs[0], "small-order R"))

    # identity-point A and R
    ident = golden.point_compress(golden.IDENT)
    cases.append((m, sig, ident, "identity A"))
    cases.append((m, ident + sig[32:], pubs[0], "identity R"))

    # undecompressable A / R (y with no sqrt); find one by search
    y = 2
    while golden.point_decompress(int(y).to_bytes(32, "little")) is not None:
        y += 1
    bad_pt = int(y).to_bytes(32, "little")
    cases.append((m, sig, bad_pt, "bad A encoding"))
    cases.append((m, bad_pt + sig[32:], pubs[0], "bad R encoding"))

    # sig swapped between two valid messages
    m2 = rng.integers(0, 256, 48, dtype=np.uint8).tobytes()
    sig2 = golden.sign(keys[0], m2)
    cases.append((m, sig2, pubs[0], "sig of other msg"))
    cases.append((m2, sig, pubs[0], "other msg of sig"))

    return cases, max_len


@pytest.mark.slow
def test_verify_batch_vs_golden():
    cases, max_len = _build_cases()
    b = len(cases)
    msgs = np.zeros((b, max_len), np.uint8)
    lens = np.zeros((b,), np.int32)
    sigs = np.zeros((b, 64), np.uint8)
    pubs = np.zeros((b, 32), np.uint8)
    for j, (m, s, p, _) in enumerate(cases):
        msgs[j, : len(m)] = np.frombuffer(m, np.uint8)
        lens[j] = len(m)
        sigs[j] = np.frombuffer(s, np.uint8)
        pubs[j] = np.frombuffer(p, np.uint8)

    got = np.asarray(V.verify_batch(msgs, lens, sigs, pubs))
    for j, (m, s, p, label) in enumerate(cases):
        want = golden.verify(m, s, p) == golden.ERR_OK
        assert bool(got[j]) == want, f"case '{label}': got {got[j]}, want {want}"
    # sanity: the valid cases really are valid
    assert got[:6].all()
    assert not got[6:].any()


@pytest.mark.slow
def test_verify_batch_random_roundtrip():
    rng = np.random.default_rng(22)
    b, max_len = 16, 64
    msgs = np.zeros((b, max_len), np.uint8)
    lens = rng.integers(0, max_len + 1, b).astype(np.int32)
    sigs = np.zeros((b, 64), np.uint8)
    pubs = np.zeros((b, 32), np.uint8)
    expect = np.zeros((b,), bool)
    for j in range(b):
        sk = rng.integers(0, 256, 32, dtype=np.uint8).tobytes()
        pk = golden.public_from_secret(sk)
        m = rng.integers(0, 256, lens[j], dtype=np.uint8).tobytes()
        s = bytearray(golden.sign(sk, m))
        good = j % 3 != 0
        if not good:  # corrupt a random byte of the 64-byte sig
            s[rng.integers(0, 64)] ^= 1 + rng.integers(0, 255)
        msgs[j, : lens[j]] = np.frombuffer(m, np.uint8)
        sigs[j] = np.frombuffer(bytes(s), np.uint8)
        pubs[j] = np.frombuffer(pk, np.uint8)
        expect[j] = golden.verify(m, bytes(s), pk) == golden.ERR_OK
    got = np.asarray(V.verify_batch(msgs, lens, sigs, pubs))
    assert (got == expect).all(), (got, expect)


if __name__ == "__main__":
    pytest.main([__file__, "-x", "-q"])


# ---------------------------------------------------------------------------
# the verify tile's batch lifecycle and phase accounting (tier-1)


def _admit_all(digests, sigs, pubs):
    return np.ones(len(digests), bool)


class _Rig:
    """src -> verify -> sink, built but never started: the test plays
    the run loop (drain -> on_frags -> after_credit) on its own thread,
    so bursts, batches and clock reads are countable.  The links are
    deep enough that the sink never has to run for credits."""

    def __init__(self, *, trace: bool = False, n_txns: int = 48, **kw):
        self.rows, self.szs, _ = make_txn_pool(n_txns, seed=77)
        self.tile = VT.VerifyTile(**{**dict(
            msg_width=256, max_lanes=8, pre_dedup=False,
            device_fn=_admit_all, async_depth=2), **kw})
        topo = self.topo = Topology()
        if trace:
            topo.enable_trace(sample=1, depth=1 << 10)
        topo.link("src_verify", depth=256, mtu=wire.LINK_MTU)
        topo.link("verify_sink", depth=256, mtu=wire.LINK_MTU)
        topo.tile(SinkTile(name="src"), outs=["src_verify"])
        topo.tile(self.tile, ins=[("src_verify", True)],
                  outs=["verify_sink"])
        topo.tile(SinkTile(), ins=[("verify_sink", True)])
        topo.build()
        self.ctx = topo.tiles["verify"].ctx
        self.src = topo.tiles["src"].ctx.outs[0]
        self.sent = 0
        self.published: list[dict] = []  # a copy of each meta + t_pub
        inner = self.tile._batch_published

        def record(ctx, meta):
            inner(ctx, meta)
            self.published.append({
                **{k: v for k, v in meta.items() if k.startswith("t_")
                   or k in ("pool_seq", "lanes")}, "t_pub": VT.now_ts()})

        self.tile._batch_published = record

    def boot(self):
        self.tile.on_boot(self.ctx)
        return self

    def burst(self, n: int) -> None:
        """Publish n txns upstream and hand them to the tile as ONE
        burst, the way run_loop does."""
        i = np.arange(self.sent, self.sent + n) % len(self.rows)
        self.src.publish(np.arange(self.sent, self.sent + n, dtype=np.uint64),
                         self.rows[i], self.szs[i])
        self.sent += n
        il = self.ctx.ins[0]
        frags, il.seq, ovr = il.mcache.drain(il.seq, n)
        assert len(frags) == n and not ovr
        self.tile.on_frags(self.ctx, 0, frags)

    def credit(self, credits: int = 256) -> None:
        self.ctx.credits = credits
        self.tile.after_credit(self.ctx)

    def settle(self, n_batches: int, credits: int = 256) -> None:
        deadline = time.monotonic() + 30.0
        while len(self.published) < n_batches:
            assert time.monotonic() < deadline, "batches did not land"
            self.credit(credits)
            time.sleep(1e-3)

    def counters(self) -> dict:
        self.tile._mirror_tick = 0  # the next mirror flushes the phases
        self.tile._mirror_policy_metrics(self.ctx)
        return self.ctx.metrics.read()

    def close(self):
        if self.tile._pool is not None:
            self.tile._pool.stop(timeout_s=5.0)
        self.topo.close()


@pytest.fixture
def rig_factory():
    rigs = []

    def make(**kw):
        rigs.append(_Rig(**kw))
        return rigs[-1]

    yield make
    for r in rigs:
        r.close()


def _ordered(b: dict) -> bool:
    ts = [b[k] for k in ("t_first", "t_submit", "t_disp", "t_land", "t_pub")]
    return all(ts_diff(y, x) >= 0 for x, y in zip(ts, ts[1:]))


@pytest.mark.parametrize("traced", [False, True])
def test_batch_lifecycle_stamps_and_hists(rig_factory, traced):
    """Every device batch carries the five stamps in order, and each of
    the four lifecycle hists is sampled exactly once a batch — with or
    without a tracer (the stamps are always taken)."""
    rig = rig_factory(trace=traced).boot()
    before = rig.counters()
    n = 5
    for i in range(n):
        rig.burst(5)       # 5 lanes < max_lanes: after_credit submits it
        rig.settle(i + 1)  # one in flight at a time: the pool never refuses
    after = rig.counters()
    assert after["device_batches"] - before["device_batches"] == n
    for h in VT.BATCH_HISTS:
        assert after[h]["count"] - before[h]["count"] == n, h
        assert len(after[h]["buckets"]) == 24  # wide: 65 ms is mid-domain
    assert [b["pool_seq"] for b in rig.published] == list(range(n))
    assert all(_ordered(b) for b in rig.published), rig.published
    assert sorted(b["lanes"] for b in rig.published) == [5] * n


def test_batch_split_on_a_txn_boundary_keeps_each_bursts_ingest_time(
        rig_factory):
    """Two bursts of 6 lanes into 8-lane batches: the first batch takes
    burst 1 and the head of burst 2, the tail of burst 2 becomes the
    second batch — whose t_first is burst 2's ingest time, not its own
    creation time and not burst 1's."""
    rig = rig_factory().boot()
    rig.burst(6)
    time.sleep(0.005)
    t_between = VT.now_ts()
    time.sleep(0.005)
    rig.burst(6)           # 12 staged >= 8: on_frags submits 8 at once
    time.sleep(0.005)
    rig.credit()           # the 4-lane tail goes as a partial batch
    rig.settle(2)
    first, second = rig.published
    assert (first["lanes"], second["lanes"]) == (8, 4)
    assert ts_diff(t_between, first["t_first"]) >= 4000   # burst 1's
    assert ts_diff(second["t_first"], t_between) >= 4000  # burst 2's
    assert ts_diff(second["t_submit"], second["t_first"]) >= 4000
    assert _ordered(first) and _ordered(second)
    h = rig.counters()
    # the tail waited >= 5 ms for its submit; both samples are in
    assert h["batch_fill_us"]["count"] == 2
    assert h["batch_fill_us"]["sum"] >= 5000


def test_batch_evicted_and_resubmitted_keeps_its_stamps_in_order(
        rig_factory):
    """A batch its first device fails is resubmitted under the same
    pool_seq: t_submit stays the first acceptance, t_disp/t_land are the
    serving device's, and the order still holds on every batch."""
    calls = []

    def flaky(digests, sigs, pubs):
        calls.append(len(digests))
        if len(calls) == 2:
            raise RuntimeError("device lost")
        return np.ones(len(digests), bool)

    rig = rig_factory(device_fn=flaky, devices=2, pad_full=True).boot()
    for i in range(4):
        rig.burst(3)
        rig.settle(i + 1)
    c = rig.counters()
    assert c["pool_resubmits"] >= 1 and c["device_errors"] >= 1
    assert c["fallback_batches"] == 0
    assert [b["pool_seq"] for b in rig.published] == [0, 1, 2, 3]
    assert all(_ordered(b) for b in rig.published), rig.published
    for h in VT.BATCH_HISTS:
        assert c[h]["count"] == 4, h


def test_drain_wait_is_charged_to_the_batch_that_waited_for_credits(
        rig_factory):
    """t_pub is when the LAST verdict of a batch left: a batch published
    in two parts for want of credits is sampled once, at the second."""
    rig = rig_factory().boot()
    rig.burst(6)
    rig.credit(credits=0)            # submitted; nothing may be published
    deadline = time.monotonic() + 30.0
    while not rig.tile._outq:
        assert time.monotonic() < deadline
        rig.credit(credits=0)
        time.sleep(1e-3)
    rig.credit(credits=2)            # two of six verdicts leave
    assert not rig.published and rig.tile._outq_txns == 4
    time.sleep(0.01)
    rig.credit(credits=16)
    (b,) = rig.published
    assert _ordered(b) and ts_diff(b["t_pub"], b["t_land"]) >= 10_000
    h = rig.counters()
    assert h["batch_drain_us"]["count"] == 1
    assert h["batch_drain_us"]["sum"] >= 10_000
    assert h["out_frags"] == 6


def test_phase_counters_advance_and_pool_full_only_while_refused(
        rig_factory):
    """expand/submit/results/publish all grow over a run; pool_full_ns
    grows only while the pool refuses new work (the tile then leaves
    its frags in the ring: in_budget is 0)."""
    gate, entered = threading.Event(), threading.Event()

    def gated(digests, sigs, pubs):
        entered.set()
        assert gate.wait(30.0)
        return np.ones(len(digests), bool)

    rig = rig_factory(device_fn=gated, async_depth=1).boot()
    tile = rig.tile
    try:
        assert tile.in_budget(rig.ctx) is None
        rig.burst(3)
        rig.credit()                 # batch 0: the worker takes it, blocks
        assert entered.wait(10.0)
        assert tile.in_budget(rig.ctx) is None
        rig.burst(3)
        rig.credit()                 # batch 1: sits in the request queue
        c = rig.counters()
        assert c["pool_full_ns"] == 0 and c["expand_ns"] > 0
        assert c["submit_ns"] > 0 and c["results_ns"] == 0
        assert tile.in_budget(rig.ctx) == 0   # refused: from here it counts
        time.sleep(0.02)
        assert tile.in_budget(rig.ctx) == 0
        refused = rig.counters()["pool_full_ns"]  # an open refusal counts
        assert refused >= 20_000_000
    finally:
        gate.set()
    rig.settle(2)
    assert tile.in_budget(rig.ctx) is None    # accepted again: it stops
    done = rig.counters()
    assert done["pool_full_ns"] >= refused
    assert done["results_ns"] > 0 and done["publish_ns"] > 0
    for _ in range(40):              # idle turns, pool open: no growth
        assert tile.in_budget(rig.ctx) is None
        rig.credit()
    idle = rig.counters()
    for k in VT.PHASE_COUNTERS:
        assert idle[k] == done[k], k


def test_host_spans_are_built_per_batch_never_per_burst(rig_factory):
    """The `fdt.verify.*` spans wrap per-BATCH steps (two on the mux
    thread, two on the worker): many bursts into few batches build four
    a batch, and a tile whose process holds no JAX builds none at all."""
    built = []
    lock = threading.Lock()

    class Span:
        def __init__(self, name, **kw):
            with lock:
                built.append((name, kw, threading.current_thread().name))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    rig = rig_factory()
    rig.tile._span = Span            # as _make_device_fns binds jax's
    rig.boot()
    for _ in range(12):              # 12 bursts of 2 lanes -> 3 batches of 8
        rig.burst(2)
    rig.settle(3)
    assert rig.ctx.metrics.read()["device_batches"] == 3
    names = sorted(n for n, _, _ in built)
    assert names == sorted(["fdt.verify.submit", "fdt.verify.dispatch",
                            "fdt.verify.land", "fdt.verify.results"] * 3)
    for step in ("submit", "dispatch", "land", "results"):
        kws = [kw for n, kw, _ in built if n == f"fdt.verify.{step}"]
        assert [kw["seq"] for kw in kws] == [0, 1, 2], step
        assert all(kw["lanes"] == 8 for kw in kws)
    threads = {n: t for n, _, t in built}
    assert threads["fdt.verify.dispatch"] == threads["fdt.verify.land"]
    assert threads["fdt.verify.submit"] != threads["fdt.verify.land"]
    # the clock tie: one zero-length span a second, from housekeeping
    built.clear()
    t0 = time.monotonic_ns()
    for _ in range(5):
        rig.tile.during_housekeeping(rig.ctx)
    ((name, kw, _),) = built
    assert name == "fdt.clock" and 0 <= kw["mono_ns"] - t0 < 10**9
    # with no JAX in the tile's process there is no span object at all
    plain = rig_factory().boot()
    assert plain.tile._span is VT._no_span
    assert VT._no_span("a", seq=1) is VT._no_span("b")
    plain.tile.during_housekeeping(plain.ctx)
