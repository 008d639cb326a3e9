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
counters, that host spans are built per BATCH, never per burst, and the
submit rule (a gated `device_fn`: batches land only as the test lets
them): a partial batch is held while its device has PARTIAL_AHEAD in
flight, and one under a kernel tile while any device of the pool has a
batch in flight; a full one goes up to `async_depth`.
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


def _until(cond, what: str = "condition") -> None:
    deadline = time.monotonic() + 30.0
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(1e-3)


class _Gate:
    """A `device_fn` shaped like JAX's: the call (the dispatch) returns
    at once with a future, and reading the future (the land: np.asarray)
    blocks until the test gives it a permit with `land()` — so batches
    land one a permit, in dispatch order on each device."""

    def __init__(self):
        self.sem = threading.Semaphore(0)
        self.lock = threading.Lock()
        self.landed = 0

    def __call__(self, digests, sigs, pubs):
        return _GatedResult(self, len(digests))

    def land(self, n: int = 1) -> None:
        """Let n of the batches in flight land, and wait until each has
        taken its permit (so none is banked for a later batch)."""
        want = self.landed + n
        for _ in range(n):
            self.sem.release()
        _until(lambda: self.landed >= want, "no batch in flight took it")

    def open(self) -> None:
        for _ in range(10_000):
            self.sem.release()


class _GatedResult:
    def __init__(self, gate, n):
        self.gate, self.n = gate, n

    def __array__(self, dtype=None, copy=None):
        assert self.gate.sem.acquire(timeout=30.0)
        with self.gate.lock:
            self.gate.landed += 1
        return np.ones(self.n, bool)


def _inflight(rig) -> list[int]:
    return [w.inflight() for w in rig.tile._pool.workers]


def _fill_ahead(rig, lanes: int = 3) -> None:
    """Put PARTIAL_AHEAD partial batches in flight on a one-device
    pool: each finds the device below the mark, so each goes at once."""
    for _ in range(VT.PARTIAL_AHEAD):
        rig.burst(lanes)
        rig.credit()
        assert rig.tile._staged_lanes == 0
    assert _inflight(rig) == [VT.PARTIAL_AHEAD]


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
    rig.burst(6)           # 12 staged >= 8
    time.sleep(0.005)
    rig.credit()           # 8 go as a full batch; the 4-lane tail goes
    rig.settle(2)          # as a partial one, now or after that lands
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

    rig = rig_factory(device_fn=flaky, devices=2).boot()
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
    grows only while no device can take a FULL batch (the tile then
    leaves its frags in the ring: in_budget is 0) — not while a partial
    batch is held for a land, when the ring stays open."""
    gate = _Gate()
    rig = rig_factory(device_fn=gate, async_depth=VT.PARTIAL_AHEAD + 1).boot()
    tile = rig.tile
    try:
        assert tile.in_budget(rig.ctx) is None
        _fill_ahead(rig)       # these go at once; the worker blocks
        assert tile.in_budget(rig.ctx) is None
        rig.burst(3)
        rig.credit()                 # held: it would queue behind them
        assert tile._staged_lanes == 3
        c = rig.counters()
        assert c["pool_full_ns"] == 0 and c["expand_ns"] > 0
        assert c["submit_ns"] > 0 and c["results_ns"] == 0
        assert tile.in_budget(rig.ctx) is None    # a hold is no refusal
        rig.burst(5)
        rig.credit()                 # 8 staged: a full batch goes at once
        assert tile._staged_lanes == 0
        assert rig.counters()["pool_full_ns"] == 0
        assert tile.in_budget(rig.ctx) == 0   # refused: from here it counts
        time.sleep(0.02)
        assert tile.in_budget(rig.ctx) == 0
        refused = rig.counters()["pool_full_ns"]  # an open refusal counts
        assert refused >= 20_000_000
    finally:
        gate.open()
    rig.settle(VT.PARTIAL_AHEAD + 1)
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


# ---------------------------------------------------------------------------
# the submit rule: when a staged batch may go to a device (tier-1)


def test_partial_batch_is_held_while_one_is_in_flight_and_goes_on_the_land(
        rig_factory):
    """With PARTIAL_AHEAD batches in flight a partial batch stays staged
    — the ring stays open and staging grows with every burst — and it
    goes, as ONE batch, on the first after_credit after a land."""
    gate = _Gate()
    rig = rig_factory(device_fn=gate, async_depth=3).boot()
    tile = rig.tile
    try:
        _fill_ahead(rig)
        for k in (1, 2):
            rig.burst(2)
            rig.credit()
            assert tile._staged_lanes == 2 * k and tile._held
            assert tile.in_budget(rig.ctx) is None
            assert _inflight(rig) == [VT.PARTIAL_AHEAD]
        for _ in range(20):          # turns without a land change nothing
            rig.credit()
        assert tile._staged_lanes == 4
        assert rig.counters()["pool_full_ns"] == 0
        gate.land()
        _until(lambda: _inflight(rig) == [VT.PARTIAL_AHEAD - 1], "no land")
        rig.credit()                 # the turn that sees the land
        assert tile._staged_lanes == 0 and not tile._held
        assert _inflight(rig) == [VT.PARTIAL_AHEAD]
    finally:
        gate.open()
    rig.settle(VT.PARTIAL_AHEAD + 1)
    assert [b["lanes"] for b in rig.published] == (
        [3] * VT.PARTIAL_AHEAD + [4])
    held = rig.published[-1]
    assert _ordered(held)
    c = rig.counters()
    assert (c["held_batches"], c["full_batches"]) == (1, 0)
    assert c["device_batches"] == VT.PARTIAL_AHEAD + 1
    assert c["out_frags"] == 3 * VT.PARTIAL_AHEAD + 4


def test_partial_batch_goes_at_once_with_nothing_in_flight(rig_factory):
    """Trickle traffic pays no linger: an idle device takes whatever is
    staged in the same turn, and such a batch is neither held nor full."""
    rig = rig_factory(async_depth=3).boot()
    for i in range(4):
        rig.burst(1 + i)
        assert rig.tile._staged_lanes == 1 + i   # on_frags only stages
        rig.credit()
        assert rig.tile._staged_lanes == 0 and not rig.tile._held
        rig.settle(i + 1)
    c = rig.counters()
    assert c["device_batches"] == 4
    assert (c["held_batches"], c["full_batches"]) == (0, 0)
    assert c["batch_fill_us"]["sum"] < 4 * 5000  # no hold in the fill time


def test_full_batches_go_up_to_async_depth_in_flight_and_no_further(
        rig_factory):
    """Rule 1: a full batch does not wait for a land — it queues behind
    others up to `async_depth` in flight, counted once (not a request
    queue of that depth plus as many dispatched) — and beyond that the
    tile refuses the ring."""
    gate = _Gate()
    rig = rig_factory(device_fn=gate, async_depth=3).boot()
    tile = rig.tile
    try:
        for k in (1, 2, 3):
            assert tile.in_budget(rig.ctx) is None
            rig.burst(8)
            rig.credit()
            assert tile._staged_lanes == 0 and _inflight(rig) == [k]
        assert tile.in_budget(rig.ctx) == 0
        rig.burst(8)                 # (the run loop would not drain it)
        rig.credit()
        assert tile._staged_lanes == 8 and _inflight(rig) == [3]
        assert tile.in_budget(rig.ctx) == 0
        gate.land()
        _until(lambda: _inflight(rig) == [2], "no land")
        rig.credit()
        assert tile._staged_lanes == 0 and _inflight(rig) == [3]
    finally:
        gate.open()
    rig.settle(4)
    c = rig.counters()
    assert (c["device_batches"], c["full_batches"], c["held_batches"]) == (
        4, 4, 0)
    assert c["pool_full_ns"] > 0
    assert [b["lanes"] for b in rig.published] == [8] * 4


def test_two_devices_carry_two_partial_batches_one_each(rig_factory):
    """The rule reads the pool: with `devices=2` a partial batch under
    one kernel tile is held while a batch is in flight, though the other
    device is idle; it grows, and goes as one batch on the land — to the
    next device in rotation, so the two devices carry one batch each."""
    gate = _Gate()
    rig = rig_factory(device_fn=gate, devices=2, async_depth=3).boot()
    tile = rig.tile
    try:
        rig.burst(3)
        rig.credit()                 # the pool is idle: it goes at once
        assert tile._staged_lanes == 0 and _inflight(rig) == [1, 0]
        for k in (1, 2):
            rig.burst(2)
            rig.credit()
            assert tile._staged_lanes == 2 * k and tile._held
            assert tile.in_budget(rig.ctx) is None
            assert _inflight(rig) == [1, 0]     # device 1 stays idle
        gate.land()
        _until(lambda: _inflight(rig) == [0, 0], "no land")
        rig.credit()                 # the turn that sees the land
        assert tile._staged_lanes == 0 and not tile._held
        assert _inflight(rig) == [0, 1]
    finally:
        gate.open()
    rig.settle(2)
    assert [b["lanes"] for b in rig.published] == [3, 4]
    assert [b["pool_seq"] for b in rig.published] == [0, 1]
    c = rig.counters()
    assert (c["held_batches"], c["full_batches"], c["spread_batches"]) == (
        1, 0, 0)
    assert (c["dev0_landed"], c["dev1_landed"]) == (1, 1)


@pytest.mark.parametrize("devices", [2, 4])
def test_a_partial_batch_that_fills_a_tile_goes_to_an_idle_device_at_once(
        rig_factory, devices):
    """A partial batch held for the land grows until it fills one kernel
    tile; then it is real work for an idle device and goes at once,
    beside the batch in flight (`spread_batches`, not `held_batches`).
    The next sub-tile batch is held again, whatever devices are idle."""
    tile_lanes = VT.KERNEL_TILE
    gate = _Gate()
    rig = rig_factory(device_fn=gate, devices=devices, async_depth=3,
                      max_lanes=2 * tile_lanes).boot()
    tile = rig.tile
    idle = [0] * (devices - 2)
    try:
        rig.burst(3)
        rig.credit()
        assert _inflight(rig) == [1, 0] + idle
        rig.burst(100)
        rig.credit()                 # under a tile: held
        assert tile._staged_lanes == 100 and tile._held
        rig.burst(tile_lanes - 100)
        rig.credit()                 # one tile staged: it goes
        assert tile._staged_lanes == 0 and not tile._held
        assert _inflight(rig) == [1, 1] + idle
        rig.burst(5)
        rig.credit()
        assert tile._staged_lanes == 5 and tile._held
        assert _inflight(rig) == [1, 1] + idle
    finally:
        gate.open()
    rig.settle(3)
    assert [b["lanes"] for b in rig.published] == [3, tile_lanes, 5]
    c = rig.counters()
    assert (c["spread_batches"], c["held_batches"], c["full_batches"]) == (
        1, 1, 0)
    assert c["out_frags"] == 3 + tile_lanes + 5


#: a fixed script of (bursts, credits, lands) for a width-one tile, and
#: what the rule before the pool-wide hold did with it: lanes of each
#: batch in submit order, and (device, held, full) batch counts
_WIDTH_ONE_SCRIPT = "b5 c b2 c b3 c l c b9 c b4 c b6 c l c l c b1 c l c " \
    "b12 c b8 c b8 c b2 c l c l c l c b7 c b3 c l c l c"
_WIDTH_ONE_LANES = [5, 5, 8, 8, 4, 8, 8, 8, 8, 8]
_WIDTH_ONE_COUNTS = (10, 2, 7)


def test_width_one_makes_the_decisions_it_made_before(rig_factory):
    """At width one "no device has a batch in flight" and "the pool has
    none" are one test: on a fixed script of bursts, credits and lands
    the tile sends the batches it sent before the rule read the pool,
    counts them alike, and never counts a spread."""
    gate = _Gate()
    rig = rig_factory(device_fn=gate, async_depth=3).boot()
    lanes: list[int] = []
    w = rig.tile._pool.workers[0]

    def submit(meta, args, mode="auto", inner=w.submit):
        lanes.append(meta["lanes"])
        inner(meta, args, mode)

    w.submit = submit
    try:
        for step in _WIDTH_ONE_SCRIPT.split():
            if step[0] == "b":
                rig.burst(int(step[1:]))
            elif step == "c":
                rig.credit()
            else:
                n = w.inflight()
                gate.land()
                _until(lambda: w.inflight() == n - 1, "no land")
        assert rig.tile._staged_lanes == 0
    finally:
        gate.open()
    rig.settle(len(lanes))
    c = rig.counters()
    assert lanes == _WIDTH_ONE_LANES
    assert (c["device_batches"], c["held_batches"], c["full_batches"]) == (
        _WIDTH_ONE_COUNTS)
    assert c["spread_batches"] == 0


def test_halt_flushes_a_held_stage(rig_factory):
    """on_halt does not wait for the rule: what is staged goes behind
    the batch in flight, and everything lands and is published."""
    gate = _Gate()
    rig = rig_factory(device_fn=gate, async_depth=3).boot()
    _fill_ahead(rig)
    rig.burst(5)
    rig.credit()
    assert rig.tile._staged_lanes == 5 and rig.tile._held
    threading.Timer(0.05, gate.open).start()
    rig.tile.on_halt(rig.ctx)
    assert rig.tile._staged_lanes == 0 and not rig.tile._outq
    assert rig.tile._pool.idle()
    assert [b["lanes"] for b in rig.published] == (
        [3] * VT.PARTIAL_AHEAD + [5])
    c = rig.ctx.metrics.read()
    assert c["out_frags"] == 3 * VT.PARTIAL_AHEAD + 5
    # the flush is neither of the rule's two classes
    assert (c["held_batches"], c["full_batches"]) == (0, 0)


def test_elastic_drain_completes_with_a_held_stage(rig_factory):
    """A retiring member with a held partial batch is not drained until
    that batch, too, has gone, landed and been published — and it does
    go: the hold ends with the land of the batch in flight."""
    gate = _Gate()
    rig = rig_factory(device_fn=gate, async_depth=3).boot()
    tile = rig.tile
    try:
        _fill_ahead(rig)
        rig.burst(4)
        rig.credit()
        assert tile._held and not tile.elastic_drained(rig.ctx)
        gate.land(VT.PARTIAL_AHEAD)
        rig.settle(VT.PARTIAL_AHEAD)   # the credit turns submit the held one
        assert tile._staged_lanes == 0
        assert not tile.elastic_drained(rig.ctx)   # it is in flight now
        gate.land()
        rig.settle(VT.PARTIAL_AHEAD + 1)
        assert tile.elastic_drained(rig.ctx)
    finally:
        gate.open()
    assert rig.counters()["held_batches"] == 1


def test_crash_teardown_drops_a_held_stage_and_the_next_life_starts_clean(
        rig_factory):
    """on_crash discards staging (the ring replay re-delivers): the held
    flag must not survive into the next incarnation's first batch."""
    gate = _Gate()
    rig = rig_factory(device_fn=gate, async_depth=3).boot()
    _fill_ahead(rig)
    rig.burst(4)
    rig.credit()
    assert rig.tile._held
    gate.open()
    rig.tile.on_crash(rig.ctx)
    assert not rig.tile._held and rig.tile._staged_lanes == 0
    assert rig.tile.ack_floor(rig.ctx, 0) is None
    rig.boot()
    n0 = len(rig.published)
    rig.burst(2)
    rig.credit()
    rig.settle(n0 + 1)
    assert rig.counters()["held_batches"] == 0


@pytest.mark.parametrize("devices,seed,max_lanes", [
    pytest.param(d, s, n, id=f"{d}-{s}" if n == 8 else f"{d}-{s}-{n}")
    for d, s, n in ((1, 1, 8), (1, 2, 8), (1, 3, 8), (2, 4, 8), (2, 5, 8),
                    (3, 6, 8), (1, 7, 2 * VT.KERNEL_TILE),
                    (4, 8, 2 * VT.KERNEL_TILE))])
def test_random_bursts_publish_every_txn_once_in_ring_order(
        rig_factory, devices, seed, max_lanes):
    """Random bursts, credits and lands against the rule: every submit
    obeys it, ack_floor is exactly the oldest unpublished frag at every
    step, the counters add up, and the published stream is the ring's,
    txn for txn.  Batches of two kernel tiles let a partial batch fill
    one (bursts scale with `max_lanes`)."""
    rng = np.random.default_rng(seed)
    gate = _Gate()
    depth = 3
    unit = max(max_lanes // 32, 1)      # lanes a burst step: 1 at 8 lanes
    rig = rig_factory(device_fn=gate, devices=devices, async_depth=depth,
                      max_lanes=max_lanes).boot()
    tile, ctx = rig.tile, rig.ctx
    tags = rig.rows[:, 1:9].copy().view("<u8").ravel()
    out: list[int] = []
    publish = ctx.publish

    def recording(t, *a, **kw):
        out.extend(int(x) for x in t)
        return publish(t, *a, **kw)

    ctx.publish = recording
    #: (lanes, in flight ahead of it on its device, in the whole pool)
    subs: list[tuple[int, int, int]] = []
    for w in tile._pool.workers:
        def submit(meta, args, mode="auto", w=w, inner=w.submit):
            subs.append((meta["lanes"], w.inflight(), tile._pool.inflight()))
            inner(meta, args, mode)
        w.submit = submit
    seq0 = int(ctx.ins[0].seq)
    held_turns: list[bool] = []   # after each step: a partial batch held

    def check():
        floor = tile.ack_floor(ctx, 0)
        if len(out) == rig.sent:
            assert floor is None
        else:
            assert floor == seq0 + len(out)
        assert tile._staged_lanes == sum(
            len(b["sigs"]) for b in tile._staged)
        assert all(0 <= n <= depth for n in _inflight(rig))
        held_turns.append(tile._held)

    try:
        for _ in range(300):
            act = rng.integers(0, 6)
            if act <= 1 and tile.in_budget(ctx) is None:
                rig.burst(int(rng.integers(1, 13)) * unit)
            elif act == 2:
                rig.credit(int(rng.integers(0, 17)))
            elif act == 3 and sum(_inflight(rig)):
                gate.land()
            else:
                rig.credit()
            check()
    finally:
        gate.open()
    _until(lambda: (rig.credit(), check(), len(out) == rig.sent)[-1],
           "the tail did not drain")
    assert out == [int(t) for t in tags[np.arange(rig.sent) % len(tags)]]
    for lanes, ahead, pool_ahead in subs:
        if lanes == max_lanes:
            assert ahead < depth, subs
        elif lanes < VT.KERNEL_TILE:
            assert pool_ahead == 0, subs
        else:
            assert ahead < VT.PARTIAL_AHEAD, subs
    c = rig.counters()
    assert c["device_batches"] == len(subs) == len(rig.published)
    assert c["full_batches"] == sum(1 for n, _, _ in subs if n == max_lanes)
    # the rule held a partial batch; in a wider pool, where a batch is
    # nearly always in flight, the hold may end in a full batch each time
    assert any(held_turns)
    assert c["held_batches"] <= len(subs) - c["full_batches"]
    assert c["held_batches"] > 0 or devices > 1
    # a spread was sent beside a batch in flight: it filled a tile
    assert c["spread_batches"] <= sum(
        1 for n, _, _ in subs if VT.KERNEL_TILE <= n < max_lanes)
    assert c["verified_sigs"] == c["out_frags"] == rig.sent
