"""Native block egress (ISSUE 12): poh, shred, and net as native stem
handlers + after-credit hooks, with batched datagram syscalls.

Tier-1 contract:

  1. SHA-256 PRIMITIVES: fdt_sha256 / _mix / _append differential-fuzzed
     against hashlib (streaming, block boundaries, empty, >1-block).
  2. GOLDEN PARITY: each native path produces publish streams and chain
     state BIT-IDENTICAL to the Python loop on the same deterministic
     input — poh across mixin/tick/slot-boundary interleavings, shred
     across entry append → boundary shred → sign request/response →
     queue drain, net across real-socket rx/tx bursts.
  3. ZERO PYTHON PER FRAG: the bank→poh→shred leader egress chain at
     steady state advances stem_frags/entries with py_frags and
     py_credit FLAT on poh and shred (the ROADMAP item-1 counter
     assert).
  4. SIGKILL MID-BURST: killing the poh child mid-stream recovers
     through the chain journal — every microblock mixed EXACTLY once,
     the entry stream verifies as one gapless hash chain end to end.
"""

from __future__ import annotations

import hashlib
import os
import signal
import socket
import time

import numpy as np
import pytest

from firedancer_tpu.disco import Topology
from firedancer_tpu.disco.metrics import Metrics
from firedancer_tpu.disco.mux import InLink, MuxCtx, OutLink, Tile
from firedancer_tpu.disco.supervisor import RestartPolicy, Supervisor
from firedancer_tpu.tango import rings as R
from firedancer_tpu.tiles.poh import ENTRY_SZ, SLOT_BOUNDARY_TAG, PohTile
from firedancer_tpu.tiles.shred import ShredTile
from firedancer_tpu.tiles.sink import SinkTile, read_siglog
from firedancer_tpu.ballet import shred as SH


@pytest.fixture(autouse=True)
def _no_shm_leak(no_shm_leak):
    """Every test here runs under conftest's /dev/shm leak check."""
    yield


# ---------------------------------------------------------------------------
# 1. SHA-256 primitives vs hashlib


def test_sha256_differential_fuzz():
    """Every length through both block-boundary regimes (one padding
    block vs two) plus larger multi-block inputs, against hashlib."""
    lib = R._lib
    rng = np.random.default_rng(12)
    sizes = list(range(0, 132)) + [192, 1000, 4096, 5000]
    for sz in sizes:
        msg = bytes(rng.integers(0, 256, max(sz, 1), np.uint8))[:sz]
        buf = np.frombuffer(msg, np.uint8).copy() if sz else np.zeros(
            1, np.uint8
        )
        out = np.zeros(32, np.uint8)
        lib.fdt_sha256(buf.ctypes.data, sz, out.ctypes.data)
        assert out.tobytes() == hashlib.sha256(msg).digest(), sz


def test_sha256_mix_and_append_match_hashlib():
    rng = np.random.default_rng(13)
    for _ in range(16):
        prev = rng.integers(0, 256, 32, np.uint8).astype(np.uint8)
        mix = rng.integers(0, 256, 32, np.uint8).astype(np.uint8)
        out = np.zeros(32, np.uint8)
        R._lib.fdt_sha256_mix(
            prev.ctypes.data, mix.ctypes.data, out.ctypes.data
        )
        assert out.tobytes() == hashlib.sha256(
            prev.tobytes() + mix.tobytes()
        ).digest()
    st = rng.integers(0, 256, 32, np.uint8).astype(np.uint8)
    for n in (0, 1, 7, 64):
        ref = st.tobytes()
        for _ in range(n):
            ref = hashlib.sha256(ref).digest()
        got = st.copy()
        R._lib.fdt_sha256_append(got.ctypes.data, n)
        assert got.tobytes() == ref, n


# ---------------------------------------------------------------------------
# 2a. poh: raw-ring golden parity across mixin/tick interleavings


def _mk_poh(tick_batch=8, ticks_per_slot=16, depth=1 << 10, n_ins=1,
            ticks=True):
    ins = []
    for i in range(n_ins):
        mc = R.MCache(np.zeros(R.MCache.footprint(depth), np.uint8), depth)
        dc = R.DCache(
            np.zeros(R.DCache.footprint(1024, depth), np.uint8), 1024,
            depth,
        )
        ins.append(
            InLink(f"mb{i}", mc, dc,
                   R.FSeq(np.zeros(R.FSeq.footprint(), np.uint8)))
        )
    out_mc = R.MCache(np.zeros(R.MCache.footprint(depth), np.uint8), depth)
    out_dc = R.DCache(
        np.zeros(R.DCache.footprint(ENTRY_SZ, depth), np.uint8), ENTRY_SZ,
        depth,
    )
    cons = R.FSeq(np.zeros(R.FSeq.footprint(), np.uint8))
    poh = PohTile(
        tick_batch=tick_batch, ticks_per_slot=ticks_per_slot, slot_ms=0
    )
    schema = poh.schema.with_base()
    ctx = MuxCtx(
        "poh", R.CNC(np.zeros(R.CNC.footprint(), np.uint8)), ins,
        [OutLink("entries", out_mc, out_dc, [cons])],
        Metrics(np.zeros(Metrics.footprint(schema), np.uint8), schema),
    )
    poh.on_boot(ctx)
    if not ticks:
        # park the pacing deadline far out so the after-credit hook
        # never fires: mixin-only streams for the replay/crash tests
        poh._w[4] = 1          # interval (paced)
        poh._w[3] = 1 << 62    # next_batch_ns
    return poh, ctx, cons


def _feed_mbs(ctx, i, n, seed, seq0):
    il = ctx.ins[i]
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, 256, (n, 200), np.uint8).astype(np.uint8)
    szs = np.full(n, 200, np.uint16)
    chunks = il.dcache.write_batch(rows, szs)
    il.mcache.publish_batch(
        seq0, np.arange(1, n + 1, dtype=np.uint64), chunks, szs, None, 3,
        None,
    )
    return rows


def _drain_out(ol, cons, max_frags=2048):
    seq = cons.query()
    frags, seq, ovr = ol.mcache.drain(seq, max_frags)
    assert ovr == 0
    out = [
        (int(f["sig"]), int(f["sz"]),
         bytes(ol.dcache.read(int(f["chunk"]), int(f["sz"]))))
        for f in frags
    ]
    cons.update(seq)
    return out


def test_poh_stem_bit_identical_on_raw_rings():
    """Scripted mixin/tick interleaving (tick batches crossing slot
    boundaries included): entry stream — sig, sz, payload bytes — plus
    the final chain state/hashcnt/slot words must match the Python loop
    exactly."""

    def run(native):
        poh, ctx, cons = _mk_poh()
        stem = None
        if native:
            spec = poh.native_handler(ctx)
            assert spec is not None and spec.ac_handler
            stem = R.Stem(ctx.ins, ctx.outs, spec, cap=64)
        stream = []
        seq0 = 0
        for r in range(6):
            _feed_mbs(ctx, 0, 3 + r, 50 + r, seq0)
            seq0 += 3 + r
            if native:
                stem.run(64, 7)
            else:
                il = ctx.ins[0]
                frags, il.seq, _ = il.mcache.drain(il.seq, 64)
                poh.on_frags(ctx, 0, frags)
                poh.after_credit(ctx)
            stream += _drain_out(ctx.outs[0], cons)
        return stream, poh

    g_stream, g = run(False)
    n_stream, n = run(True)
    assert g_stream == n_stream, (len(g_stream), len(n_stream))
    assert bytes(g.state) == bytes(n.state)
    assert g.hashcnt == n.hashcnt and g.slot == n.slot
    assert g.ticks_in_slot == n.ticks_in_slot
    # the stream contains all three entry kinds
    sigs = {s for s, _, _ in g_stream}
    assert 1 in sigs and 8 in sigs
    assert any(s & SLOT_BOUNDARY_TAG for s in sigs)
    # chain continuity: every entry's prev is the previous entry's state
    for a, b in zip(g_stream, g_stream[1:]):
        assert b[2][0:32] == a[2][72:104]


def test_poh_replay_below_high_water_is_skipped():
    """Replaying an already-mixed window (the supervisor's at-least-once
    delivery) must be a metered skip, not a re-mix."""
    poh, ctx, cons = _mk_poh(ticks=False)
    stem = R.Stem(ctx.ins, ctx.outs, poh.native_handler(ctx), cap=64)
    _feed_mbs(ctx, 0, 8, 5, 0)
    stem.run(64, 7)
    first = _drain_out(ctx.outs[0], cons)
    state0 = bytes(poh.state)
    # rewind the consumer cursor and replay the same window
    ctx.ins[0].seq = 0
    stem.run(64, 7)
    assert int(stem.counters[5]) == 8  # replayed_mixins
    assert _drain_out(ctx.outs[0], cons) == []
    assert bytes(poh.state) == state0
    assert len(first) == 8


def test_poh_crash_window_recovers_exactly_once():
    """Kill (simulated: exception from the crash probe) between the
    journal arm and the publish: a re-boot re-derives the emission,
    publishes the missing entry once, and the replayed frag is skipped."""
    poh, ctx, cons = _mk_poh(ticks=False)
    boom = RuntimeError("crash window")

    def probe():
        raise boom

    poh._crash_probe = probe
    _feed_mbs(ctx, 0, 1, 9, 0)
    il = ctx.ins[0]
    frags, il.seq, _ = il.mcache.drain(il.seq, 8)
    with pytest.raises(RuntimeError):
        poh.on_frags(ctx, 0, frags)
    # died inside the window: journal armed, state advanced, entry
    # unpublished
    assert int(poh._jnl[0]) == 1
    assert _drain_out(ctx.outs[0], cons) == []
    poh._crash_probe = None
    ctx.incarnation += 1
    poh.on_boot(ctx)  # rejoins the same (idempotent) chain block
    out = _drain_out(ctx.outs[0], cons)
    assert len(out) == 1 and out[0][0] == 1
    assert out[0][2][72:104] == bytes(poh.state)
    # the supervisor replay of the same frag is now a metered skip
    il.seq = 0
    frags, il.seq, _ = il.mcache.drain(il.seq, 8)
    poh.on_frags(ctx, 0, frags)
    assert ctx.metrics.counter("replayed_mixins") == 1
    assert _drain_out(ctx.outs[0], cons) == []


# ---------------------------------------------------------------------------
# 2b. shred: raw-ring golden parity (keyguard shape)


def _mk_shred(depth=1 << 10):
    def ring(d, mtu=None):
        mc = R.MCache(np.zeros(R.MCache.footprint(d), np.uint8), d)
        dc = None
        if mtu is not None:
            dc = R.DCache(
                np.zeros(R.DCache.footprint(mtu, d), np.uint8), mtu, d
            )
        return mc, dc

    e_mc, e_dc = ring(depth, ENTRY_SZ)
    r_mc, r_dc = ring(256, 64)
    ins = [
        InLink("ent", e_mc, e_dc,
               R.FSeq(np.zeros(R.FSeq.footprint(), np.uint8))),
        InLink("sresp", r_mc, r_dc,
               R.FSeq(np.zeros(R.FSeq.footprint(), np.uint8))),
    ]
    o_mc, o_dc = ring(depth, SH.MAX_SZ)
    q_mc, q_dc = ring(256, 32)
    ofs = R.FSeq(np.zeros(R.FSeq.footprint(), np.uint8))
    qfs = R.FSeq(np.zeros(R.FSeq.footprint(), np.uint8))
    outs = [
        OutLink("shreds", o_mc, o_dc, [ofs]),
        OutLink("sreq", q_mc, q_dc, [qfs]),
    ]
    sh = ShredTile(shred_version=7)
    schema = sh.schema.with_base()
    ctx = MuxCtx(
        "shred", R.CNC(np.zeros(R.CNC.footprint(), np.uint8)), ins, outs,
        Metrics(np.zeros(Metrics.footprint(schema), np.uint8), schema),
    )
    sh.on_boot(ctx)
    return sh, ctx, ofs, qfs


def _feed_entries(ctx, payloads, sigs, seq0):
    il = ctx.ins[0]
    rows = np.zeros((len(payloads), ENTRY_SZ), np.uint8)
    szs = np.zeros(len(payloads), np.uint16)
    for i, p in enumerate(payloads):
        rows[i, : len(p)] = np.frombuffer(p, np.uint8)
        szs[i] = len(p)
    chunks = il.dcache.write_batch(rows, szs)
    il.mcache.publish_batch(
        seq0, np.asarray(sigs, np.uint64), chunks, szs, None, 3, None
    )


def test_shred_stem_bit_identical_on_raw_rings():
    """Entries append natively, the slot boundary hands back to the
    Python shredder, sign requests drain from the shared sign queue,
    responses patch + queue natively, and the out-queue drain publishes
    — streams on BOTH out rings byte-identical to the Python loop."""

    def run(native):
        sh, ctx, ofs, qfs = _mk_shred()
        stem = spec = None
        ctrs = {}
        if native:
            spec = sh.native_handler(ctx)
            assert spec is not None and spec.manual and spec.ac_handler
            stem = R.Stem(ctx.ins, ctx.outs, spec, cap=256)
            ctrs = dict.fromkeys(spec.counters, 0)

        def step():
            if stem is not None:
                _g, stat, _i = stem.run(256, 5)
                for j, nm in enumerate(spec.counters):
                    ctrs[nm] += int(stem.counters[j])
                if stat != R.STEM_PYTHON:
                    return
            for i in (0, 1):
                il = ctx.ins[i]
                frags, il.seq, _ = il.mcache.drain(il.seq, 256)
                if len(frags):
                    sh.on_frags(ctx, i, frags)
            sh.after_credit(ctx)

        rng = np.random.default_rng(3)
        stream, reqs = [], []
        seq0 = sseq = 0
        for r in range(3):
            pls = [
                bytes(rng.integers(0, 256, 104, np.uint8))
                for _ in range(6)
            ]
            _feed_entries(ctx, pls, [7] * 6, seq0)
            seq0 += 6
            step()
            _feed_entries(
                ctx, [b"\0" * 104], [SLOT_BOUNDARY_TAG | (r + 1)], seq0
            )
            seq0 += 1
            step()
            reqs_r = _drain_out(ctx.outs[1], qfs)
            reqs += reqs_r
            sil = ctx.ins[1]
            for tag, _sz, root in reqs_r:
                sig = (
                    hashlib.sha256(root).digest()
                    + hashlib.sha256(root + b"x").digest()
                )
                row = np.frombuffer(sig, np.uint8)[None, :]
                ch = sil.dcache.write_batch(row, np.array([64], np.uint16))
                sil.mcache.publish_batch(
                    sseq, np.array([tag], np.uint64), ch,
                    np.array([64], np.uint16), None, 3, None,
                )
                sseq += 1
            step()
            step()
            stream += _drain_out(ctx.outs[0], ofs)
        m = {
            k: ctx.metrics.counter(k) + ctrs.get(k, 0)
            for k in ("batches", "fec_sets", "data_shreds",
                      "parity_shreds", "sign_requests", "sign_responses")
        }
        return stream, reqs, m

    g_stream, g_reqs, g_m = run(False)
    n_stream, n_reqs, n_m = run(True)
    assert g_reqs == n_reqs
    assert g_stream == n_stream, (len(g_stream), len(n_stream))
    assert g_m == n_m, (g_m, n_m)
    assert g_m["sign_requests"] == 3 and len(g_stream) > 0
    # every published shred carries the patched signature
    for tag, _sz, raw in g_stream:
        assert raw[0:64] != b"\0" * 64
        s = SH.parse(raw)
        assert s is not None


def test_shred_outq_drain_is_credit_gated_per_round():
    """A stalled shreds consumer: the drain must publish at most depth
    frags (one live cr_avail re-read per round — the
    shred-outq-stale-credit mutant class), then deliver the remainder
    exactly-once after release."""
    sh, ctx, ofs, qfs = _mk_shred(depth=64)
    spec = sh.native_handler(ctx)
    stem = R.Stem(ctx.ins, ctx.outs, spec, cap=256)
    # fill the out queue way past the ring depth via a big local batch
    for i in range(200):
        sh._outq_push(1000 + i, bytes([i & 0xFF]) * 100)
    stem.run(256, 5)  # hook drains within credits only
    ol = ctx.outs[0]
    assert R.seq_diff(ol.mcache.seq_query(), ofs.query()) <= 64
    got = []
    for _ in range(10):
        got += _drain_out(ctx.outs[0], ofs, max_frags=64)
        stem.run(256, 5)
    assert [t for t, _, _ in got] == [1000 + i for i in range(200)]


# ---------------------------------------------------------------------------
# 2c. net: real-socket parity


def _mk_net(burst=64):
    from firedancer_tpu.tiles.net import NET_MTU, NetTile

    d = 1 << 10
    tx_mc = R.MCache(np.zeros(R.MCache.footprint(d), np.uint8), d)
    tx_dc = R.DCache(
        np.zeros(R.DCache.footprint(NET_MTU, d), np.uint8), NET_MTU, d
    )
    rx_mc = R.MCache(np.zeros(R.MCache.footprint(d), np.uint8), d)
    rx_dc = R.DCache(
        np.zeros(R.DCache.footprint(NET_MTU, d), np.uint8), NET_MTU, d
    )
    fs = R.FSeq(np.zeros(R.FSeq.footprint(), np.uint8))
    cons = R.FSeq(np.zeros(R.FSeq.footprint(), np.uint8))
    net = NetTile(burst=burst)
    schema = net.schema.with_base()
    ctx = MuxCtx(
        "net", R.CNC(np.zeros(R.CNC.footprint(), np.uint8)),
        [InLink("tx", tx_mc, tx_dc, fs)],
        [OutLink("rx", rx_mc, rx_dc, [cons])],
        Metrics(np.zeros(Metrics.footprint(schema), np.uint8), schema),
    )
    net.on_boot(ctx)
    return net, ctx, cons


def test_net_stem_parity_real_sockets():
    """Same datagram workload through the Python loop and the native
    stem: identical rx payload streams (addr prefix excluded — the
    ephemeral peer port differs per run), identical tx deliveries,
    identical metrics — including an oversize drop and the route-miss
    Python handback."""
    from firedancer_tpu.tiles.net import ADDR_SZ, NET_MTU, addr_pack

    def run(native):
        net, ctx, cons = _mk_net()
        stem = spec = None
        ctrs = {}
        if native:
            spec = net.native_handler(ctx)
            assert spec is not None and spec.ac_handler
            stem = R.Stem(ctx.ins, ctx.outs, spec, cap=128)
            ctrs = dict.fromkeys(spec.counters, 0)
        peer = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        peer.bind(("127.0.0.1", 0))
        peer.settimeout(2)

        def step():
            if native:
                _g, stat, _i = stem.run(128, 5)
                for j, nm in enumerate(spec.counters):
                    ctrs[nm] += int(stem.counters[j])
                if stat != R.STEM_PYTHON:
                    return
                il = ctx.ins[0]
                frags, il.seq, _ = il.mcache.drain(il.seq, 128)
                if len(frags):
                    net.on_frags(ctx, 0, frags)
                ctx.credits = 128
                net.after_credit(ctx)
            else:
                il = ctx.ins[0]
                frags, il.seq, _ = il.mcache.drain(il.seq, 128)
                if len(frags):
                    net.on_frags(ctx, 0, frags)
                ctx.credits = 128
                net.after_credit(ctx)

        # rx: deterministic burst to both ports, one oversize IN THE
        # MIDDLE of the quic burst — the kept rows after it exercise
        # the native hole-reclaim compaction (an oversize drop must
        # never advance the dcache cursor or corrupt later payloads)
        for i in range(10):
            peer.sendto(bytes([i]) * (30 + i), net.quic_addr)
        peer.sendto(b"z" * (NET_MTU - ADDR_SZ + 1), net.quic_addr)
        for i in range(10, 20):
            peer.sendto(bytes([i]) * (30 + i), net.quic_addr)
        for i in range(5):
            peer.sendto(bytes([0x40 + i]) * 25, net.udp_addr)
        time.sleep(0.1)
        for _ in range(6):
            step()
        ol = ctx.outs[0]
        seq = cons.query()
        frags, seq, _ = ol.mcache.drain(seq, 1024)
        cons.update(seq)
        rx = sorted(
            (int(f["sz"]), int(f["ctl"]) & 0x18,
             bytes(ol.dcache.read(int(f["chunk"]), int(f["sz"])))[
                 ADDR_SZ:
             ])
            for f in frags
        )
        # tx: addr-prefixed datagrams through the tx ring
        il = ctx.ins[0]
        rows = np.zeros((12, NET_MTU), np.uint8)
        szs = np.zeros(12, np.uint16)
        for i in range(12):
            pl = addr_pack(peer.getsockname()) + bytes([0x80 + i]) * 40
            rows[i, : len(pl)] = np.frombuffer(pl, np.uint8)
            szs[i] = len(pl)
        chunks = il.dcache.write_batch(rows, szs)
        il.mcache.publish_batch(
            0, np.arange(12, dtype=np.uint64), chunks, szs, None, 3, None
        )
        for _ in range(4):
            step()
        tx = []
        try:
            for _ in range(12):
                d, _a = peer.recvfrom(4096)
                tx.append(d)
        except socket.timeout:
            pass
        m = {
            k: ctx.metrics.counter(k) + ctrs.get(k, 0)
            for k in net.schema.counters
        }
        net.on_halt(ctx)
        peer.close()
        return rx, tx, m

    g_rx, g_tx, g_m = run(False)
    n_rx, n_tx, n_m = run(True)
    assert g_rx == n_rx, (len(g_rx), len(n_rx))
    assert g_tx == n_tx, (len(g_tx), len(n_tx))
    assert g_m == n_m, (g_m, n_m)
    assert g_m["oversize_drops"] == 1
    assert g_m["rx_dgrams"] == 25 and g_m["tx_dgrams"] == 12
    assert g_m["tx_routed"] + g_m["tx_unrouted"] == g_m["tx_dgrams"]


# ---------------------------------------------------------------------------
# 3. bank -> poh -> shred: zero Python per frag at steady state


def _transfer_mbs(n_mbs, per_mb=16, n_payers=24, seed=17):
    """Pre-encoded fast-transfer microblocks + the funded funk, the
    shape bank receives from pack."""
    from firedancer_tpu.ballet import txn as BT
    from firedancer_tpu.flamenco.accounts import Account, AccountMgr
    from firedancer_tpu.funk.funk import Funk
    from firedancer_tpu.tiles.pack import mb_encode

    rng = np.random.default_rng(seed)
    payers = [
        bytes(rng.integers(0, 256, 32, np.uint8)) for _ in range(n_payers)
    ]
    txns = []
    for i in range(n_mbs * per_mb):
        p = payers[i % n_payers]
        d = payers[(i * 7 + 3) % n_payers]
        data = (2).to_bytes(4, "little") + int(
            1 + rng.integers(1, 999)
        ).to_bytes(8, "little")
        txns.append(
            BT.build(
                [bytes(64)], [p, d, bytes(32)], bytes(32),
                [(2, [0, 1], data)], readonly_unsigned_cnt=1,
            )
        )
    width = max(len(t) for t in txns)
    rows = np.zeros((len(txns), width), np.uint8)
    szs = np.zeros(len(txns), np.uint16)
    for i, t in enumerate(txns):
        rows[i, : len(t)] = np.frombuffer(t, np.uint8)
        szs[i] = len(t)
    payloads = [
        mb_encode(
            h, 0, rows, szs,
            idx=np.arange(h * per_mb, (h + 1) * per_mb, dtype=np.int64),
        )
        for h in range(n_mbs)
    ]
    funk = Funk()
    mgr = AccountMgr(funk)
    for p in payers:
        mgr.store(p, Account(1 << 40))
    return payloads, funk


class _MbFeeder(Tile):
    """Publishes pre-encoded microblocks, credit-gated; `total` beyond
    len(payloads) cycles them (a steady-state firehose)."""

    name = "feeder"

    def __init__(self, payloads, total=None):
        self.payloads = payloads
        self.total = len(payloads) if total is None else total
        self.sent = 0

    def after_credit(self, ctx):
        while self.sent < self.total and ctx.outs[0].cr_avail():
            pl = self.payloads[self.sent % len(self.payloads)]
            ctx.outs[0].publish(
                np.array([self.sent], np.uint64), pl[None, :],
                np.array([len(pl)], np.uint16),
            )
            self.sent += 1


def _local_signer(root) -> bytes:
    """Deterministic stand-in signer (module-level: spawn-picklable)."""
    return (hashlib.sha256(root).digest() +
            hashlib.sha256(root + b"s").digest())


def test_egress_zero_python_steady_state():
    """The acceptance counter-assert: with the native stem active on the
    bank→poh→shred chain, a steady window advances stem_frags/entries
    with ZERO Python per frag and per after-credit on poh AND shred
    (run_loop skips tile.after_credit when the hook scheduled
    natively).  Tracing is ON (ISSUE 15): the in-burst native emitter
    records per-frag hists and spans WITHOUT re-introducing any
    per-frag Python — py_frags must stay zero with the full
    observability substrate live."""
    from firedancer_tpu.tiles.bank import BankTile

    payloads, funk = _transfer_mbs(96)
    topo = Topology()
    topo.enable_trace(sample=4)
    topo.link("fb", depth=256, mtu=65_535)
    topo.link("bp", depth=256)
    topo.link("bpoh", depth=256, mtu=65_535)
    topo.link("poh_shred", depth=1 << 12, mtu=ENTRY_SZ)
    topo.link("shred_sink", depth=1 << 12, mtu=SH.MAX_SZ)
    topo.tile(_MbFeeder(payloads, total=10**9), outs=["fb"])
    topo.tile(
        BankTile(0, funk=funk, native=True, table_slots=1 << 12),
        ins=[("fb", True)], outs=["bp", "bpoh"],
    )
    topo.tile(SinkTile(shm_log=1 << 12, name="comp"), ins=[("bp", True)])
    # long slots + no pacing: mixin entries flow continuously, no slot
    # boundary (a Python handback by design) inside the window
    poh = PohTile(tick_batch=8, ticks_per_slot=1 << 30, slot_ms=0)
    topo.tile(poh, ins=[("bpoh", True)], outs=["poh_shred"])
    topo.tile(
        ShredTile(signer=_local_signer),
        ins=[("poh_shred", True)], outs=["shred_sink"],
    )
    topo.tile(SinkTile(shm_log=1 << 14), ins=[("shred_sink", True)])
    topo.build()
    topo.start(batch_max=64, stem="native")
    try:
        mpoh = topo.metrics("poh")
        msh = topo.metrics("shred")
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            if mpoh.counter("mixins") >= 8 and msh.counter("in_frags") >= 8:
                break
            time.sleep(0.02)
        assert mpoh.counter("mixins") >= 8, "chain never engaged"
        keys = ("py_frags", "py_credit", "stem_frags", "in_frags")
        base_p = {k: mpoh.counter(k) for k in keys}
        base_s = {k: msh.counter(k) for k in keys}
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            if (
                mpoh.counter("stem_frags") > base_p["stem_frags"]
                and msh.counter("stem_frags") > base_s["stem_frags"]
            ):
                break
            time.sleep(0.02)
        after_p = {k: mpoh.counter(k) for k in keys}
        after_s = {k: msh.counter(k) for k in keys}
        # the window moved natively...
        assert after_p["stem_frags"] > base_p["stem_frags"]
        assert after_s["stem_frags"] > base_s["stem_frags"]
        # ...and executed zero Python per frag and per after-credit
        assert after_p["py_frags"] == base_p["py_frags"], (base_p, after_p)
        assert after_s["py_frags"] == base_s["py_frags"], (base_s, after_s)
        assert after_p["py_credit"] == base_p["py_credit"]
        assert after_s["py_credit"] == base_s["py_credit"]
        # full coverage: every frag poh and shred consumed rode the stem
        assert after_p["py_frags"] == 0
        assert after_s["py_frags"] == 0
        # ...while the native emitter measured every one of them: the
        # qwait samples can only have come from the in-burst C path
        hq = mpoh.hist("qwait_us_bpoh")
        assert hq["count"] == after_p["in_frags"], hq
        evs, _, _ = topo._tracers["poh"].ring.read(0)
        assert len(evs) > 0, "native span emission produced nothing"
    finally:
        topo.halt()
        topo.close()


# ---------------------------------------------------------------------------
# 4. SIGKILL the poh child mid-burst: exactly-once, gapless chain


def test_poh_sigkill_mid_burst_exactly_once():
    """Process runtime, native stem: SIGKILL the poh child while the
    mixin ladder is hot.  The shm chain block + emission journal +
    consumed high-water mark must make every microblock mix EXACTLY
    once across the supervisor replay, and the recovered entry stream
    must verify as one gapless SHA-256 chain (every entry re-derived
    and checked, ticks included)."""
    n_mbs = 1536
    rng = np.random.default_rng(23)
    payloads = [
        np.frombuffer(
            bytes(rng.integers(0, 256, 160, np.uint8)), np.uint8
        ).copy()
        for _ in range(n_mbs)
    ]
    depth = 1 << 12  # holds the WHOLE entry stream for the final audit
    topo = Topology(name=f"pohk{os.getpid()}", runtime="process")
    topo.link("fb", depth=256, mtu=256)
    topo.link("poh_entries", depth=depth, mtu=ENTRY_SZ)
    topo.tile(_MbFeeder(payloads), outs=["fb"])
    # pacing pushed far out: at most one tick batch per incarnation
    # fires (the first after_credit, whose deadline word then parks in
    # the FUTURE and survives the restart in shm), keeping the stream
    # inside `depth`
    # interval = slot_ms*1e6*tick_batch/ticks_per_slot ns ~= 35 hours
    poh = PohTile(tick_batch=8, ticks_per_slot=64, slot_ms=1e9)
    topo.tile(poh, ins=[("fb", True)], outs=["poh_entries"])
    topo.tile(SinkTile(shm_log=1 << 14), ins=[("poh_entries", True)])
    sup = Supervisor(
        topo,
        RestartPolicy(
            hb_timeout_s=1.0, backoff_base_s=0.05,
            replay={"poh": 128, "sink": 128},
        ),
    )
    sup.start(batch_max=32, idle_sleep_s=2e-3, stem="native")
    try:
        mpoh = topo.metrics("poh")
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if (
                mpoh.counter("mixins") >= n_mbs // 8
                and mpoh.counter("stem_frags") > 0
            ):
                break
            time.sleep(0.02)
        assert mpoh.counter("stem_frags") > 0, "stem never engaged"
        pid = topo.tile_pid("poh")
        assert pid is not None
        os.kill(pid, signal.SIGKILL)
        mc = topo._mcaches["poh_entries"]
        dc = topo._dcaches["poh_entries"]

        def ring_mixins() -> int:
            n = min(R.seq_diff(mc.seq_query(), 0), depth)
            frags, _s, _o = mc.drain(0, n)
            return int((frags["sig"] == 1).sum())

        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            if sup.restarts("poh") >= 1 and ring_mixins() >= n_mbs:
                break
            time.sleep(0.1)
        assert sup.restarts("poh") >= 1
        # the sink consumed the stream (credits flowed end to end)
        assert len(
            read_siglog(topo.tile_alloc_view("sink", "siglog"))
        ) >= n_mbs
        # audit the FULL entry stream straight off the ring
        total = R.seq_diff(mc.seq_query(), 0)
        assert 0 < total <= depth
        frags, _seq, ovr = mc.drain(0, total)
        assert ovr == 0 and len(frags) == total
        entries = [
            bytes(dc.read(int(f["chunk"]), int(f["sz"]))) for f in frags
        ]
        sigs = [int(f["sig"]) for f in frags]
        mixins = [e for e, s in zip(entries, sigs) if s == 1]
        # exactly-once: one mixin entry per fed microblock, in feed
        # order, each mixing the right bytes
        assert len(mixins) == n_mbs, f"{len(mixins)} != {n_mbs}"
        state = b"\0" * 32
        mi = 0
        for e, s in zip(entries, sigs):
            prev, mix, st = e[0:32], e[40:72], e[72:104]
            assert prev == state, "chain gap (prev != running state)"
            if s == 1:
                assert mix == hashlib.sha256(
                    payloads[mi].tobytes()
                ).digest(), f"mixin {mi} mixed the wrong microblock"
                assert st == hashlib.sha256(prev + mix).digest()
                mi += 1
            else:
                # tick batch: re-derive the ladder
                n = int.from_bytes(e[32:40], "little")
                ref = prev
                for _ in range(n):
                    ref = hashlib.sha256(ref).digest()
                assert st == ref, "tick ladder diverged"
            state = st
        assert mi == n_mbs
        # metrics are best-effort across a SIGKILL (a mid-burst kill
        # loses that burst's counter deltas); the STREAM is the
        # exactly-once proof — but mixins can never overcount it
        assert mpoh.counter("mixins") <= n_mbs
    finally:
        sup.halt()
        topo.close()
