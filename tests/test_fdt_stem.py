"""fdt_stem — the GIL-released native inner loop (ISSUE 10).

Tier-1 contract:

  1. GOLDEN PARITY: each native handler (dedup / bank pipeline / pack
     insert) produces publish streams and state BIT-IDENTICAL to the
     Python on_frags loop on the same input — checked per tile on raw
     rings (payload bytes included) and end-to-end on the
     quic→verify(host)→dedup→pack pipeline.
  2. SIGKILL MID-BURST: a dedup child killed while inside the native
     burst recovers through the UNCHANGED journal/amnesty discipline —
     zero lost, zero duplicated frags.
  3. FAULTINJ AT THE BURST BOUNDARY: on="frag" triggers keep firing
     with the stem active (the stem feeds the cumulative counters at
     burst granularity; drop/corrupt faults force the Python loop).
  4. BACKPRESSURE HANDOFF: cr_avail=0 keeps the existing Python
     backpressure path — the stem is never entered without credits and
     everything flows exactly-once after release.
"""

from __future__ import annotations

import os
import signal
import threading
import time

import numpy as np
import pytest

from firedancer_tpu.disco import Topology
from firedancer_tpu.disco.faultinj import FaultInjector, FaultKill
from firedancer_tpu.disco.metrics import Metrics
from firedancer_tpu.disco.mux import InLink, MuxCtx, OutLink, Tile, run_loop
from firedancer_tpu.disco.supervisor import RestartPolicy, Supervisor
from firedancer_tpu.tango import rings as R
from firedancer_tpu.tiles import wire
from firedancer_tpu.tiles.dedup import DedupTile
from firedancer_tpu.tiles.sink import SinkTile, read_siglog
from firedancer_tpu.tiles.synth import SynthTile, make_txn_pool


@pytest.fixture(autouse=True)
def _no_shm_leak(no_shm_leak):
    """Every test here runs under conftest's /dev/shm leak check."""
    yield


# ---------------------------------------------------------------------------
# raw-ring harness: one dedup tile over numpy-backed rings, driven
# synchronously so the comparison is deterministic down to the byte


def _mk_dedup_ctx(depth=256, mtu=512):
    in_mc = R.MCache(np.zeros(R.MCache.footprint(depth), np.uint8), depth)
    in_dc = R.DCache(
        np.zeros(R.DCache.footprint(mtu, depth), np.uint8), mtu, depth
    )
    in_fs = R.FSeq(np.zeros(R.FSeq.footprint(), np.uint8))
    out_mc = R.MCache(np.zeros(R.MCache.footprint(depth), np.uint8), depth)
    out_dc = R.DCache(
        np.zeros(R.DCache.footprint(mtu, depth), np.uint8), mtu, depth
    )
    cons_fs = R.FSeq(np.zeros(R.FSeq.footprint(), np.uint8))
    ded = DedupTile(depth=1 << 10)
    schema = ded.schema.with_base()
    ctx = MuxCtx(
        "dedup",
        R.CNC(np.zeros(R.CNC.footprint(), np.uint8)),
        [InLink("in", in_mc, in_dc, in_fs)],
        [OutLink("out", out_mc, out_dc, [cons_fs])],
        Metrics(np.zeros(Metrics.footprint(schema), np.uint8), schema),
    )
    ded.on_boot(ctx)
    return ded, ctx, cons_fs


def _feed(ctx, sigs, payload_of, tsorig=7):
    """Publish len(sigs) frags into the dedup in-ring."""
    il = ctx.ins[0]
    rows = np.stack([payload_of(i) for i in range(len(sigs))])
    szs = np.full(len(sigs), rows.shape[1], np.uint16)
    chunks = il.dcache.write_batch(rows, szs)
    il.mcache.publish_batch(
        il.mcache.seq_query(), np.asarray(sigs, np.uint64), chunks, szs,
        None, 3, np.full(len(sigs), tsorig, np.uint32),
    )


def _drain_out(ctx, cons_fs, max_frags=1 << 10):
    """Consume the out ring; returns [(sig, sz, ctl, tsorig, payload)]."""
    ol = ctx.outs[0]
    seq = cons_fs.query()
    frags, seq, ovr = ol.mcache.drain(seq, max_frags)
    assert ovr == 0
    out = []
    for f in frags:
        out.append(
            (
                int(f["sig"]), int(f["sz"]), int(f["ctl"]),
                int(f["tsorig"]),
                bytes(ol.dcache.read(int(f["chunk"]), int(f["sz"]))),
            )
        )
    cons_fs.update(seq)
    return out


def _sig_pattern(n, dup_every=3, zero_at=(5, 17)):
    """Deterministic tag stream with in-batch dups and zero tags."""
    sigs = [(i // dup_every) * 1000 + 1 for i in range(n)]
    for z in zero_at:
        if z < n:
            sigs[z] = 0
    return sigs


def test_dedup_stem_bit_identical_on_raw_rings():
    """Same frag stream through the Python on_frags loop and through one
    native stem burst: the published stream must match byte for byte —
    sig, sz, ctl, carried tsorig, AND payload bytes — including in-batch
    duplicates and zero-tag pass-through survivors (which exercise the
    survivor-list journal rewrite)."""
    n = 64
    sigs = _sig_pattern(n)

    def payload_of(i):
        return ((np.arange(96) * 13 + i * 7) & 0xFF).astype(np.uint8)

    # python reference
    ded_p, ctx_p, fs_p = _mk_dedup_ctx()
    _feed(ctx_p, sigs, payload_of)
    il = ctx_p.ins[0]
    frags, il.seq, _ = il.mcache.drain(il.seq, n)
    ded_p.on_frags(ctx_p, 0, frags)
    golden = _drain_out(ctx_p, fs_p)

    # native stem
    ded_n, ctx_n, fs_n = _mk_dedup_ctx()
    _feed(ctx_n, sigs, payload_of)
    spec = ded_n.native_handler(ctx_n)
    assert spec is not None
    stem = R.Stem(ctx_n.ins, ctx_n.outs, spec, cap=256)
    got, status, _ = stem.run(256, tspub=99)
    assert got == n
    assert status in (R.STEM_IDLE, R.STEM_BUDGET)
    native = _drain_out(ctx_n, fs_n)

    assert native == golden
    # the journal must be CLEAN after the burst (phase cleared), and the
    # tile-counter scratch must match the python-side metric
    assert int(ded_n._jnl[0]) == 0
    assert int(stem.counters[0]) == ctx_p.metrics.counter("dup_txns")
    # second delivery of the same stream: everything is a duplicate now
    _feed(ctx_n, [s or 1 for s in sigs], payload_of)
    got2, _, _ = stem.run(256, tspub=100)
    assert got2 == n and _drain_out(ctx_n, fs_n) == []


def test_stem_sweep_rotation_prevents_in_link_starvation():
    """The stem's sweep start index must rotate ACROSS calls (cfg word
    10), like the Python loop's drain-order rotation: a first in-link
    whose backlog always covers the whole burst budget must not starve
    the other native in-links (dedup in the validator topology has one
    in per verify replica)."""
    depth, mtu = 1 << 10, 512
    ins = []
    for _ in range(2):
        mc = R.MCache(np.zeros(R.MCache.footprint(depth), np.uint8), depth)
        dc = R.DCache(
            np.zeros(R.DCache.footprint(mtu, depth), np.uint8), mtu, depth
        )
        fs = R.FSeq(np.zeros(R.FSeq.footprint(), np.uint8))
        ins.append(InLink(f"in{len(ins)}", mc, dc, fs))
    out_mc = R.MCache(np.zeros(R.MCache.footprint(depth), np.uint8), depth)
    out_dc = R.DCache(
        np.zeros(R.DCache.footprint(mtu, depth), np.uint8), mtu, depth
    )
    cons = R.FSeq(np.zeros(R.FSeq.footprint(), np.uint8))
    ded = DedupTile(depth=1 << 12)
    schema = ded.schema.with_base()
    ctx = MuxCtx(
        "dedup", R.CNC(np.zeros(R.CNC.footprint(), np.uint8)),
        ins, [OutLink("out", out_mc, out_dc, [cons])],
        Metrics(np.zeros(Metrics.footprint(schema), np.uint8), schema),
    )
    ded.on_boot(ctx)
    stem = R.Stem(ctx.ins, ctx.outs, ded.native_handler(ctx), cap=32)

    def feed(i, n, tag0):
        il = ctx.ins[i]
        rows = np.zeros((n, 64), np.uint8)
        szs = np.full(n, 64, np.uint16)
        chunks = il.dcache.write_batch(rows, szs)
        il.mcache.publish_batch(
            il.mcache.seq_query(),
            np.arange(tag0, tag0 + n, dtype=np.uint64), chunks, szs,
            None, 3, None,
        )

    feed(1, 8, 1_000_000)  # the minority link
    tag = 1
    in1_total = 0
    for call in range(6):
        feed(0, 64, tag)  # in0's backlog always exceeds the budget
        tag += 64
        stem.run(32, 5)
        cons.update(ctx.outs[0].seq)
        in1_total += stem.consumed(1)
    assert in1_total == 8, (
        f"in1 starved behind a saturated in0 ({in1_total}/8 drained)"
    )


def test_dedup_stem_respects_amnesty_gate():
    """A pending replay amnesty is host-side state only the Python path
    consumes — the spec's ready() gate must hold the stem off until it
    drains."""
    ded, ctx, _fs = _mk_dedup_ctx()
    spec = ded.native_handler(ctx)
    assert spec.ready()
    ded._amnesty = {123}
    assert not spec.ready()
    ded._amnesty = set()
    assert spec.ready()


# ---------------------------------------------------------------------------
# relay parity (threaded topology): python vs native stem


def _run_relay(stem_mode, pool_n=256, repeat=2, batch_max=128):
    rows, szs, _ = make_txn_pool(pool_n, seed=7)
    total = pool_n * repeat
    topo = Topology()
    topo.link("s", depth=1 << 10, mtu=wire.LINK_MTU)
    topo.link("d", depth=1 << 10, mtu=wire.LINK_MTU)
    topo.tile(SynthTile(rows, szs, total=total, repeat=repeat), outs=["s"])
    topo.tile(DedupTile(depth=1 << 14), ins=[("s", True)], outs=["d"])
    topo.tile(SinkTile(shm_log=1 << 13), ins=[("d", True)])
    topo.build()
    topo.start(batch_max=batch_max, stem=stem_mode)
    try:
        md = topo.metrics("dedup")
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            if (
                md.counter("in_frags") >= total
                and topo.metrics("sink").counter("in_frags") >= pool_n
            ):
                break
            time.sleep(0.02)
        sigs = read_siglog(topo.tile_alloc_view("sink", "siglog"))
        counters = {
            "in": md.counter("in_frags"),
            "stem": md.counter("stem_frags"),
            "dup": md.counter("dup_txns"),
            "out": md.counter("out_frags"),
            "out_bytes": md.counter("out_bytes"),
        }
        topo.halt()
        return sigs, counters
    finally:
        topo.close()


def test_dedup_stem_relay_parity_with_python_loop():
    g_sigs, g = _run_relay("python")
    n_sigs, n = _run_relay("native")
    assert np.array_equal(g_sigs, n_sigs), "publish stream diverged"
    assert g["stem"] == 0
    assert n["stem"] == n["in"], "native stem must cover the whole stream"
    for k in ("in", "dup", "out", "out_bytes"):
        assert g[k] == n[k], k


# ---------------------------------------------------------------------------
# bank: fused pipeline parity + fallback handoff


def _bank_corpus(rng, n_payers, n_txns, nontrivial_dst=None):
    from firedancer_tpu.ballet import txn as BT

    payers = [
        bytes(rng.integers(0, 256, 32, np.uint8)) for _ in range(n_payers)
    ]
    txns = []
    for i in range(n_txns):
        p = payers[i % n_payers]
        d = payers[(i * 7 + 3) % n_payers]
        if nontrivial_dst is not None and i % 17 == 5:
            d = nontrivial_dst  # data-carrying account: python fallback
        data = (2).to_bytes(4, "little") + int(
            1 + rng.integers(1, 999)
        ).to_bytes(8, "little")
        txns.append(
            BT.build(
                [bytes(64)], [p, d, bytes(32)], bytes(32),
                [(2, [0, 1], data)], readonly_unsigned_cnt=1,
            )
        )
    return payers, txns


class _MbFeeder(Tile):
    """Publishes pre-encoded microblocks, credit-gated.  `hold_after`
    pauses delivery after that many microblocks until the test releases
    it — a warmup window that lets the bank resolve its cold keys so
    the steady-state portion measures/exercises the native path."""

    name = "feeder"

    def __init__(self, payloads, hold_after=None):
        self.payloads = payloads
        self.sent = 0
        self.hold_after = hold_after
        self.released = False

    def after_credit(self, ctx):
        while self.sent < len(self.payloads) and ctx.outs[0].cr_avail():
            if (
                self.hold_after is not None
                and self.sent >= self.hold_after
                and not self.released
            ):
                return
            pl = self.payloads[self.sent]
            ctx.outs[0].publish(
                np.array([self.sent], np.uint64), pl[None, :],
                np.array([len(pl)], np.uint16),
            )
            self.sent += 1


class _SigCatcher(Tile):
    """Records every frag's sig in arrival order (thread runtime)."""

    def __init__(self, name):
        self.name = name
        self.sigs: list[int] = []

    def on_frags(self, ctx, in_idx, frags):
        self.sigs.extend(int(s) for s in frags["sig"])


def _run_bank(stem_mode, txns, payers, fund=1 << 40, nontrivial=None,
              per_mb=32):
    from firedancer_tpu.flamenco.accounts import Account, AccountMgr
    from firedancer_tpu.funk.funk import Funk
    from firedancer_tpu.tiles.bank import BankTile
    from firedancer_tpu.tiles.pack import mb_encode

    funk = Funk()
    mgr = AccountMgr(funk)
    for p in payers:
        mgr.store(p, Account(fund))
    if nontrivial is not None:
        mgr.store(nontrivial, Account(5, data=b"\x07" * 9))
    width = max(len(t) for t in txns)
    rows = np.zeros((len(txns), width), np.uint8)
    szs = np.zeros(len(txns), np.uint16)
    for i, t in enumerate(txns):
        rows[i, : len(t)] = np.frombuffer(t, np.uint8)
        szs[i] = len(t)
    payloads = [
        mb_encode(
            h, 0, rows, szs,
            idx=np.arange(
                h * per_mb, min((h + 1) * per_mb, len(txns)),
                dtype=np.int64,
            ),
        )
        for h in range((len(txns) + per_mb - 1) // per_mb)
    ]
    topo = Topology()
    topo.link("fb", depth=256, mtu=65_535)
    topo.link("bp", depth=256)
    topo.link("bpoh", depth=256, mtu=65_535)
    comp, poh = _SigCatcher("comp"), _SigCatcher("poh")
    feeder = _MbFeeder(payloads, hold_after=2)
    topo.tile(feeder, outs=["fb"])
    topo.tile(
        BankTile(0, funk=funk, native=True, table_slots=1 << 12),
        ins=[("fb", True)], outs=["bp", "bpoh"],
    )
    topo.tile(comp, ins=[("bp", True)])
    topo.tile(poh, ins=[("bpoh", True)])
    topo.build()
    topo.start(batch_max=64, stem=stem_mode)
    try:
        mb_m = topo.metrics("bank0")
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            if not feeder.released and len(comp.sigs) >= 2:
                # warmup done: the first two microblocks touched every
                # pool key, so the table is hot for the steady stream
                feeder.released = True
            # gate on the bank's OWN counters too: completions publish
            # from inside the GIL-released burst, and the metric deltas
            # land at the burst boundary — reading on downstream
            # arrival alone races the apply
            if (
                len(comp.sigs) >= len(payloads)
                and mb_m.counter("in_frags") >= len(payloads)
            ):
                break
            time.sleep(0.02)
        counters = {
            k: mb_m.counter(k)
            for k in (
                "in_frags", "stem_frags", "executed_microblocks",
                "executed_txns", "fast_txns", "native_txns",
                "failed_txns", "fees_lamports", "malformed_microblocks",
            )
        }
        topo.halt()
    finally:
        topo.close()
    state = {p: AccountMgr(funk).load(p).lamports for p in payers}
    if nontrivial is not None:
        state[nontrivial] = AccountMgr(funk).load(nontrivial).lamports
    return counters, state, comp.sigs, poh.sigs


def test_bank_stem_pipeline_parity_with_python_loop():
    """All-fast microblocks: the fused native pipeline must land the
    same funk state, the same completion/poh streams, and the same
    execution metrics as the Python path — with full native coverage
    after the first (cold-key resolve) handoff."""
    rng = np.random.default_rng(5)
    payers, txns = _bank_corpus(rng, 64, 640)
    g_c, g_s, g_comp, g_poh = _run_bank("python", txns, payers)
    n_c, n_s, n_comp, n_poh = _run_bank("native", txns, payers)
    assert g_s == n_s, "funk states diverged"
    assert g_comp == n_comp and g_poh == n_poh, "publish streams diverged"
    assert g_c["stem_frags"] == 0
    # warmup (2 cold-key microblocks) may hand off to Python; the hot
    # remainder must run native
    assert n_c["stem_frags"] >= n_c["in_frags"] - 2, (
        f"native path under-engaged: {n_c}"
    )
    for k in (
        "in_frags", "executed_microblocks", "executed_txns", "fast_txns",
        "native_txns", "failed_txns", "fees_lamports",
        "malformed_microblocks",
    ):
        assert g_c[k] == n_c[k], k


def test_bank_stem_nontrivial_fallback_parity():
    """Microblocks containing NONTRIVIAL destinations (data-carrying
    accounts the table cannot hold) must hand back to the Python
    executor mid-stream and still converge to the identical state —
    the journal's (tag, done) split keeps the native fast prefix
    exactly-once."""
    rng = np.random.default_rng(6)
    nontrivial = bytes(rng.integers(0, 256, 32, np.uint8))
    payers, txns = _bank_corpus(rng, 32, 320, nontrivial_dst=nontrivial)
    g_c, g_s, g_comp, g_poh = _run_bank(
        "python", txns, payers, nontrivial=nontrivial
    )
    n_c, n_s, n_comp, n_poh = _run_bank(
        "native", txns, payers, nontrivial=nontrivial
    )
    assert g_s == n_s, "funk states diverged"
    assert g_comp == n_comp and g_poh == n_poh
    for k in (
        "executed_microblocks", "executed_txns", "fast_txns",
        "failed_txns", "fees_lamports",
    ):
        assert g_c[k] == n_c[k], k


# ---------------------------------------------------------------------------
# pack: insert-path parity


def _run_pack(stem_mode, pool_n=300, depth=512):
    from firedancer_tpu.tiles.pack import PackTile

    rows, szs, _ = make_txn_pool(pool_n, seed=9)
    topo = Topology()
    topo.link("s", depth=1 << 10, mtu=wire.LINK_MTU)
    topo.link("pb0", depth=256, mtu=65_535)
    topo.tile(SynthTile(rows, szs, total=pool_n, repeat=1), outs=["s"])
    pk = PackTile(1, depth=depth, microblock_ns=10**12)  # never schedules
    topo.tile(pk, ins=[("s", True)], outs=["pb0"])
    topo.build()
    topo.start(batch_max=128, stem=stem_mode)
    try:
        mp = topo.metrics("pack")
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            if (
                mp.counter("inserted_txns") + mp.counter("insert_rejected")
                >= pool_n
            ):
                break
            time.sleep(0.02)
        counters = {
            k: mp.counter(k)
            for k in ("in_frags", "stem_frags", "inserted_txns",
                      "insert_rejected")
        }
        eng = pk.engine
        arrays = tuple(
            a.copy()
            for a in (
                eng.state, eng.szs, eng.sig_tag, eng.rows, eng.rewards,
                eng.cost, eng.is_vote, eng.bs_rw, eng.bs_w, eng.whash,
                eng.w_cnt, eng.rhash, eng.r_cnt, eng.expires_at,
            )
        )
        topo.halt()
        return counters, arrays
    finally:
        topo.close()


def test_pack_stem_insert_parity_with_python_loop():
    """The native insert path must leave the pack engine's dense pool
    arrays bit-identical to insert_batch's — same slots, same scan
    outputs, same lock bitsets."""
    g_c, g_a = _run_pack("python")
    n_c, n_a = _run_pack("native")
    for i, (ga, na) in enumerate(zip(g_a, n_a)):
        assert np.array_equal(ga, na), f"engine array {i} diverged"
    assert g_c["inserted_txns"] == n_c["inserted_txns"]
    assert g_c["insert_rejected"] == n_c["insert_rejected"]
    assert n_c["stem_frags"] == n_c["in_frags"]


def test_pack_stem_pool_full_hands_eviction_to_python():
    """When free slots run short the native path must bail BEFORE
    mutating anything so Python's priority-eviction policy decides —
    parity of the final pool occupancy is the proof."""
    g_c, g_a = _run_pack("python", pool_n=300, depth=128)
    n_c, n_a = _run_pack("native", pool_n=300, depth=128)
    # eviction decisions are priority-based and deterministic per input
    for i, (ga, na) in enumerate(zip(g_a, n_a)):
        assert np.array_equal(ga, na), f"engine array {i} diverged"
    assert g_c["inserted_txns"] == n_c["inserted_txns"]


# ---------------------------------------------------------------------------
# pack: native after-credit scheduler (ISSUE 11) — synchronous raw-ring
# harness so the microblock stream comparison is deterministic to the byte


def _transfer_pool(n, n_payers=24, seed=13):
    """Fast-transfer txns with unique signatures + wire trailers, the
    shape the pack tile sees from dedup."""
    from firedancer_tpu.ballet import txn as BT

    rng = np.random.default_rng(seed)
    payers = [
        bytes(rng.integers(0, 256, 32, np.uint8)) for _ in range(n_payers)
    ]
    raws = []
    for i in range(n):
        p = payers[i % n_payers]
        d = payers[(i * 7 + 3) % n_payers]
        data = (2).to_bytes(4, "little") + int(
            1 + rng.integers(1, 999)
        ).to_bytes(8, "little")
        sig = bytes(rng.integers(0, 256, 64, np.uint8))
        raws.append(
            BT.build(
                [sig], [p, d, bytes(32)], bytes(32),
                [(2, [0, 1], data)], readonly_unsigned_cnt=1,
            )
        )
    from firedancer_tpu.ballet import txn as T

    rows = np.zeros((n, wire.LINK_MTU), np.uint8)
    szs = np.zeros(n, np.uint16)
    tags = np.zeros(n, np.uint64)
    for i, raw in enumerate(raws):
        pl = wire.append_trailer(raw, T.parse(raw))
        rows[i, : len(pl)] = np.frombuffer(pl, np.uint8)
        szs[i] = len(pl)
        tags[i] = int.from_bytes(raw[1:9], "little")
    return rows, szs, tags, payers


def _mk_pack_sched_ctx(n_banks=2, depth=512, mb_inflight=2,
                       slot_ns=10**15, ring_depth=1 << 9):
    from firedancer_tpu.tiles.pack import PackTile

    def ring(mtu=None):
        mc = R.MCache(
            np.zeros(R.MCache.footprint(ring_depth), np.uint8), ring_depth
        )
        dc = None
        if mtu is not None:
            dc = R.DCache(
                np.zeros(R.DCache.footprint(mtu, ring_depth), np.uint8),
                mtu, ring_depth,
            )
        return mc, dc

    in_mc, in_dc = ring(wire.LINK_MTU)
    cp_mc, _ = ring()  # completion ring: metadata only
    ins = [
        InLink("txns", in_mc, in_dc,
               R.FSeq(np.zeros(R.FSeq.footprint(), np.uint8))),
        InLink("comp", cp_mc, None,
               R.FSeq(np.zeros(R.FSeq.footprint(), np.uint8))),
    ]
    outs, cons = [], []
    for b in range(n_banks):
        mc, dc = ring(65_535)
        fs = R.FSeq(np.zeros(R.FSeq.footprint(), np.uint8))
        outs.append(OutLink(f"pb{b}", mc, dc, [fs]))
        cons.append(fs)
    pk = PackTile(
        n_banks, depth=depth, mb_inflight=mb_inflight, microblock_ns=0,
        slot_ns=slot_ns,
    )
    schema = pk.schema.with_base()
    ctx = MuxCtx(
        "pack", R.CNC(np.zeros(R.CNC.footprint(), np.uint8)), ins, outs,
        Metrics(np.zeros(Metrics.footprint(schema), np.uint8), schema),
    )
    pk.on_boot(ctx)
    return pk, ctx, cons


def _run_pack_sched(native, pool_n=400, depth=512, n_banks=2,
                    mb_inflight=2, slot_ns=10**15, max_rounds=4000):
    """Drive the pack tile synchronously: Phase A feeds + inserts (and
    schedules — banks fill to mb_inflight), Phase B echoes completions
    one round at a time until the pool drains.  Native mode follows the
    run_loop contract exactly: a PYTHON status falls back to the Python
    on_frags/after_credit for that round."""
    rows, szs, tags, _ = _transfer_pool(pool_n)
    pk, ctx, cons = _mk_pack_sched_ctx(
        n_banks=n_banks, depth=depth, mb_inflight=mb_inflight,
        slot_ns=slot_ns,
    )
    stem = None
    spec = None
    ctr_tot: dict[str, int] = {}
    if native:
        spec = pk.native_handler(ctx)
        assert spec is not None and spec.ac_handler, "scheduler not native"
        stem = R.Stem(ctx.ins, ctx.outs, spec, cap=256)
        ctr_tot = dict.fromkeys(spec.counters, 0)

    def py_round():
        for i in range(len(ctx.ins)):
            il = ctx.ins[i]
            frags, il.seq, _ = il.mcache.drain(il.seq, 256)
            if len(frags):
                pk.on_frags(ctx, i, frags)
        pk.after_credit(ctx)

    def step():
        if stem is None:
            py_round()
            return
        _got, stat, _sin = stem.run(256, 7)
        for i, name in enumerate(spec.counters):
            ctr_tot[name] += int(stem.counters[i])
        if stat == R.STEM_PYTHON:
            py_round()

    stream = []
    comp_seq = [0]
    held: list[int] = []  # completions withheld during phase A

    def echo_sigs(sigs):
        if len(sigs):
            cin = ctx.ins[1]
            comp_seq[0] = cin.mcache.publish_batch(
                comp_seq[0], np.asarray(sigs, np.uint64)
            )

    def harvest(echo):
        for b in range(n_banks):
            ol = ctx.outs[b]
            seq = cons[b].query()
            frags, seq, ovr = ol.mcache.drain(seq, 256)
            assert ovr == 0
            for f in frags:
                stream.append(
                    (
                        b, int(f["sig"]), int(f["sz"]),
                        bytes(ol.dcache.read(int(f["chunk"]), int(f["sz"]))),
                    )
                )
            cons[b].update(seq)
            if echo:
                echo_sigs(frags["sig"])
            else:
                held.extend(int(s) for s in frags["sig"])

    # phase A: feed + insert; scheduling fills the banks but completions
    # are withheld so insert/complete never share a round (the loop's
    # drain-order rotation makes same-round interleaving orderless)
    il = ctx.ins[0]
    fed = 0
    rounds = 0
    while fed < pool_n or R.seq_diff(il.mcache.seq_query(), il.seq) > 0:
        n = min(128, pool_n - fed)
        if n:
            chunks = il.dcache.write_batch(
                rows[fed : fed + n], szs[fed : fed + n]
            )
            il.mcache.publish_batch(
                fed, tags[fed : fed + n], chunks, szs[fed : fed + n],
                None, 3, None,
            )
            fed += n
        step()
        harvest(echo=False)
        rounds += 1
        assert rounds < max_rounds, "phase A did not converge"

    # phase B: release the withheld completions, then echo round by
    # round until the pool drains
    echo_sigs(held)
    held.clear()
    harvest(echo=True)
    eng = pk.engine
    while eng.pending_cnt or eng.outstanding_cnt:
        before = len(stream)
        step()
        harvest(echo=True)
        rounds += 1
        if len(stream) == before and not eng.outstanding_cnt \
                and eng.pending_cnt:
            # pending txns that can never schedule (conflict-starved
            # forever is impossible here: completions released all locks)
            step()
        assert rounds < max_rounds, "phase B did not converge"
    # drain the last completion echoes so bank_busy settles
    for _ in range(4):
        step()

    counters = {
        k: ctx.metrics.counter(k) + ctr_tot.get(k, 0)
        for k in (
            "inserted_txns", "insert_rejected", "microblocks",
            "microblock_txns", "completions", "stale_completions",
            "blocks",
        )
    }
    arrays = tuple(
        a.copy()
        for a in (
            eng.state, eng.szs, eng.sig_tag, eng.rewards, eng.cost,
            eng.is_vote, eng.whash, eng.w_cnt, eng.rhash, eng.r_cnt,
            eng.lw_keys, eng.lw_vals, eng.lr_keys, eng.lr_vals,
            eng.wc_keys, eng.wc_vals, eng._sched_words, eng.mb_used,
            pk.bank_busy,
        )
    )
    return stream, counters, arrays, pk


def test_pack_sched_stem_bit_identical_on_raw_rings():
    """The ISSUE 11 parity bar, deterministically: the native
    after-credit scheduler + completion handler must produce a
    microblock payload stream BIT-IDENTICAL to the Python
    after_credit's — same banks, same sigs, same encoded bytes — and
    leave every engine array (pool, exact lock tables, writer-cost
    map, shared scheduler words, registry) byte-equal."""
    g_stream, g_c, g_a, _ = _run_pack_sched(False)
    n_stream, n_c, n_a, _ = _run_pack_sched(True)
    assert g_stream == n_stream, "microblock streams diverged"
    assert g_c == n_c, (g_c, n_c)
    for i, (ga, na) in enumerate(zip(g_a, n_a)):
        assert np.array_equal(ga, na), f"engine array {i} diverged"
    assert n_c["microblocks"] > 0 and n_c["completions"] == n_c["microblocks"]
    assert n_c["microblock_txns"] == n_c["inserted_txns"]


def test_pack_sched_stem_pool_full_eviction_parity():
    """Scheduling active while the pool overflows: the insert fast path
    bails pre-mutation, Python's priority eviction decides, and the
    stream still matches (the eviction pairing is batch-size
    invariant)."""
    g_stream, g_c, g_a, _ = _run_pack_sched(False, pool_n=400, depth=128)
    n_stream, n_c, n_a, _ = _run_pack_sched(True, pool_n=400, depth=128)
    assert g_stream == n_stream
    assert g_c == n_c
    for i, (ga, na) in enumerate(zip(g_a, n_a)):
        assert np.array_equal(ga, na), f"engine array {i} diverged"


def test_pack_sched_stem_end_block_hands_back_to_python():
    """Past the block deadline the native hook must (a) keep draining
    completions while microblocks are outstanding and (b) hand back to
    Python with ZERO outstanding so end_block — a Python slow path —
    resets the budgets.  Both loop modes land identical budget words
    and block counts."""
    outs = []
    for native in (False, True):
        _s, c, a, pk = _run_pack_sched(
            native, pool_n=96, depth=128, slot_ns=1
        )
        outs.append((c, a, pk))
    (g_c, g_a, g_pk), (n_c, n_a, n_pk) = outs
    assert g_c["blocks"] >= 1 and n_c["blocks"] == g_c["blocks"]
    assert g_c == n_c
    # budgets reset by end_block in both modes
    assert int(g_pk.engine._sched_words[0]) == int(
        n_pk.engine._sched_words[0]
    )


def test_pack_sched_stem_stale_completion_is_metered_drop():
    """A completion whose (bank, handle) is no longer outstanding — a
    restarted bank replaying its ring window — must be a metered drop
    in BOTH loop modes, never a KeyError crash or a double lock
    release."""
    for native in (True, False):
        pk, ctx, cons = _mk_pack_sched_ctx(n_banks=1)
        stem = spec = None
        if native:
            spec = pk.native_handler(ctx)
            stem = R.Stem(ctx.ins, ctx.outs, spec, cap=64)
        # no outstanding microblock: every completion is stale
        cin = ctx.ins[1]
        cin.mcache.publish_batch(
            0, np.array([(0 << 32) | 7, (5 << 32) | 9], np.uint64)
        )
        if native:
            got, stat, _ = stem.run(64, 5)
            assert got == 2 and stat in (R.STEM_IDLE, R.STEM_BUDGET)
            stale = int(stem.counters[list(spec.counters).index(
                "stale_completions"
            )])
        else:
            il = ctx.ins[1]
            frags, il.seq, _ = il.mcache.drain(il.seq, 64)
            pk.on_frags(ctx, 1, frags)
            stale = ctx.metrics.counter("stale_completions")
        assert stale == 2
        assert pk.engine.outstanding_cnt == 0
        assert int(pk.bank_busy[0]) == 0


def test_pack_stem_zero_python_steady_state():
    """The acceptance counter-assert: with the native scheduler active,
    a steady scheduling window executes ZERO Python per frag and per
    microblock — py_frags/py_credit stay flat while stem_frags and
    microblocks advance (run_loop skips tile.after_credit when the
    burst scheduled natively)."""
    from firedancer_tpu.tiles.pack import PackTile

    rows, szs, tags = _transfer_pool(512)[:3]
    topo = Topology()
    topo.link("s", depth=1 << 9, mtu=wire.LINK_MTU)
    topo.link("pb0", depth=256, mtu=65_535)
    topo.link("b0p", depth=256)
    topo.tile(SynthTile(rows, szs, total=4096, repeat=8), outs=["s"])
    pk = PackTile(1, depth=1 << 12, mb_inflight=4, microblock_ns=0,
                  slot_ns=10**15)
    topo.tile(pk, ins=[("s", True), ("b0p", True)], outs=["pb0"])

    class _Echo(Tile):
        name = "echo"

        def on_frags(self, ctx, i, frags):
            ctx.outs[0].publish(frags["sig"].copy())

    topo.tile(_Echo(), ins=[("pb0", True)], outs=["b0p"])
    topo.build()
    topo.start(batch_max=128, stem="native")
    try:
        mp = topo.metrics("pack")
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            if mp.counter("microblocks") >= 8:
                break
            time.sleep(0.02)
        assert mp.counter("microblocks") >= 8, "scheduler never engaged"
        base = {
            k: mp.counter(k)
            for k in ("py_frags", "py_credit", "stem_frags", "microblocks")
        }
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            cur = {
                k: mp.counter(k)
                for k in ("stem_frags", "microblocks")
            }
            if (
                cur["stem_frags"] > base["stem_frags"]
                and cur["microblocks"] > base["microblocks"]
            ):
                break
            time.sleep(0.02)
        after = {
            k: mp.counter(k)
            for k in ("py_frags", "py_credit", "stem_frags", "microblocks")
        }
        assert after["stem_frags"] > base["stem_frags"]
        assert after["microblocks"] > base["microblocks"]
        assert after["py_frags"] == base["py_frags"], (base, after)
        assert after["py_credit"] == base["py_credit"], (base, after)
    finally:
        topo.halt()
        topo.close()


def test_pack_sched_sigkill_bank_mid_burst_exactly_once():
    """ISSUE 11 chaos bar: SIGKILL the BANK child while the pack tile's
    native scheduler is hot.  The bank's journal + completed-seq
    discipline makes every microblock execute exactly once across the
    replay; at pack, replayed completions for already-released handles
    are metered drops — so zero microblocks are lost (every scheduled
    txn completes) and zero are duplicated (microblock_txns ==
    inserted_txns, completions == microblocks)."""
    from firedancer_tpu.flamenco.accounts import Account, AccountMgr
    from firedancer_tpu.funk.funk import Funk
    from firedancer_tpu.tiles.bank import BankTile
    from firedancer_tpu.tiles.pack import PackTile

    pool_n = 3072
    rows, szs, tags, payers = _transfer_pool(pool_n, n_payers=64, seed=21)
    funk = Funk()
    mgr = AccountMgr(funk)
    for p in payers:
        mgr.store(p, Account(1 << 40))
    topo = Topology(name=f"packk{os.getpid()}", runtime="process")
    topo.link("synth_pack", depth=256, mtu=wire.LINK_MTU)
    topo.link("pack_bank0", depth=128, mtu=65_535)
    topo.link("bank0_pack", depth=128)
    topo.link("bank0_poh", depth=128, mtu=65_535)
    topo.tile(SynthTile(rows, szs, total=pool_n, repeat=1),
              outs=["synth_pack"])
    pk = PackTile(1, depth=1 << 13, mb_inflight=2, microblock_ns=0,
                  slot_ns=10**15, txn_limit=16)
    topo.tile(pk, ins=[("synth_pack", True), ("bank0_pack", True)],
              outs=["pack_bank0"])
    topo.tile(
        BankTile(0, funk=funk, native=True, table_slots=1 << 12),
        ins=[("pack_bank0", True)], outs=["bank0_pack", "bank0_poh"],
    )
    topo.tile(SinkTile(shm_log=1 << 14), ins=[("bank0_poh", True)])
    sup = Supervisor(
        topo,
        RestartPolicy(
            hb_timeout_s=1.0, backoff_base_s=0.05,
            replay={"bank0": 128, "pack": 128, "sink": 128},
        ),
    )
    sup.start(batch_max=64, idle_sleep_s=2e-3, stem="native")
    try:
        mp = topo.metrics("pack")
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if mp.counter("completions") >= 4:
                break
            time.sleep(0.02)
        assert mp.counter("completions") >= 4, "pipeline never started"
        pid = topo.tile_pid("bank0")
        assert pid is not None
        os.kill(pid, signal.SIGKILL)
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            if (
                sup.restarts("bank0") >= 1
                and mp.counter("microblock_txns") >= pool_n
                and mp.counter("completions") >= mp.counter("microblocks")
            ):
                break
            time.sleep(0.1)
        assert sup.restarts("bank0") >= 1
        assert mp.counter("inserted_txns") == pool_n
        assert mp.counter("insert_rejected") == 0
        # zero lost / zero duplicated microblocks: every inserted txn
        # scheduled exactly once, every scheduled microblock completed
        # exactly once (stale re-deliveries dropped, not double-freed)
        assert mp.counter("microblock_txns") == pool_n, (
            mp.counter("microblock_txns")
        )
        assert mp.counter("completions") == mp.counter("microblocks")
        assert mp.counter("stem_frags") > 0
    finally:
        sup.halt()
        topo.close()


# ---------------------------------------------------------------------------
# faultinj fires at the burst boundary


class _Src(Tile):
    name = "src"

    def __init__(self, n):
        self.n = n
        self.sent = 0

    def after_credit(self, ctx):
        b = min(64, self.n - self.sent, ctx.outs[0].cr_avail())
        if b <= 0:
            return
        rows = np.zeros((b, 64), np.uint8)
        sigs = (np.arange(self.sent, self.sent + b) + 1).astype(np.uint64)
        ctx.outs[0].publish(sigs, rows, np.full(b, 64, np.uint16))
        self.sent += b


def test_stem_faultinj_kill_fires_at_burst_boundary():
    """A scripted on="frag" kill must still fire with the stem active:
    the burst feeds the cumulative frag counters, and point 1 (loop
    top) consults them at the next burst boundary."""
    at = 100
    rows, szs, _ = make_txn_pool(64, seed=3)
    topo = Topology()
    topo.link("s", depth=1 << 9, mtu=wire.LINK_MTU)
    topo.link("d", depth=1 << 9, mtu=wire.LINK_MTU)
    topo.tile(SynthTile(rows, szs, total=512, repeat=8), outs=["s"])
    ded = DedupTile(depth=1 << 12)
    topo.tile(ded, ins=[("s", True)], outs=["d"])
    topo.tile(SinkTile(shm_log=1 << 12), ins=[("d", True)])
    inj = FaultInjector(seed=1).add("dedup", "kill", at=at, on="frag")
    topo.build()
    ctx = topo.tiles["dedup"].ctx
    ctx.faults = inj.view("dedup")
    topo.start(batch_max=32, stem="native")
    try:
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if topo._cncs["dedup"].signal_query() == R.CNC_FAIL:
                break
            time.sleep(0.02)
        assert topo._cncs["dedup"].signal_query() == R.CNC_FAIL
        assert inj.count("kill", "dedup") == 1
        md = topo.metrics("dedup")
        assert md.counter("stem_frags") > 0, "kill fired before any burst"
        assert ctx.faults.frags_seen >= at, "kill fired early"
        err = topo.tiles["dedup"].error
        assert isinstance(err, FaultKill)
    finally:
        topo.halt()
        topo.close()


def test_stem_drop_faults_force_python_loop():
    """drop/corrupt faults mangle frag payloads BETWEEN ring and
    callback — impossible inside the native burst, so their presence
    must pin the tile to the Python loop (deterministic windows)."""
    rows, szs, _ = make_txn_pool(64, seed=4)
    topo = Topology()
    topo.link("s", depth=1 << 9, mtu=wire.LINK_MTU)
    topo.link("d", depth=1 << 9, mtu=wire.LINK_MTU)
    topo.tile(SynthTile(rows, szs, total=128, repeat=2), outs=["s"])
    topo.tile(DedupTile(depth=1 << 12), ins=[("s", True)], outs=["d"])
    topo.tile(SinkTile(shm_log=1 << 12), ins=[("d", True)])
    inj = FaultInjector(seed=2).add("dedup", "drop", at=10, count=5)
    topo.build()
    topo.tiles["dedup"].ctx.faults = inj.view("dedup")
    topo.start(batch_max=32, stem="native")
    try:
        md = topo.metrics("dedup")
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            if md.counter("in_frags") >= 128 - 5:
                break
            time.sleep(0.02)
        assert md.counter("stem_frags") == 0, (
            "stem ran despite armed frag faults"
        )
        assert inj.dropped_frags("dedup") == 5
    finally:
        topo.halt()
        topo.close()


# ---------------------------------------------------------------------------
# backpressure (cr_avail = 0) hands off to the Python BP path


class _GatedSink(SinkTile):
    """Sink that refuses input until released (in_budget=0 propagates
    backpressure through the rings)."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.open = False

    def in_budget(self, ctx):
        return None if self.open else 0


def test_stem_backpressure_handoff_and_release():
    pool_n = 256
    rows, szs, _ = make_txn_pool(pool_n, seed=5)
    topo = Topology()
    topo.link("s", depth=1 << 9, mtu=wire.LINK_MTU)
    topo.link("d", depth=64, mtu=wire.LINK_MTU)  # small: fills fast
    topo.tile(SynthTile(rows, szs, total=pool_n, repeat=1), outs=["s"])
    topo.tile(DedupTile(depth=1 << 12), ins=[("s", True)], outs=["d"])
    gate = _GatedSink(shm_log=1 << 12)
    topo.tile(gate, ins=[("d", True)])
    topo.build()
    topo.start(batch_max=32, stem="native")
    try:
        md = topo.metrics("dedup")
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            if md.counter("backpressure_iters") > 0:
                break
            time.sleep(0.01)
        assert md.counter("backpressure_iters") > 0, (
            "gated sink never produced backpressure"
        )
        # stem never published past the ring depth while gated
        assert md.counter("out_frags") <= 64
        gate.open = True
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            # the sink can land frags published from inside a stem
            # burst before dedup's burst-boundary metrics apply — gate
            # on dedup's own counters too
            if (
                topo.metrics("sink").counter("in_frags") >= pool_n
                and md.counter("in_frags") >= pool_n
            ):
                break
            time.sleep(0.02)
        sigs = read_siglog(topo.tile_alloc_view("sink", "siglog"))
        assert len(sigs) == pool_n
        assert len(set(sigs.tolist())) == pool_n, "dup after release"
        assert md.counter("stem_frags") > 0
    finally:
        topo.halt()
        topo.close()


# ---------------------------------------------------------------------------
# SIGKILL mid-burst (process runtime): zero lost, zero duplicated


def test_stem_sigkill_mid_burst_exactly_once():
    """SIGKILL the dedup child while the native stem is hot: the
    journal discipline (armed BEFORE the insert, survivor rewrite,
    amnesty on rejoin) is byte-identical to the Python path's, so the
    restarted incarnation must collapse the supervisor's replay back to
    exactly-once — zero lost, zero duplicated frags."""
    pool_n, repeat = 768, 4
    rows, szs, _ = make_txn_pool(pool_n, seed=11)
    total = pool_n * repeat
    topo = Topology(name=f"stemk{os.getpid()}", runtime="process")
    topo.link("synth_dedup", depth=256, mtu=wire.LINK_MTU)
    topo.link("dedup_sink", depth=256, mtu=wire.LINK_MTU)
    synth = SynthTile(rows, szs, total=total, repeat=repeat)
    topo.tile(synth, outs=["synth_dedup"])
    topo.tile(
        DedupTile(depth=1 << 14), ins=[("synth_dedup", True)],
        outs=["dedup_sink"],
    )
    topo.tile(SinkTile(shm_log=1 << 14), ins=[("dedup_sink", True)])
    sup = Supervisor(
        topo,
        RestartPolicy(
            hb_timeout_s=1.0, backoff_base_s=0.05,
            replay={"dedup": 256, "sink": 256},
        ),
    )
    sup.start(batch_max=16, idle_sleep_s=2e-3, stem="native")
    try:
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            # gate on dedup's own stem counter (burst-boundary apply
            # lags the publishes) so the pre-kill native-coverage
            # assert below cannot race it
            if (
                topo.metrics("sink").counter("in_frags") >= pool_n // 4
                and topo.metrics("dedup").counter("stem_frags") > 0
            ):
                break
            time.sleep(0.02)
        assert topo.metrics("dedup").counter("stem_frags") > 0, (
            "stem never engaged before the kill"
        )
        pid = topo.tile_pid("dedup")
        assert pid is not None
        os.kill(pid, signal.SIGKILL)
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            sigs = read_siglog(topo.tile_alloc_view("sink", "siglog"))
            if len(set(sigs.tolist())) >= pool_n:
                break
            time.sleep(0.1)
        sigs = read_siglog(topo.tile_alloc_view("sink", "siglog"))
        uniq = set(sigs.tolist())
        assert sup.restarts("dedup") >= 1
        assert len(uniq) == pool_n, f"lost {pool_n - len(uniq)} frags"
        assert len(sigs) == len(uniq), "duplicated frags past dedup"
        assert uniq <= set(synth.tags.tolist())
    finally:
        sup.halt()
        topo.close()


# ---------------------------------------------------------------------------
# end-to-end golden parity: quic -> verify(host) -> dedup -> pack


def _run_quic_pipeline(stem_mode, n_txns=24):
    import socket

    from firedancer_tpu.tiles.pack import PackTile
    from firedancer_tpu.tiles.quic import QuicIngressTile
    from firedancer_tpu.tiles.verify import VerifyTile

    rng = np.random.default_rng(31)
    identity = rng.integers(0, 256, 32, np.uint8).tobytes()
    probe = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    probe.bind(("127.0.0.1", 0))
    udp_port = probe.getsockname()[1]
    probe.close()

    rows, szs, _ = make_txn_pool(n_txns, seed=11)
    tr = wire.parse_trailers(rows, szs.astype(np.int64))
    topo = Topology()
    topo.link("quic_verify", depth=256, mtu=wire.LINK_MTU)
    topo.link("verify_dedup", depth=256, mtu=wire.LINK_MTU)
    topo.link("dedup_pack", depth=256, mtu=wire.LINK_MTU)
    topo.link("pack_bank0", depth=256, mtu=65_535)
    topo.tile(
        QuicIngressTile(identity, udp_addr=("127.0.0.1", udp_port)),
        outs=["quic_verify"],
    )
    topo.tile(
        VerifyTile(
            msg_width=256, max_lanes=64, pre_dedup=False,
            device="off",
        ),
        ins=[("quic_verify", True)], outs=["verify_dedup"],
    )
    topo.tile(
        DedupTile(depth=1 << 10), ins=[("verify_dedup", True)],
        outs=["dedup_pack"],
    )
    pk = PackTile(1, microblock_ns=10**12)  # insert-only: never schedules
    topo.tile(pk, ins=[("dedup_pack", True)], outs=["pack_bank0"])
    topo.build()
    topo.start(batch_max=64, stem=stem_mode)
    try:
        tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        mp = topo.metrics("pack")
        deadline = time.monotonic() + 90.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            for i in range(n_txns):
                tx.sendto(
                    rows[i, : tr["txn_sz"][i]].tobytes(),
                    ("127.0.0.1", udp_port),
                )
            if mp.counter("inserted_txns") >= n_txns:
                break
            time.sleep(0.2)
        tx.close()
        inserted = mp.counter("inserted_txns")
        if stem_mode == "native":
            # burst-boundary metric apply lags the in-burst publishes;
            # give the final bursts a beat before reading coverage
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline and not all(
                topo.metrics(t).counter("stem_frags")
                for t in ("dedup", "pack")
            ):
                time.sleep(0.05)
        stem_cov = {
            t: topo.metrics(t).counter("stem_frags")
            for t in ("dedup", "pack")
        }
        vfail = topo.metrics("verify").counter("verify_fail_txns")
        tags = set(pk.engine.sig_tag[pk.engine.state != 0].tolist())
        topo.halt()
        return inserted, tags, stem_cov, vfail
    finally:
        topo.close()


def test_stem_golden_parity_quic_verify_dedup_pack():
    """The ISSUE-named path, both loop modes: every unique wire txn
    inserted into pack EXACTLY once, identical tag sets, zero verify
    failures — and the native run must actually exercise the stem on
    both dedup and pack."""
    n = 24
    g_ins, g_tags, _g_cov, g_vf = _run_quic_pipeline("python", n)
    n_ins, n_tags, n_cov, n_vf = _run_quic_pipeline("native", n)
    assert g_vf == 0 and n_vf == 0
    assert g_ins == n and n_ins == n, "lost or duplicated inserts"
    assert g_tags == n_tags, "pack pool tag sets diverged"
    assert n_cov["dedup"] > 0 and n_cov["pack"] > 0, (
        f"stem never engaged: {n_cov}"
    )


# ---------------------------------------------------------------------------
# config / plumbing


def test_stem_config_parses_and_resolves(monkeypatch):
    from firedancer_tpu.app import config as C

    cfg = C.parse('[topo]\nstem = "native"\n')
    assert cfg.stem == "native"
    assert C.parse("").stem is None
    t = Topology(stem="native")
    assert t._resolve_stem() == "native"
    monkeypatch.setenv("FDT_STEM", "native")
    assert Topology()._resolve_stem() == "native"
    monkeypatch.setenv("FDT_STEM", "bogus")
    with pytest.raises(ValueError):
        Topology()._resolve_stem()


def test_stem_cfg_layout_pinned():
    assert int(R._lib.fdt_stem_cfg_words()) == R._STEM_WORDS
