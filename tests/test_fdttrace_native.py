"""fdttrace-native (ISSUE 15): the in-burst measurement substrate.

Tier-1 contract:

  1. DIFFERENTIAL UNITS: the C clock/ts_diff/hist/span primitives
     (tango/native/fdt_trace.c) are pinned against their Python
     reference implementations — same u32 wrap math as disco.mux.ts_diff
     (including the wrap boundary), same log2 bucketing as
     Metrics.hist_sample, byte-identical SpanRing event records.
  2. CONCURRENT DRAIN: a NATIVE writer lapping the span ring under a
     Python reader never yields a torn or duplicated event, and the
     (returned + dropped) accounting exactly covers the written stream
     — the PR 6 analogue that found the lap-window bug, now across the
     language boundary.
  3. PARITY (the acceptance): on an identical frag stream with a
     deterministically injected clock, the native stem's qwait/svc/e2e
     hist contents, its drained span-event stream, AND its published
     frag metas (per-frag tspub included) are BIT-IDENTICAL to the
     Python loop's.
  4. SLO WIDE DOMAIN: an `[slo] e2e_p99_us` ceiling above 2^16 µs
     validates and can fire (the retired observability bound), and
     queue_wait_p99_us is computed from per-frag native samples under
     stem="native" (dedup's qwait hist count == its stem_frags, with
     Python never sampling).
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from firedancer_tpu.disco import Topology
from firedancer_tpu.disco import mux as M
from firedancer_tpu.disco.metrics import (
    HIST_BUCKETS,
    Metrics,
    MetricsSchema,
    WIDE_HIST_BUCKETS,
    hist_percentile,
)
from firedancer_tpu.disco.mux import (
    InLink,
    MuxCtx,
    OutLink,
    _arm_stem_trace,
    link_hist_names,
)
from firedancer_tpu.disco import trace as T
from firedancer_tpu.disco.trace import SpanRing, Tracer
from firedancer_tpu.tango import rings as R
from firedancer_tpu.tiles.dedup import DedupTile


@pytest.fixture(autouse=True)
def _no_shm_leak(no_shm_leak):
    """Every test here runs under conftest's /dev/shm leak check."""
    yield


# ---------------------------------------------------------------------------
# 1. differential units


def test_c_ts_diff_matches_python_across_wrap():
    """The C-side u32 timestamp math (fdt_trace_ts_diff) is the exact
    restatement of disco.mux.ts_diff — pinned across the wrap boundary
    where a naive subtraction goes negative-garbage."""
    cases = [
        (0, 0), (5, 3), (3, 5), (2**32 - 1, 0), (0, 2**32 - 1),
        (2**32 - 5, 2**32 - 10), (2**32 - 10, 2**32 - 5),
        # the wrap boundary: a just past 0, b just before it
        (3, 2**32 - 7), (2**32 - 7, 3),
        (2**31 - 1, 0), (2**31, 0), (0, 2**31 - 1),
        (123456789, 987654321),
    ]
    rng = np.random.default_rng(15)
    cases += [
        (int(a), int(b))
        for a, b in rng.integers(0, 2**32, (256, 2), np.uint64)
    ]
    for a, b in cases:
        assert R.trace_ts_diff(a, b) == M.ts_diff(a, b), (a, b)


def test_c_hist_sample_matches_python():
    """fdt_trace_hist_sample writes the exact words Metrics.hist_sample
    writes — bucket, sum clamp, count — for the 16-bucket AND the wide
    24-bucket layout (the widened link hists), including v=0, negative
    clamps, and beyond-domain overflow values."""
    values = [0, 1, 2, 3, 4, 7, 8, 1023, 65_535, 65_536, 2**24 - 1,
              2**24, 2**31, -1, -17]
    for wide in (False, True):
        name = "h"
        schema = MetricsSchema(
            hists=(name,), wide_hists=((name,) if wide else ())
        )
        nb = WIDE_HIST_BUCKETS if wide else HIST_BUCKETS
        m_py = Metrics(np.zeros(Metrics.footprint(schema), np.uint8), schema)
        m_c = Metrics(np.zeros(Metrics.footprint(schema), np.uint8), schema)
        addr, got_nb = m_c.hist_ref(name)
        assert got_nb == nb
        for v in values:
            m_py.hist_sample(name, v)
            R.trace_hist_sample(addr, nb, v)
        assert m_py.hist(name) == m_c.hist(name), (wide, m_c.hist(name))
        # raw storage words identical too (the shared-region contract)
        assert np.array_equal(m_py.words, m_c.words)


def test_c_span_events_byte_compatible():
    """fdt_trace_span produces the exact 4-u64 records Tracer.point
    writes, and fdt_trace_span_block mirrors SpanRing.write_block
    (cursors included, oversized-block tail-keep included)."""
    depth = 64
    ring_py = SpanRing(np.zeros(SpanRing.footprint(depth), np.uint8),
                       depth, sample=1)
    ring_c = SpanRing(np.zeros(SpanRing.footprint(depth), np.uint8),
                      depth, sample=1)
    tr = Tracer(ring_py, 1)
    tr.point(T.HK, link=3, ts=1234, seq=9, sig=42, aux16=7, aux64=77)
    R.trace_span(ring_c.words, T.HK, link=3, aux16=7, ts=1234, seq=9,
                 sig=42, aux64=77)
    ep, cp, _ = ring_py.read(0)
    ec, cc, _ = ring_c.read(0)
    assert cp == cc == 1
    assert np.array_equal(ep, ec)

    # block writes: same content, same committed/reserve cursors, and
    # an oversized block keeps its tail while advancing the full count
    rng = np.random.default_rng(3)
    blocks = [rng.integers(0, 2**63, (k, 4), np.uint64)
              for k in (1, 5, 48, depth + 16)]
    for rows in blocks:
        ring_py.write_block(rows)
        R.trace_span_block(ring_c.words, rows)
        assert int(ring_py.words[0]) == int(ring_c.words[0])
        assert int(ring_py.words[3]) == int(ring_c.words[3])
        assert np.array_equal(ring_py.ev, ring_c.ev)


def test_c_clock_same_domain_as_now_ts():
    """fdt_trace_now lives on the same CLOCK_MONOTONIC µs-mod-2^32 ring
    as disco.mux.now_ts — interleaved reads stay within a small window
    (the TSC-interpolated clock's anchor comes from the same clock)."""
    worst = 0
    for _ in range(50):
        a = R.trace_now()
        p = M.now_ts()
        b = R.trace_now()
        # python read is bracketed by the two native reads
        assert M.ts_diff(b, a) >= 0
        worst = max(worst, abs(M.ts_diff(p, a)), abs(M.ts_diff(b, p)))
    # generous bound: scheduling gaps on a loaded 1-CPU host, not clock
    # disagreement, dominate this number
    assert worst < 250_000, f"clock domains diverged by {worst}us"


def test_injected_clock_reads_value_and_step():
    clock = np.array([1000, 7], np.uint64)
    block = np.zeros(R._TR_WORDS, np.uint64)
    block[R._TR_W_MAGIC] = R._TR_MAGIC
    block[R._TR_W_CLOCK] = clock.ctypes.data
    assert R.trace_read_clock(block) == 1000
    assert R.trace_read_clock(block) == 1007
    assert int(clock[0]) == 1014


# ---------------------------------------------------------------------------
# 2. concurrent native-writer / Python-reader drain


def test_span_ring_native_writer_python_reader_drain():
    """A NATIVE writer (fdt_trace_span_block, GIL released per call)
    lapping the ring under a concurrently draining Python reader: no
    torn row returned as data, no duplicates, and (returned + dropped)
    exactly covers the written stream — the cross-language version of
    the PR 6 drain test whose Python-only variant found the lap-window
    bug."""
    depth = 256
    mem = np.zeros(SpanRing.footprint(depth), np.uint8)
    ring = SpanRing(mem, depth, sample=1)
    total = 40_000
    magic = np.uint64(0x9E3779B97F4A7C15)
    done = threading.Event()
    final_burst = depth + 64  # deterministic lap regardless of timing

    def _rows(i, k):
        idx = np.arange(i, i + k, dtype=np.uint64)
        rows = np.empty((k, T.EVENT_WORDS), np.uint64)
        rows[:, 0] = idx
        rows[:, 1] = idx ^ magic
        rows[:, 2] = idx * np.uint64(3)
        rows[:, 3] = ~idx
        return rows

    def writer():
        rng = np.random.default_rng(7)
        i = 0
        while i < total - final_burst:
            k = min(int(rng.integers(1, 48)), total - final_burst - i)
            R.trace_span_block(ring.words, _rows(i, k))
            i += k
        R.trace_span_block(ring.words, _rows(i, final_burst))
        done.set()

    t = threading.Thread(target=writer)
    t.start()
    seen: list[int] = []
    since = 0
    dropped_total = 0
    final_pass = False
    while True:
        ev, cur, dropped = ring.read(since)
        assert len(ev) + dropped == cur - since
        if len(ev):
            idx = ev[:, 0]
            assert np.array_equal(ev[:, 1], idx ^ magic)
            assert np.array_equal(ev[:, 2], idx * np.uint64(3))
            assert np.array_equal(ev[:, 3], ~idx)
            seen.extend(int(x) for x in idx)
        dropped_total += dropped
        since = cur
        if final_pass:
            break
        if done.is_set():
            final_pass = True
    t.join()
    assert len(seen) == len(set(seen))
    assert seen == sorted(seen)
    assert len(seen) + dropped_total == total
    assert dropped_total >= final_burst - depth


# ---------------------------------------------------------------------------
# 3. differential parity: python loop vs traced native stem
#
# The harness injects a deterministic clock (ctx.trace_clock for the
# native side, a monkeypatched disco.mux.now_ts reading the SAME array
# for the Python side) so both loops stamp identical timestamps on an
# identical frag stream — then hist words, span streams AND published
# frag metas must match bit for bit.


def _mk_traced_dedup(depth=256, mtu=512, sample=2, ring_depth=1 << 12):
    in_mc = R.MCache(np.zeros(R.MCache.footprint(depth), np.uint8), depth)
    in_dc = R.DCache(
        np.zeros(R.DCache.footprint(mtu, depth), np.uint8), mtu, depth
    )
    in_fs = R.FSeq(np.zeros(R.FSeq.footprint(), np.uint8))
    out_mc = R.MCache(np.zeros(R.MCache.footprint(depth), np.uint8), depth)
    out_dc = R.DCache(
        np.zeros(R.DCache.footprint(mtu, depth), np.uint8), mtu, depth
    )
    cons = R.FSeq(np.zeros(R.FSeq.footprint(), np.uint8))
    ded = DedupTile(depth=1 << 10)
    base = ded.schema.with_base()
    lh = link_hist_names("in")
    schema = MetricsSchema(
        base.counters, base.hists + lh, wide_hists=base.wide_hists + lh
    )
    m = Metrics(np.zeros(Metrics.footprint(schema), np.uint8), schema)
    ring = SpanRing(
        np.zeros(SpanRing.footprint(ring_depth), np.uint8), ring_depth,
        sample,
    )
    tracer = Tracer(ring, sample, name="dedup")
    il = InLink(
        "in", in_mc, in_dc, in_fs, link_id=1, h_qwait="qwait_us_in",
        h_svc="svc_us_in", h_e2e="e2e_us_in",
    )
    ol = OutLink("out", out_mc, out_dc, [cons], link_id=2, tracer=tracer)
    ctx = MuxCtx(
        "dedup", R.CNC(np.zeros(R.CNC.footprint(), np.uint8)), [il], [ol], m
    )
    ctx.tracer = tracer
    ded.on_boot(ctx)
    return ded, ctx, cons, m, tracer


def _feed(ctx, sigs, tsorig, tspub):
    il = ctx.ins[0]
    n = len(sigs)
    rows = (
        (np.arange(96)[None, :] * 13 + np.arange(n)[:, None] * 7) & 0xFF
    ).astype(np.uint8)
    szs = np.full(n, 96, np.uint16)
    chunks = il.dcache.write_batch(rows, szs)
    il.mcache.publish_batch(
        il.mcache.seq_query(), np.asarray(sigs, np.uint64), chunks, szs,
        None, tspub, np.full(n, tsorig, np.uint32),
    )


def _py_reference_batch(ded, ctx, m, tracer, budget):
    """One Python-loop iteration's frag block, verbatim from
    disco.mux.run_loop: t_cons read, qwait/e2e hist_sample_many,
    batch_sz, tracer.ingest, on_frags (publishes + publish spans), svc
    sample."""
    il = ctx.ins[0]
    frags, il.seq, ovr = il.mcache.drain(il.seq, budget)
    assert ovr == 0
    if not len(frags):
        return 0
    m.hist_sample("batch_sz", len(frags))
    t_cons = M.now_ts()
    m.hist_sample_many(
        "qwait_us_in", np.maximum(M.ts_diff_arr(t_cons, frags["tspub"]), 0)
    )
    m.hist_sample_many(
        "e2e_us_in", np.maximum(M.ts_diff_arr(t_cons, frags["tsorig"]), 0)
    )
    tracer.ingest(il.link_id, frags, t_cons)
    ded.on_frags(ctx, 0, frags)
    m.hist_sample("svc_us_in", max(M.ts_diff(M.now_ts(), t_cons), 0))
    return len(frags)


@pytest.mark.parametrize("advance", [0, 1000])
def test_stem_trace_parity_with_python_loop(monkeypatch, advance):
    """THE acceptance differential: identical frag stream, injected
    deterministic clock (constant within a round; `advance` ticks
    between rounds so latencies are non-zero), K rounds of B frags with
    dups and zero tags.  The native path's qwait/svc/e2e/batch_sz hist
    words, its drained span-event stream, and its published frag metas
    (sig, sz, ctl, tsorig AND per-frag tspub) must equal the Python
    loop's bit for bit."""
    B, K = 64, 6
    clock = np.array([50_000, 0], np.uint64)
    monkeypatch.setattr(M, "now_ts", lambda: int(clock[0]) & 0xFFFFFFFF)

    def sig_round(k):
        sigs = [(k * B + i // 3) * 1000 + 1 for i in range(B)]
        sigs[5] = 0
        sigs[17] = 0
        if k:  # cross-round dups
            sigs[::7] = [((k - 1) * B) * 1000 + 1] * len(sigs[::7])
        return sigs

    # python reference
    ded_p, ctx_p, fs_p, m_p, tr_p = _mk_traced_dedup()
    # native stem with the armed in-burst trace
    ded_n, ctx_n, fs_n, m_n, tr_n = _mk_traced_dedup()
    ctx_n.trace_clock = clock
    spec = ded_n.native_handler(ctx_n)
    stem = R.Stem(ctx_n.ins, ctx_n.outs, spec, cap=B)
    assert _arm_stem_trace(stem, ctx_n, m_n, tr_n)
    assert stem.trace_armed

    for k in range(K):
        sigs = sig_round(k)
        tsorig = (int(clock[0]) - 3_000) & 0xFFFFFFFF
        tspub = (int(clock[0]) - 1_000) & 0xFFFFFFFF
        _feed(ctx_p, sigs, tsorig, tspub)
        _feed(ctx_n, sigs, tsorig, tspub)
        got_p = _py_reference_batch(ded_p, ctx_p, m_p, tr_p, B)
        got_n, status, _ = stem.run(B, M.now_ts())
        assert got_p == got_n == B
        assert status in (R.STEM_IDLE, R.STEM_BUDGET)
        # release out credits on both sides identically
        fs_p.update(ctx_p.outs[0].seq)
        fs_n.update(ctx_n.outs[0].seq)
        clock[0] += advance

    # hists: bit-identical contents (and they are WIDE)
    for h in ("qwait_us_in", "e2e_us_in", "svc_us_in", "batch_sz"):
        assert m_p.hist(h) == m_n.hist(h), h
    assert len(m_p.hist("qwait_us_in")["buckets"]) == WIDE_HIST_BUCKETS
    # per-frag sample coverage: every consumed frag sampled exactly once
    assert m_p.hist("qwait_us_in")["count"] == B * K

    # span streams: bit-identical drained events
    ep, cp, dp = tr_p.ring.read(0)
    en, cn, dn = tr_n.ring.read(0)
    assert (cp, dp) == (cn, dn)
    assert np.array_equal(ep, en)
    assert len(ep) > 0

    # published frag metas: bit-identical including the per-frag tspub
    fp, _, _ = ctx_p.outs[0].mcache.drain(0, B * K)
    fn, _, _ = ctx_n.outs[0].mcache.drain(0, B * K)
    assert np.array_equal(fp, fn)
    # both paths collapsed the same duplicates (the Python tile counts
    # its own; the stem's per-burst scratch is applied by run_loop, so
    # here the published-stream shortfall is the cross-check)
    assert len(fp) < B * K
    assert m_p.counter("dup_txns") == B * K - len(fp)


def test_stem_trace_parity_near_wrap(monkeypatch):
    """The same differential with the injected clock sitting just past
    the u32 wrap and frag stamps just before it — the C-side wrap math
    must agree with ts_diff on real hist content, not only in the
    unit test."""
    B = 32
    clock = np.array([5, 0], np.uint64)  # 5 µs past the wrap
    monkeypatch.setattr(M, "now_ts", lambda: int(clock[0]) & 0xFFFFFFFF)
    ded_p, ctx_p, fs_p, m_p, tr_p = _mk_traced_dedup(sample=1)
    ded_n, ctx_n, fs_n, m_n, tr_n = _mk_traced_dedup(sample=1)
    ctx_n.trace_clock = clock
    stem = R.Stem(ctx_n.ins, ctx_n.outs, ded_n.native_handler(ctx_n), cap=B)
    assert _arm_stem_trace(stem, ctx_n, m_n, tr_n)
    sigs = [i * 100 + 1 for i in range(B)]
    tsorig = (2**32 - 40) & 0xFFFFFFFF  # 45 µs of e2e across the wrap
    tspub = (2**32 - 10) & 0xFFFFFFFF   # 15 µs of qwait across the wrap
    _feed(ctx_p, sigs, tsorig, tspub)
    _feed(ctx_n, sigs, tsorig, tspub)
    assert _py_reference_batch(ded_p, ctx_p, m_p, tr_p, B) == B
    got, _, _ = stem.run(B, M.now_ts())
    assert got == B
    for h in ("qwait_us_in", "e2e_us_in"):
        assert m_p.hist(h) == m_n.hist(h), h
    # the wrap-crossing deltas landed where 15 µs / 45 µs belong
    q = m_n.hist("qwait_us_in")
    assert q["buckets"][3] == B and q["sum"] == 15 * B  # [8,16)
    e = m_n.hist("e2e_us_in")
    assert e["buckets"][5] == B and e["sum"] == 45 * B  # [32,64)
    ep, _, _ = tr_p.ring.read(0)
    en, _, _ = tr_n.ring.read(0)
    assert np.array_equal(ep, en)
    fs_p.update(ctx_p.outs[0].seq)
    fs_n.update(ctx_n.outs[0].seq)


# ---------------------------------------------------------------------------
# 4. SLO wide domain + native queue-wait under stem="native"


def test_slo_ceiling_above_2_16_validates_and_fires():
    """Acceptance: an `[slo] e2e_p99_us` ceiling above 2^16 µs (the
    RETIRED 16-bucket observability bound) validates, and a violation
    recorded in the widened hists actually fires the burn engine."""
    from firedancer_tpu.disco.slo import SloConfig, SloEngine

    ceiling = float(2**17)  # 131 ms: unobservable before ISSUE 15
    cfg = SloConfig(
        e2e_p99_us=ceiling, budget=0.01,
        fast_window_s=10.0, slow_window_s=10.0,
        burn_fast=1.0, burn_slow=1.0,
    )
    cfg.validate()  # must not raise
    eng = SloEngine(cfg, {})
    empty = {"count": 0, "sum": 0, "buckets": [0] * WIDE_HIST_BUCKETS}
    bad = [0] * WIDE_HIST_BUCKETS
    bad[18] = 1000  # [2^18, 2^19) µs — above the 2^17 ceiling
    loaded = {"count": 1000, "sum": 1000 * 2**18, "buckets": bad}
    eng.observe(
        {"sink": {"counters": {}, "lat_hists": {"e2e_us_a": empty}}},
        now=0.0,
    )
    eng.observe(
        {"sink": {"counters": {}, "lat_hists": {"e2e_us_a": loaded}}},
        now=1.0,
    )
    sts = {s.name: s for s in eng.evaluate(now=1.0)}
    st = sts["e2e_p99_us"]
    assert st.breached and st.measured > ceiling


def test_queue_wait_p99_from_native_samples_under_native_stem():
    """Acceptance: under `[topo] stem = "native"` with tracing on, the
    qwait samples feeding queue_wait_p99_us come from the C emitter —
    the dedup hop consumes every frag through the stem (stem_frags ==
    in_frags, py_frags == 0 for it) yet its qwait hist holds one sample
    per frag; the SLO engine and an attached Monitor both compute the
    objective from them, and the monitor reports full stem coverage."""
    from firedancer_tpu.app.monitor import Monitor
    from firedancer_tpu.disco.flight import snapshot_topology, tile_links
    from firedancer_tpu.disco.slo import SloConfig, SloEngine
    from firedancer_tpu.tiles import wire
    from firedancer_tpu.tiles.sink import SinkTile
    from firedancer_tpu.tiles.synth import SynthTile, make_txn_pool

    rows, szs, _ = make_txn_pool(256, seed=7)
    total = 512
    topo = Topology(name=f"trace_native_{int(time.time() * 1e6) & 0xFFFFFF}")
    topo.enable_trace(sample=4)
    topo.link("s", depth=1 << 10, mtu=wire.LINK_MTU)
    topo.link("d", depth=1 << 10, mtu=wire.LINK_MTU)
    topo.tile(SynthTile(rows, szs, total=total, repeat=2), outs=["s"])
    topo.tile(DedupTile(depth=1 << 14), ins=[("s", True)], outs=["d"])
    topo.tile(SinkTile(shm_log=1 << 13), ins=[("d", True)])
    topo.build()
    eng = SloEngine(
        SloConfig(queue_wait_p99_us=50_000.0, fast_window_s=10.0,
                  slow_window_s=10.0),
        tile_links(topo),
    )
    eng.observe(snapshot_topology(topo), now=0.0)
    topo.start(batch_max=128, stem="native")
    try:
        md = topo.metrics("dedup")
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            if (
                md.counter("in_frags") >= total
                and topo.metrics("sink").counter("in_frags") >= 256
            ):
                break
            time.sleep(0.02)
        assert md.counter("in_frags") >= total
        # full native coverage on the dedup hop: the qwait samples can
        # only have come from the in-burst C emitter
        assert md.counter("stem_engaged") == 1
        assert md.counter("py_frags") == 0
        assert md.counter("stem_frags") == md.counter("in_frags")
        hq = md.hist("qwait_us_s")
        assert hq["count"] == md.counter("in_frags")
        assert len(hq["buckets"]) == WIDE_HIST_BUCKETS
        assert hist_percentile(hq, 99.0) >= 0.0
        # the objective evaluates over those samples
        eng.observe(snapshot_topology(topo), now=1.0)
        sts = {s.name: s for s in eng.evaluate(now=1.0)}
        st = sts["queue_wait_p99_us"]
        assert st.burn_fast >= 0.0  # evaluated (window has samples)
        # spans were emitted natively: INGEST + PUBLISH events for the
        # dedup tile exist in its ring with the carried sig sampling
        ring = topo._tracers["dedup"].ring
        evs, _, _ = ring.read(0)
        kinds = {(int(w0) >> 56) & 0xFF for w0 in evs[:, 0]}
        assert T.INGEST in kinds and T.PUBLISH in kinds
        # an attached monitor reports the same coverage machine-readably
        mon = Monitor(topo.name)
        doc = mon.once()
        assert doc.get("stem_mode") == "native"
        srow = doc["tiles"]["dedup"]["stem"]
        assert srow["engaged"] and srow["coverage"] == 1.0
        assert not any("pinned to the Python loop" in a
                       for a in doc["alarms"])
        topo.halt()
    finally:
        topo.close()


# ---------------------------------------------------------------------------
# 5. monitor stem rows + pinned alarm (offline)


def _tile_row(stem_engaged, stem, py, extra=None):
    c = {
        "in_frags": stem + py, "out_frags": 0,
        "stem_engaged": stem_engaged, "stem_frags": stem, "py_frags": py,
        "loop_iters": 1, "backpressure_iters": 0,
    }
    c.update(extra or {})
    return {"signal": "RUN", "heartbeat": 1, "counters": c,
            "lat_hists": {}}


def test_monitor_stem_row_and_pin_alarm():
    """The stem-coverage row and the persistence alarm: a stem-engaged
    tile whose py_frags advance while stem_frags sit flat for
    STEM_PIN_STREAK consecutive snapshots alarms; healthy coverage and
    python-loop tiles never do; a tile whose stem NEVER ran while
    Python handled a meaningful stream flags pinned immediately."""
    from firedancer_tpu.app.monitor import Monitor

    mon = object.__new__(Monitor)

    # healthy native tile: full coverage row, no alarm
    row = Monitor.stem_row({"stem_engaged": 1, "stem_frags": 100,
                            "py_frags": 0})
    assert row == {"engaged": True, "stem_frags": 100, "py_frags": 0,
                   "coverage": 1.0, "pinned": False}
    # python-loop tile: no row at all
    assert Monitor.stem_row({"stem_engaged": 0, "py_frags": 50}) is None
    # cumulative full pin flags immediately (the --once case)
    assert Monitor.stem_row(
        {"stem_engaged": 1, "stem_frags": 0, "py_frags": 500}
    )["pinned"]

    # persistence: stem was healthy, then frags start flowing Python
    snaps = [
        {"dedup": _tile_row(1, 100, 0)},
        {"dedup": _tile_row(1, 100, 40)},
        {"dedup": _tile_row(1, 100, 80)},
        {"dedup": _tile_row(1, 100, 120)},
    ]
    fired = []
    for s in snaps:
        fired = [a for a in mon.alarms(s) if "pinned" in a]
    assert fired, "persistent pin never alarmed"
    # recovery: stem frags advance again -> streak resets, no alarm
    fired = [
        a
        for a in mon.alarms({"dedup": _tile_row(1, 200, 120)})
        if "pinned" in a
    ]
    assert not fired
    # render shows the coverage sub-row
    mon2 = object.__new__(Monitor)
    out = mon2.render(None, {"dedup": _tile_row(1, 300, 100)}, 1.0)
    assert "stem: cov=75.0%" in out
