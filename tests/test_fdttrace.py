"""fdttrace tier-1 surface: wrap-safe timestamp math, span rings,
percentile estimation, and the end-to-end trace/summary workflow against
the chaos topology (quic -> verify -> dedup -> pack).

Acceptance criteria under test (ISSUE 5):
  - `scripts/fdttrace.py --summary` prints per-hop p50/p99 for the
    quic -> verify -> dedup -> pack path;
  - its Chrome trace-event JSON validates: a list of {"ph": "X"|"B"|"E"}
    events with monotone per-track timestamps;
  - injected faults and the supervisor restart are annotated into the
    trace (the kill -> restart gap is assertable).

Everything runs on the strict host verify path (device="off"), JAX-free.
"""

from __future__ import annotations

import json
import socket
import time

import numpy as np
import pytest

from firedancer_tpu.disco import (
    Fault,
    FaultInjector,
    RestartPolicy,
    Supervisor,
    Topology,
    hist_percentile,
    ts_diff,
    ts_diff_arr,
)
from firedancer_tpu.disco import trace as T
from firedancer_tpu.disco.metrics import HIST_BUCKETS, Metrics, MetricsSchema
from firedancer_tpu.tango import rings as R
from firedancer_tpu.tiles import wire
from firedancer_tpu.tiles.bank import BankTile
from firedancer_tpu.tiles.dedup import DedupTile
from firedancer_tpu.tiles.pack import PackTile
from firedancer_tpu.tiles.quic import QuicIngressTile
from firedancer_tpu.tiles.sink import SinkTile
from firedancer_tpu.tiles.verify import VerifyTile

from scripts import fdttrace


# ---------------------------------------------------------------------------
# ts_diff: wrap-safe u32 compressed-timestamp arithmetic (satellite 1)


def test_ts_diff_wrap_boundary():
    # plain subtraction would be -(2^32 - 21) garbage here
    assert ts_diff(5, 0xFFFFFFF0) == 21
    assert ts_diff(0xFFFFFFF0, 5) == -21
    assert ts_diff(7, 7) == 0
    assert ts_diff(0, 0xFFFFFFFF) == 1
    assert ts_diff(0xFFFFFFFF, 0) == -1
    # half-window extremes
    assert ts_diff(1 << 31, 0) == -(1 << 31)
    assert ts_diff((1 << 31) - 1, 0) == (1 << 31) - 1
    # inputs beyond u32 are reduced mod 2^32 first
    assert ts_diff((1 << 32) + 9, 4) == 5


def test_ts_diff_arr_matches_scalar():
    rng = np.random.default_rng(11)
    a = rng.integers(0, 1 << 32, 256, np.uint64).astype(np.uint32)
    b = rng.integers(0, 1 << 32, 256, np.uint64).astype(np.uint32)
    got = ts_diff_arr(a, b)
    want = [ts_diff(int(x), int(y)) for x, y in zip(a, b)]
    assert got.tolist() == want
    # scalar-vs-array broadcast across the wrap
    got = ts_diff_arr(np.uint32(5), np.array([0xFFFFFFF0, 3], np.uint32))
    assert got.tolist() == [21, 2]


# ---------------------------------------------------------------------------
# percentile estimation vs exact numpy percentiles (satellite 4)


def _hist_of(values: np.ndarray) -> dict:
    schema = MetricsSchema(hists=("h",))
    m = Metrics(np.zeros(Metrics.footprint(schema), np.uint8), schema)
    m.hist_sample_many("h", values.astype(np.int64))
    return m.hist("h")


@pytest.mark.parametrize(
    "name,values",
    [
        ("uniform", np.random.default_rng(1).integers(1, 5000, 20000)),
        ("exponential", np.random.default_rng(2).exponential(800, 20000)),
        ("lognormal", np.random.default_rng(3).lognormal(5.0, 1.2, 20000)),
        ("constant", np.full(1000, 100.0)),
        ("bimodal", np.concatenate([
            np.full(9900, 50.0),
            np.random.default_rng(4).uniform(8000, 16000, 100),
        ])),
    ],
)
def test_hist_percentile_tracks_numpy(name, values):
    """Log-bucket interpolation is exact to within the bucket's 2x span:
    the estimate must land inside [exact/2, 2*exact] (plus the integer
    floor at the bottom buckets)."""
    values = np.maximum(np.asarray(values), 0)
    h = _hist_of(values)
    ints = np.maximum(values.astype(np.int64), 1)  # the stored domain
    for q in (50.0, 90.0, 99.0, 99.9):
        # method="lower": an actual sample, not numpy's between-samples
        # interpolation (which lands inside the gap of a bimodal
        # distribution where no sample exists)
        exact = float(np.percentile(ints, q, method="lower"))
        est = hist_percentile(h, q)
        lo, hi = exact / 2.0 - 2.0, exact * 2.0 + 2.0
        assert lo <= est <= hi, (name, q, exact, est)


def test_hist_percentile_boundary_contract():
    """ISSUE 6 satellite: the estimator's boundary behavior is pinned —
    empty/torn inputs, q extremes, single-bucket and overflow-bucket
    mass all produce finite, in-bucket estimates."""
    from firedancer_tpu.disco.metrics import hist_frac_above

    # single bucket: all mass in [64, 128); q=0 -> lower edge, q=100 ->
    # upper edge, q clamped outside [0, 100]
    h = _hist_of(np.array([100.0] * 50))
    assert hist_percentile(h, 0) == 64.0
    assert hist_percentile(h, 100) == 128.0
    assert hist_percentile(h, -5) == 64.0
    assert hist_percentile(h, 250) == 128.0
    # all mass in the clamped overflow bucket: finite, inside
    # [2^15, 2^16] (the documented 2x-span bias beyond the top bucket)
    h = _hist_of(np.array([1e12] * 10))
    for q in (0.0, 50.0, 100.0):
        assert (1 << 15) <= hist_percentile(h, q) <= (1 << 16)
    # torn snapshot: count incremented ahead of its bucket — the walk
    # must stay inside the occupied mass, not jump to the 2^16 sentinel
    h = {"buckets": [0] * 6 + [5] + [0] * 9, "count": 50, "sum": 0}
    assert 64.0 <= hist_percentile(h, 99) <= 128.0
    # count > 0 with no occupied bucket at all (torn) -> 0.0
    assert hist_percentile(
        {"buckets": [0] * 16, "count": 3, "sum": 0}, 50
    ) == 0.0
    # negative bucket deltas (windowed diffs of torn reads) are ignored
    h = {"buckets": [-2, 0, 4] + [0] * 13, "count": 4, "sum": 0}
    assert 4.0 <= hist_percentile(h, 50) <= 8.0
    # hist_frac_above (the SLO engine's primitive): exact on bucket
    # boundaries, clamped at the ends, safe on empty
    h = _hist_of(np.array([100.0] * 90 + [10000.0] * 10))
    assert abs(hist_frac_above(h, 1000) - 0.1) < 1e-9
    assert hist_frac_above(h, 0) > 0.99
    assert hist_frac_above(h, 1 << 20) == 0.0
    assert hist_frac_above({"buckets": [], "count": 0}, 5) == 0.0


def test_hist_percentile_edge_cases():
    assert hist_percentile({"buckets": [], "count": 0, "sum": 0}, 99) == 0.0
    assert hist_percentile({}, 50) == 0.0
    # single sample of 100 -> bucket 6 = [64, 128); every q interpolates
    # inside that bucket
    h = _hist_of(np.array([100.0]))
    for q in (0.0, 50.0, 99.9, 100.0):
        assert 64.0 <= hist_percentile(h, q) <= 128.0
    # clamped top bucket: values beyond 2^16 still produce a finite,
    # top-bucket estimate
    h = _hist_of(np.array([1e9] * 10))
    assert (1 << (HIST_BUCKETS - 1)) <= hist_percentile(h, 50) <= (
        1 << HIST_BUCKETS
    )


# ---------------------------------------------------------------------------
# span ring storage contract


def test_span_ring_write_read_wrap_and_join():
    depth = 16
    mem = np.zeros(T.SpanRing.footprint(depth), np.uint8)
    ring = T.SpanRing(mem, depth, sample=4)
    rows = np.arange(10 * T.EVENT_WORDS, dtype=np.uint64).reshape(10, -1)
    ring.write_block(rows)
    ev, cur, dropped = ring.read(0)
    assert (cur, dropped) == (10, 0)
    assert np.array_equal(ev, rows)
    # lap the ring: only the last `depth` events survive, the reader
    # reports the overwritten ones as dropped
    more = np.arange(20 * T.EVENT_WORDS, dtype=np.uint64).reshape(20, -1)
    ring.write_block(more)
    ev, cur, dropped = ring.read(10)
    assert cur == 30 and dropped == 4  # events 10..13 were lapped
    assert len(ev) == depth
    assert np.array_equal(ev, more[-depth:])
    # incremental cursor: nothing new -> empty, nothing dropped
    ev, cur2, dropped = ring.read(cur)
    assert len(ev) == 0 and cur2 == cur and dropped == 0
    # a reader joining the same memory sees the header config
    j = T.SpanRing(mem, join=True)
    assert (j.depth, j.sample) == (depth, 4)
    assert j.cursor() == 30
    # torn-write guard: the writer reserves (header word3) BEFORE
    # storing rows — a read overlapping an in-progress write_block must
    # discard every slot the reservation covers, not return torn rows.
    # Simulate the mid-write state: reservation advanced, committed
    # cursor and slots untouched.
    ring.words[3] = np.uint64(30 + 6)
    ev, cur, dropped = ring.read(14)
    assert cur == 30 and dropped == 6  # 14..19 may be mid-overwrite
    assert np.array_equal(ev, more[-depth:][6:])
    ring.words[3] = np.uint64(30)  # restore the quiescent invariant


def test_span_ring_concurrent_drain_never_torn_or_duplicated():
    """ISSUE 6 satellite: a reader draining (the fdttrace --follow
    path) while the writer wraps the ring must never observe a torn or
    duplicated event.  Every written row is self-checking (w1/w2/w3 are
    functions of w0), so any torn row returned as data is detected; the
    reader's (returned + dropped) accounting must exactly cover the
    written stream."""
    import threading

    depth = 256
    mem = np.zeros(T.SpanRing.footprint(depth), np.uint8)
    ring = T.SpanRing(mem, depth, sample=1)
    total = 40_000
    magic = np.uint64(0x9E3779B97F4A7C15)
    done = threading.Event()

    # the final burst is one block LARGER than the ring: write_block
    # keeps only the tail, so the head of that block is unreadably
    # lapped no matter how the threads interleave — the wrap-accounting
    # path is exercised deterministically, not scheduling-dependent
    final_burst = depth + 64

    def writer():
        rng = np.random.default_rng(7)
        i = 0
        while i < total - final_burst:
            k = min(int(rng.integers(1, 48)), total - final_burst - i)
            ring.write_block(_rows(i, k))
            i += k
        ring.write_block(_rows(i, final_burst))
        done.set()

    def _rows(i, k):
        idx = np.arange(i, i + k, dtype=np.uint64)
        rows = np.empty((k, T.EVENT_WORDS), np.uint64)
        rows[:, 0] = idx
        rows[:, 1] = idx ^ magic
        rows[:, 2] = idx * np.uint64(3)
        rows[:, 3] = ~idx
        return rows

    t = threading.Thread(target=writer)
    t.start()
    seen: list[int] = []
    since = 0
    dropped_total = 0
    final_pass = False
    while True:
        ev, cur, dropped = ring.read(since)
        # accounting: everything between the cursors is either returned
        # or declared dropped — nothing silently vanishes
        assert len(ev) + dropped == cur - since
        if len(ev):
            idx = ev[:, 0]
            # torn-row detection: all four words must be consistent
            assert np.array_equal(ev[:, 1], idx ^ magic)
            assert np.array_equal(ev[:, 2], idx * np.uint64(3))
            assert np.array_equal(ev[:, 3], ~idx)
            seen.extend(int(x) for x in idx)
        dropped_total += dropped
        since = cur
        if final_pass:
            break
        if done.is_set():
            final_pass = True  # one more drain after the writer stopped
    t.join()
    # no duplicates, globally in order, and full coverage
    assert len(seen) == len(set(seen))
    assert seen == sorted(seen)
    assert len(seen) + dropped_total == total
    # the oversized final burst guarantees at least one lap was
    # observed regardless of thread scheduling
    assert dropped_total >= final_burst - depth, (
        "ring never wrapped under the reader"
    )


def test_tracer_sampling_selects_same_sigs_every_hop():
    depth = 64
    ring = T.SpanRing(
        np.zeros(T.SpanRing.footprint(depth), np.uint8), depth, sample=4
    )
    tr = T.Tracer(ring, sample=4)
    frags = np.zeros(16, R.FRAG_DTYPE)
    frags["sig"] = np.arange(16)
    frags["seq"] = np.arange(16) + 100
    frags["tspub"] = 7
    frags["tsorig"] = 3
    tr.ingest(2, frags, ts=9)
    tr.publish(3, 200, frags["sig"], tspub=11, tsorigs=frags["tsorig"])
    evs = T.decode(ring.read(0)[0])
    ingests = [e for e in evs if e["kind"] == T.INGEST]
    pubs = [e for e in evs if e["kind"] == T.PUBLISH]
    # sig % 4 == 0 -> sigs 0, 4, 8, 12 at BOTH hops (the sig is the
    # carried dedup tag, so sampling picks the same frags everywhere)
    assert [e["sig"] for e in ingests] == [0, 4, 8, 12]
    assert [e["sig"] for e in pubs] == [0, 4, 8, 12]
    e = ingests[1]
    assert (e["link"], e["ts"], e["seq"]) == (2, 9, 104)
    assert e["aux64"] == (3 << 32) | 7  # tsorig / tspub ride along
    assert [e["seq"] for e in pubs] == [200, 204, 208, 212]


# ---------------------------------------------------------------------------
# the acceptance run: chaos topology + fdttrace --summary + Chrome JSON


def _mint_txns(n: int, seed: int) -> list[bytes]:
    from firedancer_tpu.ballet import txn as TX
    from firedancer_tpu.ops.ed25519 import hostpath

    rng = np.random.default_rng(seed)
    sk = rng.integers(0, 256, 32, np.uint8).tobytes()
    pk = hostpath.public_from_secret(sk)
    blockhash = rng.integers(0, 256, 32, np.uint8).tobytes()
    out = []
    for _ in range(n):
        extra = [rng.integers(0, 256, 32, np.uint8).tobytes()]
        data = rng.integers(0, 256, 24, np.uint8).tobytes()
        body = TX.build([bytes(64)], [pk] + extra, blockhash,
                        [(1, [0], data)])
        desc = TX.parse(body)
        sig = hostpath.sign(sk, desc.message(body))
        out.append(body[:1] + sig + body[1 + 64 :])
    return out


def test_fdttrace_summary_and_chrome_trace(tmp_path, capsys):
    """The flagship workflow: run the tier-1 chaos topology (named
    workspace, tracing on, a scripted kill of verify), then drive
    scripts/fdttrace.py against it — the summary table must carry
    per-hop p50/p99 for quic -> verify -> dedup -> pack, and the Chrome
    trace must validate and contain the kill + restart annotations."""
    n_txns = 80
    txns = _mint_txns(n_txns, seed=0x7ACE)
    name = f"fdttrace_{int(time.time() * 1e6) & 0xFFFFFF}"

    inj = FaultInjector(seed=1, faults=[
        Fault("verify", "kill", at=30, on="frag"),
    ])
    identity = np.random.default_rng(9).integers(
        0, 256, 32, np.uint8
    ).tobytes()
    qt = QuicIngressTile(identity)
    verify = VerifyTile(
        msg_width=256, max_lanes=32, pre_dedup=False, device="off",
        async_depth=2,
    )
    dedup = DedupTile(depth=1 << 12)
    pack = PackTile(1, microblock_ns=1_000)
    bank = BankTile(0)
    sink = SinkTile(record=True)

    topo = Topology(name=name)
    topo.enable_trace(sample=1, depth=1 << 14)
    topo.link("quic_verify", depth=256, mtu=wire.LINK_MTU)
    topo.link("verify_dedup", depth=256, mtu=wire.LINK_MTU)
    topo.link("dedup_pack", depth=256, mtu=wire.LINK_MTU)
    topo.link("pack_bank0", depth=64, mtu=40_000)
    topo.link("bank0_pack", depth=64)
    topo.link("bank0_poh", depth=64, mtu=40_000)
    topo.tile(qt, outs=["quic_verify"])
    topo.tile(verify, ins=[("quic_verify", True)], outs=["verify_dedup"])
    topo.tile(dedup, ins=[("verify_dedup", True)], outs=["dedup_pack"])
    topo.tile(
        pack,
        ins=[("dedup_pack", True), ("bank0_pack", True)],
        outs=["pack_bank0"],
    )
    topo.tile(bank, ins=[("pack_bank0", True)],
              outs=["bank0_pack", "bank0_poh"])
    topo.tile(sink, ins=[("dedup_pack", True)])

    sup = Supervisor(
        topo,
        RestartPolicy(
            hb_timeout_s=2.0, backoff_base_s=0.05,
            replay={"verify": 256},
        ),
        faults=inj,
    )
    sup.start(batch_max=32)
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        for t in txns:
            tx.sendto(t, qt.udp_addr)
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            bad = {
                n: d
                for n in topo.tiles
                if (d := sup.degraded(n)) is not None
            }
            assert not bad, f"tiles degraded: {bad}"
            if (
                len(set(sink.all_sigs().tolist())) >= n_txns
                and topo.metrics("pack").counter("inserted_txns") >= n_txns
                and sup.restarts("verify") >= 1
            ):
                break
            time.sleep(0.05)
        else:
            raise TimeoutError("pipeline did not drain")

        # ---- --summary: per-hop p50/p99 table (acceptance) ----
        rc = fdttrace.main([name, "--summary"])
        assert rc == 0
        out = capsys.readouterr().out
        for hop in (
            "verify < quic_verify",
            "dedup < verify_dedup",
            "pack < dedup_pack",
        ):
            assert hop in out, out
        rows = fdttrace.summary_rows(fdttrace.TraceSession.attach(name))
        by_hop = {(r["tile"], r["link"]): r for r in rows}
        for hop in (
            ("verify", "quic_verify"),
            ("dedup", "verify_dedup"),
            ("pack", "dedup_pack"),
        ):
            r = by_hop[hop]
            for kind in ("qwait_us", "e2e_us"):
                assert r[kind]["count"] > 0, (hop, rows)
                assert r[kind]["p99"] >= r[kind]["p50"] >= 0.0
        # e2e accumulates down the path (p50 at pack >= p50 at verify)
        assert (
            by_hop[("pack", "dedup_pack")]["e2e_us"]["p50"]
            >= by_hop[("verify", "quic_verify")]["e2e_us"]["p50"]
        )

        # ---- Chrome trace-event JSON export (acceptance) ----
        trace_path = tmp_path / "trace.json"
        rc = fdttrace.main(
            [name, "--seconds", "0.2", "--out", str(trace_path)]
        )
        assert rc == 0
        capsys.readouterr()
        doc = json.loads(trace_path.read_text())
        assert isinstance(doc, list) and len(doc) > n_txns
        last_ts: dict = {}
        for e in doc:
            assert e["ph"] in ("X", "B", "E"), e
            assert e["dur"] >= 0 and e["ts"] >= 0
            key = (e["pid"], e["tid"])
            assert e["ts"] >= last_ts.get(key, 0), (key, e)
            last_ts[key] = e["ts"]
        names = {e["name"] for e in doc}
        assert any("verify quic_verify" in n for n in names), names
        assert any("dedup verify_dedup" in n for n in names), names
        # the scripted kill and the supervisor's restart are annotated —
        # the kill -> restart gap is visible in the trace
        assert "verify fault:kill" in names, names
        assert "verify fault:restart" in names, names
        kill_ts = [e["ts"] for e in doc if e["name"] == "verify fault:kill"]
        restart_ts = [
            e["ts"] for e in doc if e["name"] == "verify fault:restart"
        ]
        assert min(restart_ts) >= min(kill_ts)

        # ---- timeline completeness over the drained spans ----
        session = fdttrace.TraceSession.attach(name)
        session.drain()
        assert sum(session.dropped.values()) == 0
        timelines = fdttrace.assemble(session)
        whole, lost = fdttrace.classify(
            timelines, ["quic_verify", "verify_dedup", "dedup_pack"]
        )
        sunk = set(sink.all_sigs().tolist())
        assert sunk <= whole
    finally:
        tx.close()
        sup.halt()
        topo.close()


def test_trace_off_installs_no_tracer():
    """sampling=0 / no enable_trace: the topology installs no tracer and
    allocates no span rings — the hot path pays only the None checks."""
    # both entry points honor TraceConfig's "sample <= 0 disables"
    # contract — the constructor path must not install a full-rate
    # tracer for a config object that means "off"
    assert Topology(trace=T.TraceConfig(sample=0)).trace is None
    topo = Topology()
    topo.enable_trace(sample=0)
    assert topo.trace is None
    topo.link("a_sink", depth=64, mtu=wire.LINK_MTU)
    topo.tile(SinkTile(name="src"), outs=["a_sink"])
    topo.tile(SinkTile(), ins=[("a_sink", True)])
    topo.build()
    assert topo._tracers == {}
    assert topo.tiles["sink"].ctx.tracer is None
    assert all(not k.startswith("trace_") for k in topo.wksp._allocs)
    # the per-link latency hists are part of the schema regardless of
    # tracing (attribution is always-on; spans are the opt-in layer)
    assert "qwait_us_a_sink" in topo.metrics("sink").schema.hists
    topo.close()


# ---------------------------------------------------------------------------
# the verify tile's batch lifecycle in the span rings, `[trace]` in a
# config file, and the profiler-clock tie (ISSUE 25)


def _admit_all(digests, sigs, pubs):
    return np.ones(len(digests), bool)


def test_batch_lifecycle_is_in_the_ring_once_per_pool_seq():
    """synth -> verify -> sink under the real run loop, tracing at
    sample 1: the verify ring holds STAGE, ENQUEUE, DISPATCH, LAND and
    PUBLISHED exactly once per pool_seq, in time order, and the Chrome
    export draws them as fill / queue / batch / drain on one track."""
    from firedancer_tpu.tiles.synth import SynthTile, make_txn_pool

    n = 40
    rows, szs, _ = make_txn_pool(n, seed=31)
    verify = VerifyTile(
        msg_width=256, max_lanes=8, pre_dedup=False, device_fn=_admit_all,
        async_depth=2,
    )
    topo = Topology()
    topo.enable_trace(sample=1, depth=1 << 12)
    topo.link("synth_verify", depth=256, mtu=wire.LINK_MTU)
    topo.link("verify_sink", depth=256, mtu=wire.LINK_MTU)
    topo.tile(SynthTile(rows, szs, total=n), outs=["synth_verify"])
    topo.tile(verify, ins=[("synth_verify", True)], outs=["verify_sink"])
    topo.tile(SinkTile(), ins=[("verify_sink", True)])
    topo.start(batch_max=8)
    try:
        deadline = time.monotonic() + 60.0
        while topo.metrics("sink").counter("sunk_frags") < n:
            assert time.monotonic() < deadline, "pipeline did not drain"
            topo.poll_failure()
            time.sleep(0.01)
    finally:
        topo.halt()
    try:
        m = topo.metrics("verify").read()
        batches = m["device_batches"]
        assert batches >= n // 8
        for h in ("batch_fill_us", "batch_queue_us", "batch_inflight_us",
                  "batch_drain_us"):
            assert m[h]["count"] == batches, h
        session = fdttrace.TraceSession.from_topology(topo)
        session.drain()
        assert sum(session.dropped.values()) == 0
        life = (T.STAGE, T.ENQUEUE, T.DISPATCH, T.LAND, T.PUBLISHED)
        per_seq: dict = {}
        for e in session.events["verify"]:
            if e["kind"] in life:
                per_seq.setdefault(e["seq"], []).append(e)
        assert sorted(per_seq) == list(range(batches))
        for seq, evs in per_seq.items():
            by_kind = {e["kind"]: e["ts"] for e in evs}
            assert len(evs) == 5 and set(by_kind) == set(life), (seq, evs)
            ts = [by_kind[k] for k in life]
            assert all(ts_diff(b, a) >= 0 for a, b in zip(ts, ts[1:])), ts
        assert [T.KIND_NAMES[k] for k in life] == [
            "stage", "enqueue", "dispatch", "land", "published"]
        doc = fdttrace.chrome_trace(session)
        track = [e for e in doc if e["args"].get("pool_seq") is not None]
        assert len({e["tid"] for e in track}) == 1
        for part in ("fill", "queue", "dev0 batch", "drain"):
            spans = [e for e in track if e["name"] == f"verify {part}"]
            assert sorted(e["args"]["pool_seq"] for e in spans) == list(
                range(batches)), part
        # a batch's four spans abut: each starts where the last one ended
        # (to the microsecond the 1 us floor on a span's length allows)
        first = sorted((e for e in track if e["args"]["pool_seq"] == 0),
                       key=lambda e: e["ts"])
        assert [e["name"].split(" ", 1)[1] for e in first] == [
            "fill", "queue", "dev0 batch", "drain"]
        for a, b in zip(first, first[1:]):
            assert 0 <= a["ts"] + a["dur"] - b["ts"] <= 1
    finally:
        topo.close()


@pytest.mark.parametrize("section,want", [
    ("", None),
    ("[trace]\n", T.TraceConfig(sample=64, depth=1 << 14)),
    ("[trace]\nsample = 1\ndepth = 256\n", T.TraceConfig(1, 256)),
    ("[trace]\nsample = 0\n", "off"),
])
def test_trace_section_of_a_config_file_installs_the_tracers(section, want):
    """`[trace]` is the operator's switch: a topology booted from a file
    (fdtctl run, every benchmark cell) gets span rings from it, and none
    without it."""
    from firedancer_tpu.app import config as C

    cfg = C.parse(
        "[tiles.dedup]\nsignature_cache_size = 4096\n"
        "[links]\ndepth = 64\n" + section
    )
    assert cfg.trace == (T.TraceConfig(sample=0) if want == "off" else want)
    topo, _ = C.build_ingress_topology(cfg, bytes(32))
    topo.build()
    try:
        if want in (None, "off"):
            assert topo._tracers == {}
            assert all(ts.ctx.tracer is None for ts in topo.tiles.values())
            assert all(not k.startswith("trace_") for k in topo.wksp._allocs)
        else:
            assert set(topo._tracers) == set(topo.tiles)
            tr = topo.tiles["verify0"].ctx.tracer
            assert (tr.sample, tr.ring.depth) == (want.sample, want.depth)
    finally:
        topo.close()


@pytest.mark.parametrize("text,match", [
    ("[trace]\ndepth = 1000\n", "power of two"),
    ("[trace]\nrate = 3\n", "unknown keys"),
])
def test_trace_section_rejects_what_it_cannot_run(text, match):
    from firedancer_tpu.app import config as C

    with pytest.raises(ValueError, match=match):
        C.parse(text)


def test_profiler_clock_is_tied_to_the_rings_by_fdt_clock(tmp_path):
    """The CPU profiler records TraceAnnotations too: a trace that holds
    `fdt.clock` spans gives the offset from the profiler's clock to
    time.monotonic_ns(), and the program's `fdt.*` spans land on the
    rings' time axis within the tie's own jitter."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        marks = []
        for seq in range(3):
            now = time.monotonic_ns()
            with jax.profiler.TraceAnnotation("fdt.clock", mono_ns=now):
                pass
            t0 = time.monotonic_ns()
            with jax.profiler.TraceAnnotation(
                    "fdt.verify.dispatch", seq=seq, lanes=8, txns=3,
                    tiles=1, dev=seq % 2):
                time.sleep(0.002)
            marks.append(t0)
        with jax.profiler.TraceAnnotation("not.ours"):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    offset, events = fdttrace.profiler_events(str(path))
    assert offset is not None
    names = [e["name"] for e in events]
    assert names.count("fdt.clock") == 3 and "not.ours" not in names
    spans = sorted((e for e in events if e["name"] == "fdt.verify.dispatch"),
                   key=lambda e: e["start_ns"])
    assert len(spans) == 3
    # the worker's pool domain rides along (`dev=`), beside seq and lanes,
    # and so do the batch's txns (fewer than its lanes where a txn carries
    # several signatures) and the kernel tiles the device computed
    assert [e["args"] for e in spans] == [
        dict(seq=i, lanes=8, txns=3, tiles=1, dev=i % 2) for i in range(3)]
    for e, t0 in zip(spans, marks):
        assert abs(e["start_ns"] + offset - t0) < 1_000_000  # < 1 ms
        assert e["dur_ns"] >= 2_000_000
    # on the Chrome export they sit under pid 2, on the rings' axis
    ring = T.SpanRing(np.zeros(T.SpanRing.footprint(64), np.uint8), 64, 1)
    T.Tracer(ring, 1).point(T.HK, ts=(marks[0] // 1000) & 0xFFFFFFFF)
    session = fdttrace.TraceSession({"verify": ring}, [])
    session.drain()
    doc = fdttrace.chrome_trace(session, (offset, events))
    ours = [e for e in doc if e["pid"] == 2
            and e["name"] == "fdt.verify.dispatch"]
    hk = next(e for e in doc if e["name"] == "verify hk")
    assert [e["args"]["dev"] for e in ours] == [0, 1, 0]
    assert len(ours) == 3 and abs(min(e["ts"] for e in ours) - hk["ts"]) < 1000
