"""disco layer tests: metrics, the mux loop, topologies, and the
synth → dedup → sink pipeline (the multi-tile-in-one-process harness the
reference models in src/disco/dedup/test_dedup.c)."""

import numpy as np
import pytest

from firedancer_tpu.disco import Metrics, MetricsSchema, Tile, Topology
from firedancer_tpu.disco.mux import MuxCtx
from firedancer_tpu.tiles.dedup import DedupTile
from firedancer_tpu.tiles.sink import SinkTile
from firedancer_tpu.tiles.synth import SynthTile, make_txn_pool
from firedancer_tpu.tiles import wire


# ---------------------------------------------------------------------------
# metrics


def test_metrics_counters_and_hists():
    schema = MetricsSchema(counters=("a", "b"), hists=("h",)).with_base()
    mem = np.zeros(Metrics.footprint(schema), dtype=np.uint8)
    m = Metrics(mem, schema)
    m.inc("a")
    m.inc("a", 5)
    m.set("b", 42)
    assert m.counter("a") == 6
    assert m.counter("b") == 42
    m.hist_sample("h", 1)
    m.hist_sample("h", 1024)
    m.hist_sample_many("h", np.array([2, 3, 4, 1 << 40]))
    h = m.hist("h")
    assert h["count"] == 6
    assert h["buckets"][0] == 1  # value 1
    assert h["buckets"][10] == 1  # value 1024
    assert h["buckets"][1] == 2  # values 2, 3
    assert h["buckets"][2] == 1  # value 4
    assert h["buckets"][15] == 1  # clamped huge value
    # readable cross-"process" through the same buffer
    m2 = Metrics(mem, schema)
    assert m2.counter("a") == 6


def test_metrics_wide_hist_domain():
    """sched_lag-class wide hists: 24 buckets, values past the 16-bucket
    2^16 ceiling stay representable, with the top bucket as the explicit
    overflow bucket.  Normal hists keep the 16-bucket layout (the two
    widths coexist in one region)."""
    from firedancer_tpu.disco.metrics import (
        WIDE_HIST_BUCKETS,
        hist_percentile,
    )

    schema = MetricsSchema(
        counters=("c",), hists=("narrow", "wide"), wide_hists=("wide",)
    )
    mem = np.zeros(Metrics.footprint(schema), dtype=np.uint8)
    m = Metrics(mem, schema)
    # 100 ms-class lag (past the 16-bucket domain's clamp) and a
    # sub-ms lag must BOTH be representable in the wide hist
    m.hist_sample("wide", 100_000)
    m.hist_sample("wide", 500)
    m.hist_sample_many("wide", np.array([100_000, 100_000, 100_000]))
    h = m.hist("wide")
    assert len(h["buckets"]) == WIDE_HIST_BUCKETS
    assert h["count"] == 5
    assert h["buckets"][16] == 4  # 100_000 in [2^16, 2^17) — NOT clamped
    p99 = hist_percentile(h, 99)
    assert 65_536 < p99 < 262_144, p99
    # the narrow hist still clamps at its 16-bucket overflow
    m.hist_sample("narrow", 100_000)
    hn = m.hist("narrow")
    assert len(hn["buckets"]) == 16
    assert hn["buckets"][15] == 1
    # overflow bucket: wide values beyond 2^24 land in the top bucket
    m.hist_sample("wide", 1 << 30)
    assert m.hist("wide")["buckets"][WIDE_HIST_BUCKETS - 1] == 1
    # cross-reader parity: a second Metrics over the same region with
    # the same schema decodes identically (the manifest contract)
    assert Metrics(mem, schema).hist("wide") == m.hist("wide")
    # the topology's schema flattening must PRESERVE wideness (a tile
    # declaring a wide hist whose width silently dropped to 16 buckets
    # would re-introduce the sched_lag saturation bug per-tile)
    class _WideTile(Tile):
        name = "w"
        schema = MetricsSchema(hists=("x_us",), wide_hists=("x_us",))

    topo = Topology()
    topo.tile(_WideTile())
    assert topo._tile_schema(topo.tiles["w"]).wide_hists == ("x_us",)


def test_slo_ceiling_bound_derived_from_hist_width():
    """The slo ceiling-bound check is derived from the storage format.
    ISSUE 15 widened the per-link latency hists to WIDE_HIST_BUCKETS,
    so the old 2^16-µs SLO-ceiling observability bound is RETIRED: a
    ceiling above 65.5 ms (e.g. 70 ms, or 2^17 µs) now validates, and
    the bound sits at the wide domain end (2^24 µs)."""
    from firedancer_tpu.disco.slo import (
        SloConfig,
        SloEngine,
        hist_domain_end_us,
    )

    assert hist_domain_end_us() == float(1 << 16)
    assert hist_domain_end_us(wide=True) == float(1 << 24)
    SloEngine(SloConfig(e2e_p99_us=50_000))  # observable: fine
    # above the RETIRED 16-bucket bound: now observable (wide hists)
    SloEngine(SloConfig(e2e_p99_us=70_000))
    SloEngine(SloConfig(e2e_p99_us=float(2**17)))
    with pytest.raises(ValueError, match="unobservable"):
        SloEngine(SloConfig(e2e_p99_us=float(1 << 24)))


# ---------------------------------------------------------------------------
# wire format


def test_wire_trailer_roundtrip():
    rows, szs, good = make_txn_pool(8, seed=3)
    assert good.all()
    tr = wire.parse_trailers(rows, szs.astype(np.int64))
    assert (tr["txn_sz"] + wire.TRAILER_SZ == szs).all()
    assert (tr["sig_cnt"] == 1).all()
    assert (tr["sig_off"] == 1).all()
    msgs, lens, sigs, pubs, txn_idx = wire.expand_sig_lanes(rows, tr, 512)
    assert len(lens) == 8
    # lane content matches a scalar re-parse
    from firedancer_tpu.ballet import txn as T

    for i in range(8):
        payload = bytes(rows[i, : tr["txn_sz"][i]])
        d = T.parse(payload)
        assert d is not None
        assert bytes(sigs[i]) == d.signatures(payload)[0]
        assert bytes(pubs[i]) == d.acct_addr(payload, 0)
        m = d.message(payload)
        assert lens[i] == len(m)
        assert bytes(msgs[i, : len(m)]) == m
        assert (msgs[i, len(m) :] == 0).all()


def test_expand_multi_sig_lanes():
    # synthetic 2-sig rows: exercise the repeat/cumsum lane expansion
    rows, szs, _ = make_txn_pool(4, seed=5)
    tr = wire.parse_trailers(rows, szs.astype(np.int64))
    tr = {k: v.copy() for k, v in tr.items()}
    tr["sig_cnt"][:] = np.array([1, 2, 1, 3])
    msgs, lens, sigs, pubs, txn_idx = wire.expand_sig_lanes(rows, tr, 256)
    assert len(lens) == 7
    assert (txn_idx == np.array([0, 1, 1, 2, 3, 3, 3])).all()


# ---------------------------------------------------------------------------
# pipeline: synth -> dedup -> sink (no device work; pure runtime test)


def _run_pipeline(pool_n, repeat, total, depth=1 << 12, batch_max=256):
    rows, szs, _ = make_txn_pool(pool_n, seed=7)
    synth = SynthTile(rows, szs, total=total, repeat=repeat)
    dedup = DedupTile(depth=depth)
    sink = SinkTile(record=True)

    topo = Topology()
    topo.link("synth_dedup", depth=512, mtu=wire.LINK_MTU)
    topo.link("dedup_sink", depth=512, mtu=wire.LINK_MTU)
    topo.tile(synth, outs=["synth_dedup"])
    topo.tile(dedup, ins=[("synth_dedup", True)], outs=["dedup_sink"])
    topo.tile(sink, ins=[("dedup_sink", True)])
    topo.build()
    topo.start(batch_max=batch_max)
    import time

    deadline = time.monotonic() + 30.0
    while synth.sent < total and time.monotonic() < deadline:
        topo.poll_failure()
        time.sleep(0.01)
    # let the tail drain
    t_end = time.monotonic() + 5.0
    while time.monotonic() < t_end:
        topo.poll_failure()
        if topo.metrics("sink").counter("in_frags") + topo.metrics(
            "dedup"
        ).counter("dup_txns") >= total:
            break
        time.sleep(0.01)
    topo.halt()
    return topo, synth, dedup, sink


def test_pipeline_dedup_drops_repeats():
    pool_n, repeat = 64, 3
    total = pool_n * repeat
    topo, synth, dedup, sink = _run_pipeline(pool_n, repeat, total)
    try:
        assert synth.sent == total
        md = topo.metrics("dedup")
        ms = topo.metrics("sink")
        assert md.counter("in_frags") == total
        assert md.counter("overrun_frags") == 0
        assert md.counter("dup_txns") == total - pool_n
        assert ms.counter("sunk_frags") == pool_n
        # each unique tag exactly once, and payloads intact
        sigs = sink.all_sigs()
        assert len(sigs) == pool_n
        assert len(np.unique(sigs)) == pool_n
        assert set(sigs.tolist()) == set(synth.tags.tolist())
    finally:
        topo.close()


def test_pipeline_flow_control_no_loss():
    """Tiny rings + reliable consumers: credit flow control must prevent
    any overrun loss end to end."""
    pool_n, repeat = 32, 1
    total = 2048  # cycles the pool many times
    rows, szs, _ = make_txn_pool(pool_n, seed=11)
    synth = SynthTile(rows, szs, total=total, repeat=1)
    sink = SinkTile()

    topo = Topology()
    topo.link("s", depth=16, mtu=wire.LINK_MTU)
    topo.tile(synth, outs=["s"])
    topo.tile(sink, ins=[("s", True)])
    topo.build()
    topo.start(batch_max=8)
    import time

    deadline = time.monotonic() + 30.0
    while (
        topo.metrics("sink").counter("in_frags") < total
        and time.monotonic() < deadline
    ):
        topo.poll_failure()
        time.sleep(0.005)
    topo.halt()
    try:
        assert topo.metrics("sink").counter("in_frags") == total
        assert topo.metrics("sink").counter("overrun_frags") == 0
    finally:
        topo.close()


def test_tile_failure_fail_stop():
    class BoomTile(Tile):
        name = "boom"

        def on_frags(self, ctx: MuxCtx, in_idx: int, frags: np.ndarray) -> None:
            raise RuntimeError("boom")

    rows, szs, _ = make_txn_pool(4, seed=13)
    synth = SynthTile(rows, szs, total=16)
    topo = Topology()
    topo.link("s", depth=64, mtu=wire.LINK_MTU)
    topo.tile(synth, outs=["s"])
    topo.tile(BoomTile(), ins=[("s", False)])
    topo.build()
    topo.start()
    import time

    with pytest.raises(RuntimeError):
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            time.sleep(0.01)
    topo.close()
