"""pcap determinism + replay tile: write a corpus, replay it twice through
a pipeline, assert bit-identical delivery (round-1 review, item 6)."""

import time

import numpy as np
import pytest

from firedancer_tpu.disco import Topology
from firedancer_tpu.tiles import wire
from firedancer_tpu.tiles.replay import ReplayTile, corpus_to_pool
from firedancer_tpu.tiles.sink import SinkTile
from firedancer_tpu.tiles.synth import make_txn_pool
from firedancer_tpu.waltz import pcap


def _write_corpus(path, n=32, seed=3):
    rows, szs, good = make_txn_pool(n, corrupt_frac=0.25, seed=seed)
    w = pcap.PcapWriter(path)
    tr = wire.parse_trailers(rows, szs.astype(np.int64))
    for i in range(n):
        # strip the trailer: the corpus carries raw wire txns
        raw = rows[i, : tr["txn_sz"][i]].tobytes()
        w.write(raw, ts_us=1000 * i)
    w.close()
    return good


def test_pcap_roundtrip(tmp_path):
    p = str(tmp_path / "c.pcap")
    payloads = [bytes([i]) * (i + 1) for i in range(5)]
    w = pcap.PcapWriter(p)
    for i, pl in enumerate(payloads):
        w.write(pl, ts_us=i * 7)
    w.close()
    got = pcap.read_udp_payloads(p)
    assert [g[1] for g in got] == payloads
    assert [g[0] for g in got] == [i * 7 for i in range(5)]


def test_corpus_pool_deterministic(tmp_path):
    p = str(tmp_path / "c.pcap")
    _write_corpus(p)
    r1, s1, t1 = corpus_to_pool(p)
    r2, s2, t2 = corpus_to_pool(p)
    assert (r1 == r2).all() and (s1 == s2).all() and (t1 == t2).all()
    assert len(r1) == 32  # corrupt sigs still parse (parse is not verify)


def _run_replay(path, total):
    replay = ReplayTile(path, total=total)
    sink = SinkTile(record=True)
    topo = Topology()
    topo.link("replay_sink", depth=256, mtu=wire.LINK_MTU)
    topo.tile(replay, outs=["replay_sink"])
    topo.tile(sink, ins=[("replay_sink", True)])
    topo.build()
    topo.start(batch_max=64)
    try:
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            if topo.metrics("sink").counter("sunk_frags") >= total:
                break
            time.sleep(0.01)
        topo.halt()
        with sink.lock:
            sigs = np.concatenate(sink.sigs)
            payloads = np.concatenate(sink.payloads)
        return sigs, payloads
    finally:
        topo.close()


def test_replay_bit_identical(tmp_path):
    p = str(tmp_path / "c.pcap")
    _write_corpus(p)
    total = 48  # corpus loops (32 entries -> 1.5 passes)
    s1, p1 = _run_replay(p, total)
    s2, p2 = _run_replay(p, total)
    assert (s1 == s2).all()
    assert (p1 == p2).all()
    # latency observability: the sink sampled tsorig->arrival deltas
