"""The verify pool's reorder and placement account (PR 29), against the
benchmark's plain reference, on the CPU with no JAX compile.

A seeded corpus from benchmark/lib/corpus.py (unique transfers, byte-for-
byte re-sends, corrupted copies) goes synth -> verify -> dedup -> sink
through a pool of four domains, the strict host verifier standing in for
each chip and domain 0 made slow, so that later batches land on the other
domains first and wait in `pool.reorder`.  What comes out has to be what
`lib/reference.outcome` says, in the order a pool of ONE gives, and the
pool has to say what it did: `reordered_batches`, `batch_reorder_us`,
`dev{i}_landed`.
"""

import os
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.lib import corpus as C  # noqa: E402
from benchmark.lib import reference  # noqa: E402
from firedancer_tpu.ballet import txn as T  # noqa: E402
from firedancer_tpu.disco import Topology  # noqa: E402
from firedancer_tpu.ops.ed25519 import hostpath  # noqa: E402
from firedancer_tpu.tiles import verify as VT  # noqa: E402
from firedancer_tpu.tiles import wire  # noqa: E402
from firedancer_tpu.tiles.dedup import DedupTile  # noqa: E402
from firedancer_tpu.tiles.sink import SinkTile  # noqa: E402
from firedancer_tpu.tiles.synth import SynthTile  # noqa: E402

SEED = (1 << 31) + 29
N_UNIQUE, LANES = 192, 8
#: seconds domain 0's stand-in chip takes longer than the others
SLOW_S = 0.02


@pytest.fixture(scope="module")
def corp():
    return C.make_corpus(N_UNIQUE, 16, 16, 64, SEED, workers=1)


def _frag_rows(send: np.ndarray):
    """The corpus's datagrams as quic publishes them: payload + trailer."""
    rows = np.zeros((len(send), wire.LINK_MTU), np.uint8)
    szs = np.zeros(len(send), np.uint16)
    for i, row in enumerate(send):
        payload = row.tobytes()
        full = wire.append_trailer(payload, T.parse(payload))
        rows[i, : len(full)] = np.frombuffer(full, np.uint8)
        szs[i] = len(full)
    return rows, szs


def _chip(i: int):
    def fn(digests, sigs, pubs):
        if i == 0:
            time.sleep(SLOW_S)
        return hostpath.verify_batch_digest_host(digests, sigs, pubs)

    return fn


def _run(monkeypatch, corp, width: int):
    """-> (tags in publish order, verify's counters and hists, dedup's)."""
    monkeypatch.setattr(
        VT.VerifyTile, "_make_device_fns",
        lambda self: [_chip(i) for i in range(self.n_devices)])
    rows, szs = _frag_rows(corp["send"])
    n = len(rows)
    synth = SynthTile(rows, szs, total=n)
    verify = VT.VerifyTile(msg_width=256, max_lanes=LANES, devices=width,
                           async_depth=2)
    assert verify.n_devices == width
    sink = SinkTile(record=True)
    topo = Topology()
    for link in ("synth_verify", "verify_dedup", "dedup_sink"):
        topo.link(link, depth=256, mtu=wire.LINK_MTU)
    topo.tile(synth, outs=["synth_verify"])
    topo.tile(verify, ins=[("synth_verify", True)], outs=["verify_dedup"])
    topo.tile(DedupTile(depth=1 << 12), ins=[("verify_dedup", True)],
              outs=["dedup_sink"])
    topo.tile(sink, ins=[("dedup_sink", True)])
    topo.build()
    topo.start(batch_max=LANES)
    try:
        mv, md = topo.metrics("verify"), topo.metrics("dedup")
        settled = lambda: (  # noqa: E731
            topo.metrics("sink").counter("sunk_frags")
            + mv.counter("verify_fail_txns") + mv.counter("dedup_drop_txns")
            + md.counter("dup_txns"))
        end = time.monotonic() + 120.0
        while settled() < n:
            topo.poll_failure()
            assert time.monotonic() < end, (settled(), n)
            time.sleep(0.01)
    finally:
        topo.halt()
    try:
        return sink.all_sigs().tolist(), mv.read(), md.read()
    finally:
        topo.close()


def test_a_pool_of_four_against_the_reference_and_a_pool_of_one(
        monkeypatch, corp):
    want = reference.outcome(corp, len(corp["send"]), balances=False)
    one, v1, d1 = _run(monkeypatch, corp, 1)
    four, v4, d4 = _run(monkeypatch, corp, 4)
    for tags, v, d in ((one, v1, d1), (four, v4, d4)):
        assert len(tags) == want["landed"]
        assert v["verify_fail_txns"] == want["rejected"]
        assert v["dedup_drop_txns"] + d["dup_txns"] == want["dups"]
        assert sorted(tags) == want["tags"].tolist()
        assert v["fallback_batches"] == v["device_errors"] == 0
        assert v["pool_resubmits"] == v["pool_late_results"] == 0
        # the account is whole: one reorder sample a batch, and every
        # batch under the device that landed it
        assert v[VT.REORDER_HIST]["count"] == v["device_batches"] > 0
        assert sum(v[k] for k in v if k.endswith("_landed")
                   ) == v["device_batches"]
    # the ORDER is width one's, whatever landed first
    assert four == one
    # width one: nothing ever waits for another device
    assert v1[VT.REORDER_COUNTER] == 0 and v1[VT.REORDER_HIST]["sum"] == 0
    assert v1["dev0_landed"] == v1["device_batches"]
    # width four, domain 0 slow: batches behind its seqs were parked (its
    # own never are), and every domain took work
    assert 0 < v4[VT.REORDER_COUNTER] < v4["device_batches"]
    # the wait is a part of land -> publish, never more than the whole
    assert 0 < v4[VT.REORDER_HIST]["sum"] <= v4["batch_drain_us"]["sum"]
    assert all(v4[f"dev{i}_landed"] > 0 for i in range(4)), v4


def test_a_batch_released_by_the_poll_that_took_it_waited_zero():
    """The pool alone: results taken in one poll share one stamp with
    their release; one left behind an earlier seq is `parked` and released
    on a later stamp."""
    pool = VT._DevicePool(
        [VT.DevicePolicy(None, None, index=i) for i in range(2)], depth=2)
    try:
        metas = [dict(lanes=1), dict(lanes=1), dict(lanes=1)]
        for seq, (m, dev) in enumerate(zip(metas, (0, 1, 1))):
            m["pool_seq"] = seq
            pool.outstanding[seq] = [m, (), "auto", dev]
        pool.next_seq = 3
        ok = np.ones(1, bool)
        # seq 1 lands on domain 1 while seq 0 is still out on domain 0
        pool.workers[1].results.append((metas[1], ok))
        pool.poll()
        assert not pool.ready and metas[1]["parked"]
        time.sleep(0.002)
        pool.workers[0].results.append((metas[0], ok))
        pool.workers[1].results.append((metas[2], ok))
        pool.poll()
        assert [m["pool_seq"] for m, _ in pool.ready] == [0, 1, 2]
        wait = [VT.ts_diff(m["t_rel"], m["t_taken"]) for m in metas]
        assert wait[0] == 0 and wait[2] == 0 and wait[1] >= 2000
        assert [m["parked"] for m in metas] == [False, True, False]
        assert [m["t_dev"] for m in metas] == [0, 1, 1]
        assert [w.landed_n for w in pool.workers] == [1, 2]
    finally:
        pool.stop(5.0)
