"""End-to-end ingress slice: synth → verify(TPU kernel) → dedup → sink.

The minimum end-to-end checkpoint from SURVEY.md §7: a replayed ingress
stream verified on the device, deduped, with metrics proving the counts.
Runs on the virtual CPU mesh in CI; the same topology runs unchanged on a
real chip (chip_smoke.py's ingress phase and the `ingress.flood` cell of
benchmark/run.py drive it there)."""

import time

import numpy as np

from firedancer_tpu.disco import Topology
from firedancer_tpu.tiles import wire
from firedancer_tpu.tiles.dedup import DedupTile
from firedancer_tpu.tiles.sink import SinkTile
from firedancer_tpu.tiles.synth import SynthTile, make_txn_pool
from firedancer_tpu.tiles.verify import VerifyTile
import pytest

pytestmark = pytest.mark.slow


def test_ingress_pipeline_end_to_end():
    pool_n, repeat = 24, 2
    total = pool_n * repeat
    rows, szs, good = make_txn_pool(pool_n, corrupt_frac=0.3, seed=17)
    n_good = int(good.sum())
    assert 0 < n_good < pool_n  # mix of valid and corrupted

    synth = SynthTile(rows, szs, total=total, repeat=repeat)
    # pre_dedup off: the 16-deep pre-tcache would swallow the back-to-back
    # repeats that the dedup-tile assertion below wants to see
    verify = VerifyTile(msg_width=256, max_lanes=32, pre_dedup=False)
    dedup = DedupTile(depth=1 << 12)
    sink = SinkTile(record=True)

    topo = Topology()
    topo.link("synth_verify", depth=256, mtu=wire.LINK_MTU)
    topo.link("verify_dedup", depth=256, mtu=wire.LINK_MTU)
    topo.link("dedup_sink", depth=256, mtu=wire.LINK_MTU)
    topo.tile(synth, outs=["synth_verify"])
    topo.tile(verify, ins=[("synth_verify", True)], outs=["verify_dedup"])
    topo.tile(dedup, ins=[("verify_dedup", True)], outs=["dedup_sink"])
    topo.tile(sink, ins=[("dedup_sink", True)])
    topo.build()
    topo.start(batch_max=32)
    try:
        deadline = time.monotonic() + 120.0
        want_dedup_in = n_good * repeat
        while time.monotonic() < deadline:
            topo.poll_failure()
            if (
                synth.sent >= total
                and topo.metrics("dedup").counter("in_frags") >= want_dedup_in
                and topo.metrics("sink").counter("sunk_frags") >= n_good
            ):
                break
            time.sleep(0.02)
        topo.halt()

        mv = topo.metrics("verify")
        md = topo.metrics("dedup")
        ms = topo.metrics("sink")
        # verify saw everything, failed exactly the corrupted txns
        assert mv.counter("in_frags") == total
        assert mv.counter("verify_fail_txns") == (pool_n - n_good) * repeat
        assert mv.counter("out_frags") == n_good * repeat
        # dedup dropped exactly the repeats
        assert md.counter("in_frags") == n_good * repeat
        assert md.counter("dup_txns") == n_good * (repeat - 1)
        assert ms.counter("sunk_frags") == n_good
        # survivor tags are exactly the good pool entries' tags
        sigs = sink.all_sigs()
        assert set(sigs.tolist()) == set(synth.tags[good].tolist())
        # payload integrity end to end: survivors byte-match the pool
        tag_to_pool = {int(t): i for i, t in enumerate(synth.tags)}
        with sink.lock:
            recorded = [
                (int(t), row)
                for sig_arr, rows_arr in zip(sink.sigs, sink.payloads)
                for t, row in zip(sig_arr, rows_arr)
            ]
        for t, row in recorded:
            i = tag_to_pool[t]
            assert (row[: szs[i]] == rows[i, : szs[i]]).all()
    finally:
        topo.close()


def test_verify_pre_dedup_with_duplicates():
    """Back-to-back duplicate sigs with pre_dedup=True: the tile must drop
    them via its 16-deep tcache and keep tsorig propagation consistent
    (regression: the keep-filter/tsorig index mismatch crashed here)."""
    pool_n, repeat = 6, 2
    rows, szs, good = make_txn_pool(pool_n, seed=37)
    synth = SynthTile(rows, szs, total=pool_n * repeat, repeat=repeat)
    verify = VerifyTile(msg_width=256, max_lanes=32, pre_dedup=True)
    sink = SinkTile()
    topo = Topology()
    topo.link("synth_verify", depth=64, mtu=wire.LINK_MTU)
    topo.link("verify_sink", depth=64, mtu=wire.LINK_MTU)
    topo.tile(synth, outs=["synth_verify"])
    topo.tile(verify, ins=[("synth_verify", True)], outs=["verify_sink"])
    topo.tile(sink, ins=[("verify_sink", True)])
    topo.build()
    topo.start(batch_max=pool_n * repeat)  # one batch: dups land together
    try:
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            if topo.metrics("sink").counter("sunk_frags") >= pool_n:
                break
            time.sleep(0.02)
        topo.halt()
        mv = topo.metrics("verify")
        assert mv.counter("dedup_drop_txns") == pool_n * (repeat - 1)
        assert topo.metrics("sink").counter("sunk_frags") == pool_n
    finally:
        topo.close()
