"""Horizontal verify scaling (seq round-robin across replicas), monitor
attach from the published workspace directory, and TOML config -> topology
(asked for by the round-1 review, items 8 and 9)."""

import time

import numpy as np
import pytest

from firedancer_tpu.app import config as C
from firedancer_tpu.app.monitor import Monitor
from firedancer_tpu.disco import Topology
from firedancer_tpu.tiles import wire
from firedancer_tpu.tiles.dedup import DedupTile
from firedancer_tpu.tiles.sink import SinkTile
from firedancer_tpu.tiles.synth import SynthTile, make_txn_pool
from firedancer_tpu.tiles.verify import VerifyTile

pytestmark = pytest.mark.slow


def test_two_verify_replicas_seq_sharded():
    """Interleaved seqs across two verify tiles cover the whole stream
    exactly once (fd_verify.c:46 round-robin)."""
    pool_n = 32
    rows, szs, good = make_txn_pool(pool_n, corrupt_frac=0.25, seed=23)
    n_good = int(good.sum())
    synth = SynthTile(rows, szs, total=pool_n)
    v0 = VerifyTile(msg_width=256, max_lanes=32, pre_dedup=False,
                    shard=(0, 2), name="verify0")
    v1 = VerifyTile(msg_width=256, max_lanes=32, pre_dedup=False,
                    shard=(1, 2), name="verify1")
    dedup = DedupTile(depth=1 << 10)
    sink = SinkTile(record=True)

    topo = Topology(name=f"shardtest_{int(time.time()*1e6) & 0xFFFFFF}")
    topo.link("synth_verify", depth=256, mtu=wire.LINK_MTU)
    topo.link("verify0_dedup", depth=256, mtu=wire.LINK_MTU)
    topo.link("verify1_dedup", depth=256, mtu=wire.LINK_MTU)
    topo.link("dedup_sink", depth=256, mtu=wire.LINK_MTU)
    topo.tile(synth, outs=["synth_verify"])
    topo.tile(v0, ins=[("synth_verify", True)], outs=["verify0_dedup"])
    topo.tile(v1, ins=[("synth_verify", True)], outs=["verify1_dedup"])
    topo.tile(
        dedup,
        ins=[("verify0_dedup", True), ("verify1_dedup", True)],
        outs=["dedup_sink"],
    )
    topo.tile(sink, ins=[("dedup_sink", True)])
    topo.build()
    topo.start(batch_max=16)
    try:
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            if topo.metrics("sink").counter("sunk_frags") >= n_good:
                break
            time.sleep(0.02)

        # both replicas did real, disjoint work covering everything
        m0, m1 = topo.metrics("verify0"), topo.metrics("verify1")
        out0, out1 = m0.counter("out_frags"), m1.counter("out_frags")
        assert out0 > 0 and out1 > 0
        assert out0 + out1 == n_good
        assert set(sink.all_sigs().tolist()) == set(
            synth.tags[good].tolist()
        )

        # ---- monitor attaches from ANOTHER workspace mapping ----
        mon = Monitor(topo.name)
        snap = mon.snapshot()
        assert snap["verify0"]["signal"] == "RUN"
        assert (
            snap["verify0"]["counters"]["out_frags"]
            + snap["verify1"]["counters"]["out_frags"]
            == n_good
        )
        # link fseqs visible too
        assert "synth_verify" in snap["_links"]
        # render produces a table without blowing up
        txt = mon.render(None, snap, 1.0)
        assert "verify0" in txt
        topo.halt()
    finally:
        topo.close()


def test_config_parse_and_topology():
    cfg = C.parse(
        """
name = "cfgtest"
[tiles.quic]
udp_port = 0
[tiles.verify]
count = 2
max_lanes = 64
msg_width = 256
[tiles.dedup]
signature_cache_size = 1024
[links]
depth = 128
"""
    )
    assert cfg.verify_count == 2 and cfg.dedup_depth == 1024
    topo, qt = C.build_ingress_topology(cfg, b"\x07" * 32)
    assert set(topo.tiles) == {
        "quic", "verify0", "verify1", "dedup", "sink"
    }
    # verify replicas are seq-sharded
    assert topo.tiles["verify0"].tile.shard == (0, 2)
    assert topo.tiles["verify1"].tile.shard == (1, 2)
    # dedup consumes both verify links
    assert len(topo.tiles["dedup"].ins) == 2
    del qt
