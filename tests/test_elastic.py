"""fdt_elastic tier-1 suite (ISSUE 14): SLO-driven runtime scaling and
live topology reconfiguration with zero-loss shard handover.

What is asserted, per the acceptance bar:

  * scale-out then scale-in of verify and bank shards under sustained
    traffic with ZERO lost and ZERO duplicated frags — digest-asserted
    stream parity against a static topology — on BOTH the thread and
    process runtimes x both stem modes;
  * rolling restart (and config reload) of a mid-pipeline tile under
    traffic meets the same bar;
  * a SIGKILL landing mid-drain recovers exactly-once (chaos layered on
    top of reconfiguration);
  * commanded operations never count toward the supervisor circuit
    breaker and classify as `reconfig:<op>` incident bundles;
  * the controller scales end to end: a queue-wait SLO burn fires
    scale-out (dwell-paced), sustained idle fires scale-in;
  * admission caps observably track the live verify shard count;
  * boot-manifest rewrites during reconfig are atomic (a concurrent
    reader never sees a torn manifest).

Process-runtime topologies are kept small (each child pays a fresh
interpreter import on this host) and traffic is paced so membership
changes overlap live frags even when a spawn takes tens of seconds.
"""

from __future__ import annotations

import json
import os
import signal
import threading
import time

import numpy as np
import pytest

from firedancer_tpu.disco import (
    ElasticConfig,
    ElasticController,
    ElasticKindConfig,
    FlightRecorder,
    Metrics,
    RestartPolicy,
    ShardMap,
    SloConfig,
    SloEngine,
    Supervisor,
    Topology,
)
from firedancer_tpu.disco.elastic import (
    SHARDMAP_FOOTPRINT,
    ElasticBinding,
    active_members,
)
from firedancer_tpu.disco.metrics import MetricsSchema
from firedancer_tpu.disco.mux import MuxCtx, Tile
from firedancer_tpu.tango import rings as R
from firedancer_tpu.tiles import wire
from firedancer_tpu.tiles.bank import BankTile
from firedancer_tpu.tiles.dedup import DedupTile
from firedancer_tpu.tiles.pack import PackTile
from firedancer_tpu.tiles.sink import SinkTile, read_siglog
from firedancer_tpu.tiles.synth import SynthTile, make_txn_pool
from firedancer_tpu.tiles.verify import VerifyTile
from firedancer_tpu.ops.ed25519 import hostpath


@pytest.fixture(autouse=True)
def _no_shm_leak(no_shm_leak):
    """Every test here runs under conftest's /dev/shm leak check."""
    yield


# ---------------------------------------------------------------------------
# units


def test_shardmap_assignment_unit():
    """Journal-resolved seq assignment: pure function of (seq, journal),
    wrap-safe, later entries shadow earlier ones."""
    smv = ShardMap(np.zeros(SHARDMAP_FOOTPRINT, np.uint8), join=False)
    smv.init_kind(0, 3, 0b001)
    assert smv.n_active(0) == 1 and smv.epoch(0) == 1
    # entry 0 (mask {0}) covers everything: member 0 owns every seq
    seqs = np.arange(16, dtype=np.uint64)
    assert smv.assign_mask(0, seqs, 0).all()
    assert not smv.assign_mask(0, seqs, 1).any()
    # flip to {0,1} effective at seq 8 (producer-side append)
    ep = smv.flip(0, 0b011)
    smv.append_flip(0, 8, 0b011)
    smv.set_producer_ack(0, ep)
    m0 = smv.assign_mask(0, seqs, 0)
    m1 = smv.assign_mask(0, seqs, 1)
    # pre-boundary seqs: all member 0; post-boundary: round-robin of
    # the sorted active list [0, 1]
    assert m0[:8].all() and not m1[:8].any()
    for s in range(8, 16):
        want = active_members(0b011)[s % 2]
        assert bool(m0[s]) == (want == 0)
        assert bool(m1[s]) == (want == 1)
    # exactly-one-owner invariant across the flip
    assert ((m0.astype(int) + m1.astype(int)) == 1).all()
    # wrap boundary: entries + seqs straddling 2^64
    smv2 = ShardMap(np.zeros(SHARDMAP_FOOTPRINT, np.uint8), join=False)
    smv2.init_kind(0, 2, 0b11)
    smv2.flip(0, 0b01)
    wrap = (1 << 64) - 2
    smv2.append_flip(0, wrap, 0b01)
    ws = np.array(
        [wrap - 2, wrap - 1, wrap, (wrap + 3) % (1 << 64)], np.uint64
    )
    a0 = smv2.assign_mask(0, ws, 0)
    a1 = smv2.assign_mask(0, ws, 1)
    # past the wrap boundary only member 0 owns seqs
    assert bool(a0[2]) and bool(a0[3])
    assert not a1[2] and not a1[3]
    assert ((a0.astype(int) + a1.astype(int)) == 1).all()
    assert smv2.member_past_flip(0, 1, (wrap + 1) % (1 << 64))
    assert not smv2.member_past_flip(0, 1, wrap - 1)
    # journal RING wrap: more lifetime flips than retained entries —
    # the tagged entries keep the retained window consistent (oldest
    # first, append-ordered) and the newest entry governs new seqs
    smv3 = ShardMap(np.zeros(SHARDMAP_FOOTPRINT, np.uint8), join=False)
    smv3.init_kind(0, 2, 0b11)
    for k in range(12):
        mask = 0b01 if k % 2 == 0 else 0b11
        smv3.flip(0, mask)
        smv3.append_flip(0, 100 * (k + 1), mask)
    starts, masks = smv3.journal(0)
    assert len(starts) == 8
    assert [int(s) for s in starts] == [100 * j for j in range(5, 13)]
    late = np.array([1201, 1202], np.uint64)
    a0 = smv3.assign_mask(0, late, 0)
    a1 = smv3.assign_mask(0, late, 1)
    assert ((a0.astype(int) + a1.astype(int)) == 1).all()
    assert smv3.jlen(0) == 13


def test_admission_autosize_unit():
    from firedancer_tpu.waltz.admission import AdmissionConfig

    cfg = AdmissionConfig(max_conns=1000, backlog_cap=800, txn_rate=50)
    up = cfg.autosized(4, 2)
    assert up.max_conns == 2000 and up.backlog_cap == 1600
    assert up.txn_rate == 50  # rate knobs are per-source, not capacity
    down = cfg.autosized(1, 2)
    assert down.max_conns == 500 and down.backlog_cap == 400
    assert cfg.autosized(2, 2) is cfg


def test_slo_queue_wait_objective():
    """The new capacity-signal SLO: qwait hists merge across every hop
    and burn like the other latency objectives."""
    from firedancer_tpu.disco.metrics import HIST_BUCKETS

    cfg = SloConfig(
        queue_wait_p99_us=4.0, budget=0.01,
        fast_window_s=10.0, slow_window_s=10.0,
        burn_fast=1.0, burn_slow=1.0,
    )
    assert "queue_wait_p99_us" in cfg.asserted()
    eng = SloEngine(cfg, {})
    hist0 = {"count": 0, "sum": 0, "buckets": [0] * HIST_BUCKETS}
    # every sample lands in bucket 6 (~64us >> the 4us ceiling)
    bad = [0] * HIST_BUCKETS
    bad[6] = 1000
    hist1 = {"count": 1000, "sum": 64000, "buckets": bad}
    eng.observe({"relay": {"counters": {}, "lat_hists": {"qwait_us_a": hist0}}}, now=0.0)
    eng.observe({"relay": {"counters": {}, "lat_hists": {"qwait_us_a": hist1}}}, now=1.0)
    sts = {s.name: s for s in eng.evaluate(now=1.0)}
    st = sts["queue_wait_p99_us"]
    assert st.burn_fast >= 1.0 and st.breached
    # an unobservable ceiling is rejected loudly (the bound moved to
    # the wide-hist domain end with ISSUE 15's link-hist widening)
    SloConfig(queue_wait_p99_us=float(1 << 20)).validate()  # now fine
    with pytest.raises(ValueError, match="unobservable"):
        SloConfig(queue_wait_p99_us=float(1 << 25)).validate()


def test_stem_epoch_handback_unit():
    """The native stem's burst-boundary epoch check: a moved shard-map
    epoch word hands the whole burst back UNCONSUMED."""
    from firedancer_tpu.disco.mux import InLink, OutLink

    w = R.Workspace(1 << 20)
    mc_in = R.MCache.create(w, "mi", 64)
    dc_in = R.DCache.create(w, "di", mtu=256, depth=64)
    fs = R.FSeq.create(w, "fs", 0)
    mc_out = R.MCache.create(w, "mo", 64)
    dc_out = R.DCache.create(w, "do", mtu=256, depth=64)
    tc_mem = np.zeros(
        R.TCache.footprint(256, R.TCache.map_cnt_for(256)), np.uint8
    )
    tc = R.TCache(tc_mem, 256, R.TCache.map_cnt_for(256))
    isdup = np.zeros(64, np.uint8)
    tags = np.zeros(64, np.uint64)
    args = np.zeros(8, np.uint64)
    args[0] = tc.mem.ctypes.data
    args[3] = isdup.ctypes.data
    args[4] = tags.ctypes.data
    spec = R.StemSpec(
        R.STEM_H_DEDUP, args, counters=("dup_txns",),
        keepalive=(tc_mem, isdup, tags, args), cap=64,
    )
    il = InLink("in", mc_in, dc_in, fs)
    ol = OutLink("out", mc_out, dc_out, [])
    stem = R.Stem([il], [ol], spec, cap=64)
    epoch = np.zeros(1, np.uint64)
    epoch[0] = 7
    stem.watch_epoch(epoch, 7)
    # publish two frags; epoch unchanged -> consumed normally
    for k in range(2):
        chunk = dc_in.write(np.full(16, k, np.uint8))
        mc_in.publish(seq=k, sig=100 + k, chunk=chunk, sz=16)
    n, stat, s_in = stem.run(64, 0)
    assert n == 2 and stat in (R.STEM_IDLE, R.STEM_BUDGET)
    # epoch moves -> the next burst consumes NOTHING and names the
    # epoch sentinel
    chunk = dc_in.write(np.full(16, 9, np.uint8))
    mc_in.publish(seq=2, sig=109, chunk=chunk, sz=16)
    epoch[0] = 8
    n, stat, s_in = stem.run(64, 0)
    assert n == 0
    assert stat == R.STEM_PYTHON and s_in == R.STEM_IN_EPOCH
    assert il.seq == 2, "epoch handback must not consume"
    # host re-reads the map, updates SEEN -> the burst proceeds
    stem.set_epoch_seen(8)
    n, stat, s_in = stem.run(64, 0)
    assert n == 1


def test_fdtincident_reconfig_classification():
    from scripts.fdtincident import classify_bundle

    row = classify_bundle(
        {
            "id": "x-0001-reconfig",
            "trigger": {
                "kind": "reconfig",
                "tile": "verify1",
                "detail": {"op": "scale-out:verify", "member": 1},
            },
        }
    )
    assert row["class"] == "reconfig:scale-out:verify"
    assert row["explained"]


def test_quic_admission_autosize_tracks_shards():
    """The quic tile's ConnAdmission caps scale with the live verify
    shard count on every epoch flip (ROADMAP item 3 leftover)."""
    from firedancer_tpu.tiles.quic import QuicIngressTile
    from firedancer_tpu.waltz.admission import AdmissionConfig

    qt = QuicIngressTile(
        bytes(32),
        admission=AdmissionConfig(max_conns=100, backlog_cap=200),
    )
    qt.elastic = ElasticBinding(
        "verify", 0, "producer", link="quic_verify", base_active=2
    )
    ctx = MuxCtx(
        "quic",
        R.CNC(np.zeros(R.CNC.footprint(), np.uint8)),
        [],
        [],
        Metrics(
            np.zeros(Metrics.footprint(qt.schema.with_base()), np.uint8),
            qt.schema.with_base(),
        ),
    )
    try:
        qt.on_boot(ctx)
        smv = qt.elastic.bind(ctx)
        smv.init_kind(0, 4, 0b0011)
        qt.on_epoch(ctx)
        assert qt.admission_cfg.max_conns == 100
        smv.flip(0, 0b1111)  # 2 -> 4 shards
        qt.on_epoch(ctx)
        assert qt.admission_cfg.max_conns == 200
        assert qt.admission_cfg.backlog_cap == 400
        assert qt.server.max_conns == 200
        assert ctx.metrics.counter("adm_autosize") == 1
        assert ctx.metrics.counter("elastic_verify_shards") == 4
        smv.flip(0, 0b0001)  # down to 1
        qt.on_epoch(ctx)
        assert qt.admission_cfg.max_conns == 50
        assert ctx.metrics.counter("adm_max_conns") == 50
    finally:
        qt.on_halt(ctx)


# ---------------------------------------------------------------------------
# pipeline harnesses


def _verify_topo(name, runtime, stem, pool, total, repeat, *, active=1,
                 provision=3, elastic=True, shard_static=False):
    rows, szs = pool
    topo = Topology(name=name, runtime=runtime, stem=stem)
    topo.link("synth_verify", depth=256, mtu=wire.LINK_MTU)
    for i in range(provision):
        topo.link(f"verify{i}_dedup", depth=256, mtu=wire.LINK_MTU)
    topo.link("dedup_sink", depth=256, mtu=wire.LINK_MTU)
    synth = SynthTile(rows, szs, total=total, repeat=repeat)
    topo.tile(synth, outs=["synth_verify"])
    for i in range(provision):
        topo.tile(
            VerifyTile(
                msg_width=256, max_lanes=32, pre_dedup=False,
                device="off",
                shard=(i, provision) if shard_static else None,
                name=f"verify{i}",
            ),
            ins=[("synth_verify", True)], outs=[f"verify{i}_dedup"],
        )
    topo.tile(
        DedupTile(depth=1 << 12),
        ins=[(f"verify{i}_dedup", True) for i in range(provision)],
        outs=["dedup_sink"],
    )
    topo.tile(SinkTile(shm_log=4 * total), ins=[("dedup_sink", True)])
    if elastic:
        topo.declare_shards(
            "verify", [f"verify{i}" for i in range(provision)],
            producer="synth", producer_link="synth_verify", active=active,
        )
    return topo, synth


def _static_digest(pool_n, seed):
    """The parity baseline: the SAME pool through a static 3-shard
    topology (boot-frozen seq filter); returns the sunk sig set."""
    rows, szs, _ = make_txn_pool(pool_n, seed=seed)
    topo, synth = _verify_topo(
        None, "thread", "python", (rows, szs), pool_n * 2, 2,
        elastic=False, shard_static=True,
    )
    topo.build()
    topo.start(batch_max=32)
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            sigs = read_siglog(topo.tile_alloc_view("sink", "siglog"))
            if len(set(sigs.tolist())) >= pool_n:
                break
            topo.poll_failure()
            time.sleep(0.02)
        sigs = read_siglog(topo.tile_alloc_view("sink", "siglog"))
        topo.halt()
        assert len(sigs) == len(set(sigs.tolist()))
        return set(sigs.tolist())
    finally:
        topo.close()


_VERIFY_PARAMS = [
    ("thread", "python"),
    ("thread", "native"),
    ("process", "python"),
    ("process", "native"),
]


@pytest.mark.parametrize(
    "runtime,stem", _VERIFY_PARAMS,
    ids=[f"{r}-{s}" for r, s in _VERIFY_PARAMS],
)
def test_verify_scale_out_in_zero_loss(runtime, stem):
    """Scale a verify shard OUT then IN under sustained traffic: zero
    lost, zero duplicated frags, digest parity with a static topology,
    the new member demonstrably sharing the load, and the retiring
    member's drained marker honored before the reap."""
    pool_n, repeat, seed = 384, 2, 5
    rows, szs, _ = make_txn_pool(pool_n, seed=seed)
    total = pool_n * repeat
    topo, synth = _verify_topo(
        f"tev{os.getpid()}_{runtime[:1]}{stem[:1]}", runtime, stem,
        (rows, szs), total, repeat,
    )
    topo.build()
    topo.start(batch_max=32, boot_timeout_s=300.0)
    try:
        ms = topo.metrics("sink")
        deadline = time.monotonic() + 120
        while ms.counter("in_frags") < pool_n // 8 and (
            time.monotonic() < deadline
        ):
            topo.poll_failure()
            time.sleep(0.01)
        i = topo.add_shard("verify")
        assert i == 1
        smv = topo.shardmap()
        assert smv.n_active(0) == 2
        while ms.counter("in_frags") < pool_n // 2 and (
            time.monotonic() < deadline
        ):
            topo.poll_failure()
            time.sleep(0.01)
        topo.retire_shard("verify", i, timeout_s=120.0)
        ep = smv.epoch(0)
        assert smv.drained(0, i) >= ep - 1, "reaped before drained"
        assert not topo.tiles["verify1"].active
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            sigs = read_siglog(topo.tile_alloc_view("sink", "siglog"))
            if len(set(sigs.tolist())) >= pool_n:
                break
            topo.poll_failure()
            time.sleep(0.05)
        sigs = read_siglog(topo.tile_alloc_view("sink", "siglog"))
        uniq = set(sigs.tolist())
        assert len(uniq) == pool_n, f"lost {pool_n - len(uniq)} frags"
        assert len(sigs) == len(uniq), "duplicated frags past dedup"
        assert uniq <= set(synth.tags.tolist())
        # the scaled-out member genuinely shared the stream
        assert topo.metrics("verify1").counter("out_frags") > 0
        # monitor surface: live elastic rows from a fresh attach
        if runtime == "thread":
            from firedancer_tpu.app.monitor import Monitor

            mon = Monitor(topo.name)
            snap = mon.snapshot()
            assert snap["_elastic"]["verify_shards"] == 1
            assert snap["_elastic"]["verify_epoch"] == smv.epoch(0)
            assert any(
                "elastic verify:" in ln
                for ln in mon.render(None, snap, 1.0).splitlines()
            )
        topo.halt()
    finally:
        topo.close()
    # digest parity: the elastic run's survivor DIGEST equals a static
    # topology's over the same pool
    assert uniq == _static_digest(pool_n, seed)


class MbCollectTile(Tile):
    """Decodes bank->poh microblocks and logs every txn's dedup tag to
    a shm log — the exactly-once surface for the bank-shard tests."""

    name = "collect"
    schema = MetricsSchema(counters=("mbs", "txns"))

    def __init__(self, cap: int, name: str = "collect"):
        self.name = name
        self.cap = cap
        self._log = None

    def wksp_footprint(self) -> int:
        return 8 * (1 + self.cap)

    def on_boot(self, ctx: MuxCtx) -> None:
        mem = ctx.alloc("taglog", 8 * (1 + self.cap))
        self._log = mem[: (len(mem) // 8) * 8].view(np.uint64)

    def on_frags(self, ctx: MuxCtx, in_idx: int, frags: np.ndarray) -> None:
        il = ctx.ins[in_idx]
        rows = il.gather(frags)
        tags = []
        for i in range(len(rows)):
            buf = rows[i, : frags["sz"][i]]
            n = int(buf[6:8].view("<u2")[0])
            off = 8
            for _ in range(n):
                sz = int(buf[off : off + 2].view("<u2")[0])
                t = buf[off + 2 : off + 2 + sz]
                tags.append(int(t[1:9].view("<u8")[0]))
                off += 2 + sz
            ctx.metrics.inc("mbs")
        if tags:
            w = self._log
            cur = int(w[0])
            keep = tags[: max(self.cap - cur, 0)]
            if keep:
                w[1 + cur : 1 + cur + len(keep)] = np.array(
                    keep, np.uint64
                )
            w[0] = np.uint64(cur + len(tags))
            ctx.metrics.inc("txns", len(tags))


def _read_taglog(mem):
    w = mem[: (len(mem) // 8) * 8].view(np.uint64)
    n = min(int(w[0]), len(w) - 1)
    return w[1 : 1 + n].copy()


_BANK_PARAMS = [
    ("thread", "python"),
    ("thread", "native"),
    ("process", "python"),
    ("process", "native"),
]


@pytest.mark.parametrize(
    "runtime,stem", _BANK_PARAMS,
    ids=[f"{r}-{s}" for r, s in _BANK_PARAMS],
)
def test_bank_scale_out_in_exactly_once(runtime, stem):
    """Bank shards scale under a live pack scheduler: the mask gates
    scheduling (native hook included, via the stem's epoch handback),
    the retiring bank drains and is reaped, and every txn executes
    EXACTLY once across both flips."""
    # pace pack so membership changes overlap live traffic even when a
    # process spawn takes tens of seconds on this host
    if runtime == "process":
        pool_n, cadence_ns = 448, 400_000_000
    else:
        pool_n, cadence_ns = 768, 10_000_000
    rows, szs, _ = make_txn_pool(pool_n, seed=9)
    n_banks = 3
    topo = Topology(
        name=f"teb{os.getpid()}_{runtime[:1]}{stem[:1]}",
        runtime=runtime, stem=stem,
    )
    topo.link("synth_pack", depth=256, mtu=wire.LINK_MTU)
    for i in range(n_banks):
        topo.link(f"pack_bank{i}", depth=128, mtu=65_535)
        topo.link(f"bank{i}_pack", depth=128)
        topo.link(f"bank{i}_poh", depth=128, mtu=65_535)
    synth = SynthTile(rows, szs, total=pool_n)
    topo.tile(synth, outs=["synth_pack"])
    topo.tile(
        PackTile(
            n_banks, mb_inflight=2, microblock_ns=cadence_ns,
            txn_limit=8,
        ),
        ins=[("synth_pack", True)]
        + [(f"bank{i}_pack", True) for i in range(n_banks)],
        outs=[f"pack_bank{i}" for i in range(n_banks)],
    )
    for i in range(n_banks):
        topo.tile(
            BankTile(i, funk=None, native=False),
            ins=[(f"pack_bank{i}", True)],
            outs=[f"bank{i}_pack", f"bank{i}_poh"],
        )
    topo.tile(
        MbCollectTile(cap=8 * pool_n),
        ins=[(f"bank{i}_poh", True) for i in range(n_banks)],
    )
    topo.declare_shards(
        "bank", [f"bank{i}" for i in range(n_banks)], producer="pack",
        member_links=[f"pack_bank{i}" for i in range(n_banks)], active=2,
    )
    topo.build()
    topo.start(batch_max=32, boot_timeout_s=300.0)
    try:
        mc = topo.metrics("collect")
        deadline = time.monotonic() + 120
        while mc.counter("txns") < pool_n // 8 and (
            time.monotonic() < deadline
        ):
            topo.poll_failure()
            time.sleep(0.01)
        i = topo.add_shard("bank")
        assert i == 2
        # under live scheduling, retire bank 1: pack must stop
        # assigning at the flip (both loop modes), bank 1 must flush
        # and mark drained before the reap
        deadline2 = time.monotonic() + 180
        while topo.metrics("bank2").counter("in_frags") == 0 and (
            time.monotonic() < deadline2
        ):
            topo.poll_failure()
            time.sleep(0.02)
        assert topo.metrics("bank2").counter("in_frags") > 0, (
            "scaled-out bank never scheduled"
        )
        topo.retire_shard("bank", 1, timeout_s=180.0)
        assert not topo.tiles["bank1"].active
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            tags = _read_taglog(topo.tile_alloc_view("collect", "taglog"))
            if len(set(tags.tolist())) >= pool_n:
                break
            topo.poll_failure()
            time.sleep(0.05)
        tags = _read_taglog(topo.tile_alloc_view("collect", "taglog"))
        uniq = set(tags.tolist())
        assert len(uniq) == pool_n, f"lost {pool_n - len(uniq)} txns"
        assert len(tags) == len(uniq), "txn executed twice"
        assert uniq == set(synth.tags.tolist())
        topo.halt()
    finally:
        topo.close()


@pytest.mark.parametrize("runtime", ["thread", "process"])
def test_rolling_restart_under_traffic(runtime):
    """Deliberate restart of the mid-pipeline dedup tile while frags
    flow: drain -> respawn-with-new-config -> rejoin, exactly-once (the
    surviving tcache collapses the replay), and the config mutation is
    visible on the respawned incarnation."""
    pool_n, repeat, seed = 384, 3, 17
    rows, szs, _ = make_txn_pool(pool_n, seed=seed)
    total = pool_n * repeat
    topo, synth = _verify_topo(
        f"ter{os.getpid()}_{runtime[:1]}", runtime, "python",
        (rows, szs), total, repeat, active=1, provision=2,
    )
    topo.build()
    topo.start(batch_max=16, boot_timeout_s=300.0)
    try:
        ms = topo.metrics("sink")
        deadline = time.monotonic() + 120
        while ms.counter("in_frags") < pool_n // 8 and (
            time.monotonic() < deadline
        ):
            topo.poll_failure()
            time.sleep(0.01)
        inc0 = topo.tiles["dedup"].ctx.incarnation
        marker = {"applied": False}

        def _mutate(tile):
            # config reload: the mutation rides the respawn (pickled
            # into the new child under the process runtime)
            tile.name = tile.name  # no-op touch
            marker["applied"] = True

        topo.rolling_restart("dedup", mutate=_mutate, replay=256)
        assert marker["applied"]
        assert topo.tiles["dedup"].ctx.incarnation == inc0 + 1
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            sigs = read_siglog(topo.tile_alloc_view("sink", "siglog"))
            if len(set(sigs.tolist())) >= pool_n:
                break
            topo.poll_failure()
            time.sleep(0.05)
        sigs = read_siglog(topo.tile_alloc_view("sink", "siglog"))
        uniq = set(sigs.tolist())
        assert len(uniq) == pool_n, f"lost {pool_n - len(uniq)} frags"
        assert len(sigs) == len(uniq), "duplicated frags past dedup"
        topo.halt()
    finally:
        topo.close()


def _slow_verify(digests, sigs, pubs):
    """Module-level slow device stub (spawn-picklable): keeps verify
    work in flight long enough for a SIGKILL to land mid-drain."""
    time.sleep(0.25)
    return hostpath.verify_batch_digest_host(digests, sigs, pubs)


def test_sigkill_mid_drain_recovers_exactly_once():
    """Chaos layered on reconfig: a SIGKILL lands on the retiring
    member while its drain is pending — the retire loop revives it
    through the ordinary rejoin path, the drain completes, and the
    stream stays exactly-once."""
    pool_n, repeat, seed = 256, 2, 21
    rows, szs, _ = make_txn_pool(pool_n, seed=seed)
    total = pool_n * repeat
    topo = Topology(name=f"tek{os.getpid()}", runtime="process")
    topo.link("synth_verify", depth=256, mtu=wire.LINK_MTU)
    for i in range(2):
        topo.link(f"verify{i}_dedup", depth=256, mtu=wire.LINK_MTU)
    topo.link("dedup_sink", depth=256, mtu=wire.LINK_MTU)
    synth = SynthTile(rows, szs, total=total, repeat=repeat)
    topo.tile(synth, outs=["synth_verify"])
    for i in range(2):
        topo.tile(
            VerifyTile(
                msg_width=256, max_lanes=32, pre_dedup=False,
                device="off", device_fn=_slow_verify, async_depth=2,
                name=f"verify{i}",
            ),
            ins=[("synth_verify", True)], outs=[f"verify{i}_dedup"],
        )
    topo.tile(
        DedupTile(depth=1 << 12),
        ins=[(f"verify{i}_dedup", True) for i in range(2)],
        outs=["dedup_sink"],
    )
    topo.tile(SinkTile(shm_log=4 * total), ins=[("dedup_sink", True)])
    topo.declare_shards(
        "verify", ["verify0", "verify1"], producer="synth",
        producer_link="synth_verify", active=2,
    )
    topo.build()
    topo.start(batch_max=16, boot_timeout_s=300.0)
    try:
        ms = topo.metrics("sink")
        deadline = time.monotonic() + 120
        while ms.counter("in_frags") < pool_n // 8 and (
            time.monotonic() < deadline
        ):
            topo.poll_failure()
            time.sleep(0.01)
        # fire the kill from a side thread shortly after the flip, while
        # the slow device stub still holds verify1's work in flight
        pid0 = topo.tile_pid("verify1")
        killed = {}

        def _kill():
            time.sleep(0.15)
            try:
                os.kill(pid0, signal.SIGKILL)
                killed["pid"] = pid0
            except OSError as e:  # pragma: no cover — diagnosing only
                killed["err"] = e

        t = threading.Thread(target=_kill)
        t.start()
        topo.retire_shard("verify", 1, timeout_s=240.0, replay=256)
        t.join()
        assert killed.get("pid") == pid0, f"kill failed: {killed}"
        smv = topo.shardmap()
        assert smv.drained(0, 1) >= smv.epoch(0)
        assert topo.metrics("verify1").counter("restarts") >= 1, (
            "the mid-drain kill was never repaired by the retire loop"
        )
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            sigs = read_siglog(topo.tile_alloc_view("sink", "siglog"))
            if len(set(sigs.tolist())) >= pool_n:
                break
            topo.poll_failure()
            time.sleep(0.05)
        sigs = read_siglog(topo.tile_alloc_view("sink", "siglog"))
        uniq = set(sigs.tolist())
        assert len(uniq) == pool_n, f"lost {pool_n - len(uniq)} frags"
        assert len(sigs) == len(uniq), "duplicated frags past dedup"
        topo.halt()
    finally:
        topo.close()


# ---------------------------------------------------------------------------
# supervisor + controller


def test_commanded_restart_not_counted():
    """Satellite: a deliberate drain/respawn must not count toward the
    circuit breaker or escalate backoff, and its flight bundle must
    classify as reconfig:<op> rather than a crash incident."""
    import shutil
    import tempfile

    pool_n, repeat = 256, 4
    rows, szs, _ = make_txn_pool(pool_n, seed=29)
    topo, synth = _verify_topo(
        None, "thread", "python", (rows, szs), pool_n * repeat, repeat,
        active=1, provision=2,
    )
    topo.build()
    # breaker_n=2: three commanded restarts WOULD trip it if they were
    # miscounted as crashes
    sup = Supervisor(
        topo, RestartPolicy(hb_timeout_s=5.0, breaker_n=2)
    )
    inc_dir = tempfile.mkdtemp(prefix="fdt_elastic_inc_")
    flight = FlightRecorder(topo, inc_dir)
    flight.attach_supervisor(sup)
    ctl = ElasticController(topo, ElasticConfig(kinds={}), sup=sup)
    sup.start(batch_max=16)
    flight.start()
    try:
        for _ in range(3):
            ctl.rolling_restart("dedup", replay=256)
        time.sleep(0.3)  # let the watcher drain the pending events
    finally:
        flight.stop()
        sup.halt()
    try:
        assert sup.restarts("dedup") == 0, "commanded op counted as crash"
        assert sup.degraded("dedup") is None, "breaker tripped"
        assert sup._state["dedup"].backoff_s == 0.0
        from scripts.fdtincident import classify_dir

        rows_ = classify_dir(inc_dir)
        rr = [
            r for r in rows_ if r["class"] == "reconfig:rolling-restart"
        ]
        assert len(rr) == 3, rows_
        assert all(r["explained"] for r in rows_)
    finally:
        topo.close()
        shutil.rmtree(inc_dir, ignore_errors=True)


def test_controller_scales_on_burn_and_idle():
    """Controller-driven scaling end to end: an injected load step
    burns the queue-wait SLO -> scale-out fires (dwell-paced,
    classified reconfig); load removal -> scale-in drains and reaps."""
    import shutil
    import tempfile

    pool_n, repeat = 512, 3
    rows, szs, _ = make_txn_pool(pool_n, seed=3)
    topo, synth = _verify_topo(
        f"tec{os.getpid()}", "thread", "python",
        (rows, szs), pool_n * repeat, repeat, active=1, provision=3,
    )
    topo.build()
    from firedancer_tpu.disco.flight import tile_links

    # a 2us queue-wait ceiling burns under ANY real load: the traffic
    # itself is the injected load step; traffic end is its removal
    slo = SloEngine(
        SloConfig(
            queue_wait_p99_us=2.0, budget=0.01,
            fast_window_s=0.3, slow_window_s=0.6,
            burn_fast=1.0, burn_slow=1.0,
        ),
        tile_links(topo),
    )
    sup = Supervisor(topo, RestartPolicy(hb_timeout_s=5.0, breaker_n=3))
    inc_dir = tempfile.mkdtemp(prefix="fdt_elastic_ctl_")
    flight = FlightRecorder(topo, inc_dir)
    flight.attach_supervisor(sup)
    dwell_s = 0.5
    ctl = ElasticController(
        topo,
        ElasticConfig(
            kinds={
                "verify": ElasticKindConfig(
                    min_shards=1, max_shards=3, scale_out_burn=1.0,
                    scale_in_idle_tps=5.0, idle_for_s=0.5,
                )
            },
            dwell_s=dwell_s, poll_s=0.05,
        ),
        sup=sup, slo=slo,
    )
    sup.start(batch_max=32)
    flight.start()
    ctl.start()
    try:
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            if topo.shardmap().n_active(0) >= 2:
                break
            time.sleep(0.05)
        assert topo.shardmap().n_active(0) >= 2, "scale-out never fired"
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if (
                topo.metrics("sink").counter("in_frags") >= pool_n
                and topo.shardmap().n_active(0) == 1
            ):
                break
            time.sleep(0.05)
        assert topo.shardmap().n_active(0) == 1, "scale-in never fired"
    finally:
        ctl.stop()
        flight.stop()
        sup.halt()
    try:
        sigs = read_siglog(topo.tile_alloc_view("sink", "siglog"))
        uniq = set(sigs.tolist())
        assert len(uniq) == pool_n and len(sigs) == len(uniq)
        # commanded ops: nothing counted as a crash
        assert all(sup.restarts(n) == 0 for n in topo.tiles)
        # dwell pacing: consecutive ops at least dwell_s apart
        ts = [o["t"] for o in ctl.ops]
        assert all(b - a >= dwell_s * 0.9 for a, b in zip(ts, ts[1:])), (
            ctl.ops
        )
        from scripts.fdtincident import classify_dir

        rows_ = classify_dir(inc_dir)
        assert any(
            r["class"].startswith("reconfig:scale-out") for r in rows_
        )
        assert any(
            r["class"].startswith("reconfig:scale-in") for r in rows_
        )
        assert all(r["explained"] for r in rows_)
        # the gauge region recorded the history
        m = topo._metrics["elastic"]
        assert m.counter("reconfigs") >= 2
    finally:
        topo.close()
        shutil.rmtree(inc_dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# manifest atomicity (satellite)


def test_manifest_atomic_under_reconfig():
    """Boot-manifest rewrites during reconfig are atomic-rename writes:
    a reader loop (a child booting mid-reconfig, a monitor attaching)
    must never observe a torn or half-written manifest."""
    pool_n = 64
    rows, szs, _ = make_txn_pool(pool_n, seed=41)
    topo, synth = _verify_topo(
        f"tem{os.getpid()}", "thread", "python",
        (rows, szs), pool_n, 1, active=1, provision=3,
    )
    topo.build()
    topo.start(batch_max=32)
    dir_path = f"/dev/shm/fdt_wksp_{topo.name}.dir"
    stop = threading.Event()
    errors: list = []
    reads = [0]

    def _reader():
        while not stop.is_set():
            try:
                with open(dir_path) as f:
                    doc = json.load(f)
                # a complete doc always carries the elastic section
                assert "elastic" in doc["extra"]
                assert "verify" in doc["extra"]["elastic"]["kinds"]
                reads[0] += 1
            except Exception as e:  # noqa: BLE001 — the assertion target
                errors.append(repr(e))
                return

    readers = [threading.Thread(target=_reader) for _ in range(2)]
    for t in readers:
        t.start()
    try:
        for _ in range(8):
            i = topo.add_shard("verify")
            topo.retire_shard("verify", i, timeout_s=60.0)
        # manifest reflects the final membership
        with open(dir_path) as f:
            doc = json.load(f)
        kinds = doc["extra"]["elastic"]["kinds"]["verify"]
        assert kinds["active"] == ["verify0"]
        assert kinds["epoch"] == topo.shardmap().epoch(0)
    finally:
        stop.set()
        for t in readers:
            t.join()
        topo.halt()
        topo.close()
    assert not errors, f"torn manifest read: {errors[:3]}"
    assert reads[0] > 0


# ---------------------------------------------------------------------------
# shard-count-aware device rebalancing (fdt_upgrade satellite)


def test_device_partition_unit():
    """device_partition is the runtime restatement of the boot-time
    assignment: rank-strided over the LIVE active set, disjoint cover,
    modulo sharing when devices are scarce, empty for inactive."""
    from firedancer_tpu.disco.elastic import device_partition

    universe = [0, 1, 2, 3]
    # sole member owns the whole universe; inactive members own nothing
    assert device_partition(universe, 0b001, 0) == [0, 1, 2, 3]
    assert device_partition(universe, 0b001, 1) == []
    # scale-out to two: the spare RECRUITS the ordinals the incumbent
    # releases (strided, so each member keeps a spread of devices)
    assert device_partition(universe, 0b011, 0) == [0, 2]
    assert device_partition(universe, 0b011, 1) == [1, 3]
    # holes in the mask: ranks follow the sorted active list
    assert device_partition(universe, 0b101, 2) == [1, 3]
    # any mask covers the universe disjointly
    parts = [device_partition(universe, 0b111, i) for i in range(3)]
    flat = sorted(x for p in parts for x in p)
    assert flat == universe
    # scarcer devices than members: round-robin sharing, never empty
    # for an active member
    assert device_partition([7], 0b011, 0) == [7]
    assert device_partition([7], 0b011, 1) == [7]
    assert device_partition([5, 9], 0b111, 2) == [5]


def _dev_stub(digests, sigs, pubs):
    """Module-level device stub (picklable): host verify, any index."""
    return hostpath.verify_batch_digest_host(digests, sigs, pubs)


def test_device_universe_scale_recruits_and_returns_ordinals():
    """Live rebalance: scale-out hands the activated spare its strided
    slice of the kind-wide device universe AT BOOT and the incumbent
    releases it at the next quiet pool boundary; scale-in returns the
    retiree's ordinals to the survivor — with the stream exactly-once
    across both repartitions."""
    pool_n, repeat = 128, 2
    rows, szs, _ = make_txn_pool(pool_n, seed=17)
    total = pool_n * repeat
    topo = Topology(name=f"tdu{os.getpid()}", runtime="thread")
    topo.link("synth_verify", depth=256, mtu=wire.LINK_MTU)
    for i in range(2):
        topo.link(f"verify{i}_dedup", depth=256, mtu=wire.LINK_MTU)
    topo.link("dedup_sink", depth=256, mtu=wire.LINK_MTU)
    synth = SynthTile(rows, szs, total=total, repeat=repeat)
    topo.tile(synth, outs=["synth_verify"])
    for i in range(2):
        topo.tile(
            VerifyTile(
                msg_width=256, max_lanes=32, pre_dedup=False,
                device="off", device_fn=_dev_stub, async_depth=2,
                device_universe=[0, 1, 2, 3], name=f"verify{i}",
            ),
            ins=[("synth_verify", True)], outs=[f"verify{i}_dedup"],
        )
    topo.tile(
        DedupTile(depth=1 << 12),
        ins=[(f"verify{i}_dedup", True) for i in range(2)],
        outs=["dedup_sink"],
    )
    topo.tile(SinkTile(shm_log=4 * total), ins=[("dedup_sink", True)])
    topo.declare_shards(
        "verify", ["verify0", "verify1"], producer="synth",
        producer_link="synth_verify", active=1,
    )
    topo.build()
    topo.start(batch_max=32)
    try:
        v0 = topo.tiles["verify0"].tile
        assert v0.device_indices == [0, 1, 2, 3]
        assert topo.add_shard("verify") == 1
        v1 = topo.tiles["verify1"].tile
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            if v0.device_indices == [0, 2] and v1.device_indices == [1, 3]:
                break
            time.sleep(0.02)
        assert v1.device_indices == [1, 3], "spare never recruited"
        assert v0.device_indices == [0, 2], "incumbent never released"
        assert v0.n_devices == 2 and len(v0._policies) == 2
        topo.retire_shard("verify", 1, timeout_s=120.0, replay=256)
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            if v0.device_indices == [0, 1, 2, 3]:
                break
            time.sleep(0.02)
        assert v0.device_indices == [0, 1, 2, 3], (
            "scale-in must return the retiree's ordinals"
        )
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            sigs = read_siglog(topo.tile_alloc_view("sink", "siglog"))
            if len(set(sigs.tolist())) >= pool_n:
                break
            topo.poll_failure()
            time.sleep(0.05)
        sigs = read_siglog(topo.tile_alloc_view("sink", "siglog"))
        uniq = set(sigs.tolist())
        assert len(uniq) == pool_n, f"lost {pool_n - len(uniq)} frags"
        assert len(sigs) == len(uniq), "duplicated frags past dedup"
        topo.halt()
    finally:
        topo.close()
