"""Process-runtime tier-1 suite (ISSUE 7): cross-process attach, golden
parity vs the threaded runtime, supervisor kill→restart→rejoin of a
CHILD PROCESS, third-process observability, boot-failure cleanup, and a
no-shm-leak fixture around every test.

Each topology here runs one OS process per tile (spawn): children
re-attach the named workspace, rebind endpoints from the boot manifest,
and run the unchanged mux loop.  Topologies are kept small — every
child pays a fresh-interpreter import on this host.
"""

from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from firedancer_tpu.disco import Topology
from firedancer_tpu.disco.metrics import MetricsSchema
from firedancer_tpu.disco.mux import MuxCtx, Tile
from firedancer_tpu.disco.supervisor import RestartPolicy, Supervisor
from firedancer_tpu.tiles import wire
from firedancer_tpu.tiles.dedup import DedupTile
from firedancer_tpu.tiles.sink import SinkTile, read_siglog
from firedancer_tpu.tiles.synth import SynthTile, make_txn_pool

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_shm_leak(no_shm_leak):
    """Every test here runs under conftest's /dev/shm leak check."""
    yield


def _relay_topo(name: str, runtime: str, pool_n: int, repeat: int,
                seed: int = 7, shm_log: int = 1 << 13):
    rows, szs, _ = make_txn_pool(pool_n, seed=seed)
    total = pool_n * repeat
    topo = Topology(name=name, runtime=runtime)
    topo.link("synth_dedup", depth=256, mtu=wire.LINK_MTU)
    topo.link("dedup_sink", depth=256, mtu=wire.LINK_MTU)
    synth = SynthTile(rows, szs, total=total, repeat=repeat)
    topo.tile(synth, outs=["synth_dedup"])
    topo.tile(
        DedupTile(depth=1 << 14), ins=[("synth_dedup", True)],
        outs=["dedup_sink"],
    )
    topo.tile(SinkTile(shm_log=shm_log), ins=[("dedup_sink", True)])
    return topo, synth, total


def _drain(
    topo: Topology, total: int, sunk: int, deadline_s: float = 120.0
) -> None:
    """Wait until dedup consumed every sent frag AND the sink landed
    every survivor — reading the siglog on dedup-progress alone races
    the last dedup→sink hop under load."""
    deadline = time.monotonic() + deadline_s
    md, ms = topo.metrics("dedup"), topo.metrics("sink")
    while time.monotonic() < deadline:
        topo.poll_failure()
        if md.counter("in_frags") >= total and ms.counter(
            "in_frags"
        ) >= sunk:
            return
        time.sleep(0.02)
    raise TimeoutError(
        f"pipeline stalled: dedup {md.counter('in_frags')}/{total}, "
        f"sink {ms.counter('in_frags')}/{sunk}"
    )


def _run_relay(runtime: str, pool_n=128, repeat=3) -> tuple[set, dict]:
    topo, synth, total = _relay_topo(
        f"tp{os.getpid()}_{runtime[:4]}", runtime, pool_n, repeat
    )
    topo.build()
    topo.start(batch_max=64, boot_timeout_s=300.0)
    try:
        _drain(topo, total, pool_n)
        sigs = read_siglog(topo.tile_alloc_view("sink", "siglog"))
        counters = {
            "dedup_in": topo.metrics("dedup").counter("in_frags"),
            "dups": topo.metrics("dedup").counter("dup_txns"),
            "sunk": topo.metrics("sink").counter("in_frags"),
            "overruns": sum(
                topo.metrics(n).counter("overrun_frags")
                for n in topo.tiles
            ),
        }
        topo.halt()
        assert len(sigs) == len(set(sigs.tolist())), "dup past dedup"
        return set(sigs.tolist()), counters
    finally:
        topo.close()


def test_process_golden_parity_with_threaded():
    """Same pool, both runtimes: identical survivor sets and identical
    landed/dup/overrun accounting — the runtimes must be behaviorally
    indistinguishable to everything downstream of the rings."""
    t_sigs, t_counters = _run_relay("thread")
    p_sigs, p_counters = _run_relay("process")
    assert p_sigs == t_sigs
    assert p_counters == t_counters
    assert p_counters["overruns"] == 0


def test_process_supervisor_kill_restart_rejoin():
    """SIGKILL a child mid-stream: the supervisor watchdog must detect,
    respawn a NEW process, the child must rejoin its rings (replay +
    surviving dedup tcache collapse redelivery to exactly-once), and
    the full survivor set must land — zero lost, zero duplicated."""
    pool_n, repeat = 1024, 4
    topo, synth, total = _relay_topo(
        f"tk{os.getpid()}", "process", pool_n, repeat, shm_log=1 << 14
    )
    sup = Supervisor(
        topo,
        RestartPolicy(
            hb_timeout_s=1.0,
            backoff_base_s=0.05,
            replay={"dedup": 256, "sink": 256},
        ),
    )
    sup.start(batch_max=16, idle_sleep_s=2e-3)
    try:
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if topo.metrics("sink").counter("in_frags") >= pool_n // 4:
                break
            time.sleep(0.02)
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
        assert sup.degraded("dedup") is None
        new_pid = topo.tile_pid("dedup")
        assert new_pid != pid, "restart must be a NEW process"
        assert len(uniq) == pool_n, f"lost {pool_n - len(uniq)} frags"
        assert len(sigs) == len(uniq), "duplicated frags past dedup"
        assert uniq <= set(synth.tags.tolist())
    finally:
        sup.halt()
        topo.close()


def _sigkill_in_window(flag_path: str) -> None:
    """One-shot crash probe for the dedup insert→publish window: the
    FIRST time the (child-process) dedup tile reaches the point after
    its journaled tcache insert but before the publish, SIGKILL
    ourselves — the exact window the rare chaos-test flake hit.  The
    flag file makes it once-ever across incarnations."""
    import os as _os

    try:
        fd = _os.open(flag_path, _os.O_CREAT | _os.O_EXCL | _os.O_WRONLY)
    except FileExistsError:
        return
    _os.close(fd)
    _os.kill(_os.getpid(), signal.SIGKILL)


def test_dedup_insert_publish_window_amnesty(tmp_path):
    """Deterministic regression for the insert-before-publish loss
    window (the rare lost-frag flake in the kill/restart chaos test): a
    dedup CHILD SIGKILLed after its surviving shm tcache absorbed a
    batch's inserts but before the publish must not lose the batch —
    the restarted incarnation reads the insert journal, grants the
    unpublished tags a one-shot replay amnesty, and the full survivor
    set lands exactly once."""
    import functools

    pool_n, repeat = 256, 2
    topo, synth, total = _relay_topo(
        f"ta{os.getpid()}", "process", pool_n, repeat, shm_log=1 << 13
    )
    topo.tiles["dedup"].tile._crash_probe = functools.partial(
        _sigkill_in_window, str(tmp_path / "window_kill_once")
    )
    sup = Supervisor(
        topo,
        RestartPolicy(
            hb_timeout_s=1.0,
            backoff_base_s=0.05,
            replay={"dedup": 256, "sink": 256},
        ),
    )
    sup.start(batch_max=16, idle_sleep_s=2e-3)
    try:
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            sigs = read_siglog(topo.tile_alloc_view("sink", "siglog"))
            if len(set(sigs.tolist())) >= pool_n:
                break
            time.sleep(0.05)
        sigs = read_siglog(topo.tile_alloc_view("sink", "siglog"))
        uniq = set(sigs.tolist())
        assert os.path.exists(tmp_path / "window_kill_once"), (
            "crash probe never fired"
        )
        assert sup.restarts("dedup") >= 1
        assert len(uniq) == pool_n, f"lost {pool_n - len(uniq)} frags"
        assert len(sigs) == len(uniq), "duplicated frags past dedup"
        # the recovery path actually ran: the killed batch's unpublished
        # survivors were amnestied, not silently re-admitted
        assert topo.metrics("dedup").counter("replay_amnesty") >= 1
    finally:
        sup.halt()
        topo.close()


def test_amnesty_survives_second_crash_before_drain():
    """The amnesty itself must be crash-safe: a recovering incarnation
    persists the pending set in shm BEFORE clearing the journal phase,
    so a second kill landing before the replay drains still grants the
    amnesty (a plain in-memory set would reopen the loss window)."""
    import numpy as np

    from firedancer_tpu.disco.metrics import Metrics
    from firedancer_tpu.disco.mux import MuxCtx, OutLink
    from firedancer_tpu.tango import rings as R
    from firedancer_tpu.tiles.dedup import (
        _B_CNT, _B_TAGS, _J_ACNT, _J_ACTIVE, _J_PHASE, _J_SEQ0, DedupTile,
    )

    mc = R.MCache(np.zeros(R.MCache.footprint(64), np.uint8), 64)
    ded = DedupTile(depth=256)
    ctx = MuxCtx(
        "dedup",
        R.CNC(np.zeros(R.CNC.footprint(), np.uint8)),
        [],
        [OutLink("dedup_sink", mc, None, [])],
        Metrics(np.zeros(Metrics.footprint(ded.schema), np.uint8),
                ded.schema),
    )
    ded.on_boot(ctx)
    # crash #1: the dead incarnation journaled 3 inserted tags (2 of 3
    # published — the out seq advanced past seq0 by 2)
    jw, b0 = ded._jnl, ded._blk[0]
    jw[_J_SEQ0] = mc.seq_query()
    mc.seq_advance(int(mc.seq_query()) + 2)
    b0[_B_TAGS : _B_TAGS + 3] = (11, 12, 13)
    b0[_B_CNT] = 3
    jw[_J_ACTIVE] = 0
    jw[_J_PHASE] = 1
    ctx.incarnation = 1
    ded.on_boot(ctx)  # recovery (ctx.alloc is idempotent: same shm)
    assert ded._amnesty == {13}, "only the unpublished tag is amnestied"
    assert int(jw[_J_ACNT]) == 1 and int(jw[_J_PHASE]) == 0
    # crash #2 BEFORE the replay drains: the next incarnation must still
    # hold the amnesty (from the persisted shm area, phase is clean)
    ctx.incarnation = 2
    ded.on_boot(ctx)
    assert ded._amnesty == {13}, "amnesty lost across a second crash"
    assert ctx.metrics.counter("replay_amnesty") == 2  # once per recovery


def test_process_monitor_attaches_from_third_process():
    """app/monitor.py AND scripts/fdttrace.py attach READ-ONLY from a
    genuinely separate process while the child tiles run, and see live
    counters / span rings."""
    topo, synth, total = _relay_topo(
        f"tm{os.getpid()}", "process", 64, 2
    )
    topo.enable_trace(sample=1, depth=1 << 10)
    topo.build()
    topo.start(batch_max=64, boot_timeout_s=300.0)
    try:
        _drain(topo, total, 64)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        r = subprocess.run(
            [
                sys.executable, "-m", "firedancer_tpu.app.monitor",
                topo.name, "--once", "--json",
            ],
            capture_output=True, text=True, timeout=120, env=env,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        import json

        doc = json.loads(r.stdout)
        assert set(doc["tiles"]) == {"synth", "dedup", "sink"}
        assert doc["tiles"]["dedup"]["counters"]["in_frags"] >= total
        # live signal states visible cross-process (cnc words)
        assert doc["tiles"]["dedup"]["signal"] == "RUN"
        # fdttrace: span rings written by the CHILDREN, assembled by a
        # third process into the per-hop summary
        r = subprocess.run(
            [
                sys.executable, str(os.path.join(REPO, "scripts",
                                                 "fdttrace.py")),
                topo.name, "--summary",
            ],
            capture_output=True, text=True, timeout=120, env=env,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        assert "dedup" in r.stdout
        topo.halt()
    finally:
        topo.close()


class _BoomBootTile(Tile):
    name = "boomboot"
    schema = MetricsSchema()

    def on_boot(self, ctx: MuxCtx) -> None:
        raise RuntimeError("scripted boot failure")


def test_process_boot_failure_raises_and_cleans():
    """A child that dies in on_boot is classified as a construction
    error (pstat booted word), start() raises with the child's
    traceback, and close() leaves no shm files or zombie children."""
    rows, szs, _ = make_txn_pool(4, seed=13)
    topo = Topology(name=f"tb{os.getpid()}", runtime="process")
    topo.link("s", depth=64, mtu=wire.LINK_MTU)
    topo.tile(SynthTile(rows, szs, total=8), outs=["s"])
    topo.tile(_BoomBootTile(), ins=[("s", True)])
    topo.build()
    try:
        with pytest.raises(RuntimeError, match="boot"):
            topo.start(batch_max=16, boot_timeout_s=300.0)
    finally:
        topo.close()


class _EchoBankTile(Tile):
    """Minimal bank stand-in for the pack smoke: decodes each
    microblock's (handle, bank) header and immediately publishes the
    completion tag back to pack — the bank-side half of the pack
    protocol without execution (tiles/bank.py publishes the same
    (bank << 32 | handle) tag)."""

    name = "bank0"
    schema = MetricsSchema(counters=("echoed_mbs",))

    def on_frags(self, ctx: MuxCtx, in_idx: int, frags: np.ndarray) -> None:
        il = ctx.ins[in_idx]
        rows = il.gather(frags)
        tags = []
        for i in range(len(rows)):
            buf = rows[i, : frags["sz"][i]]
            handle = int(buf[0:4].view("<u4")[0])
            bank = int(buf[4:6].view("<u2")[0])
            tags.append((bank << 32) | handle)
        ctx.publish(np.array(tags, dtype=np.uint64))
        ctx.metrics.inc("echoed_mbs", len(tags))


def test_process_quic_verify_dedup_pack():
    """The ISSUE-named smoke: quic (real UDP ingress) → verify(host) →
    dedup → pack, all as child processes, with a bank-echo completing
    microblocks.  Every unique wire txn must be inserted into pack
    exactly once and scheduled into at least one microblock."""
    from firedancer_tpu.tiles.pack import PackTile
    from firedancer_tpu.tiles.quic import QuicIngressTile
    from firedancer_tpu.tiles.verify import VerifyTile

    rng = np.random.default_rng(31)
    identity = rng.integers(0, 256, 32, np.uint8).tobytes()
    # fixed UDP port: the child binds it; the parent cannot read an
    # ephemeral port off its (never-booted) tile copy
    probe = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    probe.bind(("127.0.0.1", 0))
    udp_port = probe.getsockname()[1]
    probe.close()

    n_txns = 32
    rows, szs, _ = make_txn_pool(n_txns, seed=11)
    tr = wire.parse_trailers(rows, szs.astype(np.int64))

    topo = Topology(name=f"tq{os.getpid()}", runtime="process")
    topo.link("quic_verify", depth=256, mtu=wire.LINK_MTU)
    topo.link("verify_dedup", depth=256, mtu=wire.LINK_MTU)
    topo.link("dedup_pack", depth=256, mtu=wire.LINK_MTU)
    topo.link("pack_bank0", depth=256, mtu=65_535)
    topo.link("bank0_pack", depth=256)
    topo.tile(
        QuicIngressTile(identity, udp_addr=("127.0.0.1", udp_port)),
        outs=["quic_verify"],
    )
    topo.tile(
        VerifyTile(
            msg_width=256, max_lanes=64, pad_full=True,
            pre_dedup=False, device="off",
        ),
        ins=[("quic_verify", True)], outs=["verify_dedup"],
    )
    topo.tile(
        DedupTile(depth=1 << 10), ins=[("verify_dedup", True)],
        outs=["dedup_pack"],
    )
    topo.tile(
        PackTile(1, mb_inflight=4, microblock_ns=1_000_000, txn_limit=8),
        ins=[("dedup_pack", True), ("bank0_pack", True)],
        outs=["pack_bank0"],
    )
    topo.tile(
        _EchoBankTile(), ins=[("pack_bank0", True)], outs=["bank0_pack"]
    )
    topo.build()
    topo.start(batch_max=64, boot_timeout_s=300.0)
    try:
        tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        mp = topo.metrics("pack")
        deadline = time.monotonic() + 90.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            # re-send until verified through (UDP may drop; dedup
            # collapses the repeats, so pack still sees each ONCE)
            for i in range(n_txns):
                tx.sendto(
                    rows[i, : tr["txn_sz"][i]].tobytes(),
                    ("127.0.0.1", udp_port),
                )
            if (
                mp.counter("inserted_txns") >= n_txns
                and mp.counter("microblocks") >= 1
            ):
                break
            time.sleep(0.2)
        tx.close()
        assert mp.counter("inserted_txns") == n_txns
        assert mp.counter("microblocks") >= 1
        assert topo.metrics("dedup").counter("in_frags") >= n_txns
        assert topo.metrics("verify").counter("verify_fail_txns") == 0
        topo.halt()
    finally:
        topo.close()


def test_shm_leak_check_tells_foreign_workspaces_from_own():
    """conftest's leak check under xdist: a workspace another process
    tree still maps is that tree's business; one nobody outside this
    tree maps — mapped here or by no one — is ours, and a leak."""
    import mmap
    import subprocess
    import sys

    from conftest import _foreign_mapped

    own = f"/dev/shm/fdt_wksp_leakown{os.getpid()}"
    theirs = f"/dev/shm/fdt_wksp_leakfar{os.getpid()}"
    holder = far_pid = None
    try:
        for p in (own, theirs):
            with open(p, "wb") as f:
                f.write(b"\0" * 4096)
        with open(own, "r+b") as f:
            mine = mmap.mmap(f.fileno(), 4096)
        # a process outside this tree: double-forked, so its parent is
        # not this test process
        holder = subprocess.Popen(
            [sys.executable, "-c",
             "import mmap, os, sys, time\n"
             "if os.fork(): sys.exit(0)\n"
             f"f = open({theirs!r}, 'r+b'); m = mmap.mmap(f.fileno(), 4096)\n"
             "print(os.getpid(), flush=True); time.sleep(60)"],
            stdout=subprocess.PIPE, text=True,
        )
        far_pid = int(holder.stdout.readline())
        holder.wait(timeout=30)  # the intermediate is gone: reparented
        foreign = _foreign_mapped()
        assert theirs in foreign and own not in foreign
        mine.close()
    finally:
        if far_pid is not None:
            os.kill(far_pid, 9)
        if holder is not None:
            holder.wait(timeout=10)
        for p in (own, theirs):
            os.unlink(p)
