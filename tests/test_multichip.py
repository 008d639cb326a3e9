"""Multi-device scale-out surface: the verify tile's device pool.

* Verify device pool (tiles/verify.py `_DevicePool`): tier-1 tests on
  stubbed per-domain device fns (the strict host verifier standing in
  for the accelerator — JAX-free, so they run under the forced-8-device
  tier-1 environment `--xla_force_host_platform_device_count=8` that
  tests/conftest.py pins).  Covered: correctness vs the golden-signed
  pool, strict in-seq publish order across devices, work actually
  spreading over multiple domains, device-kill chaos (quarantine →
  redistribution → zero lost/duplicated batches), per-device stall
  patience, and the abort()-cannot-orphan-work accounting contract.

* The graft entry's dry run (`parallel/dryrun.py`): the same pool on
  the virtual CPU devices, tier-1 with a host stand-in for the pinned
  device fn (nothing compiles); with real pinned fns, slow tier.
"""

import threading
import time

import numpy as np
import pytest

from firedancer_tpu.disco import (
    Fault,
    FaultInjector,
    RestartPolicy,
    Supervisor,
    Topology,
)
from firedancer_tpu.ops.ed25519 import hostpath
from firedancer_tpu.tiles import wire
from firedancer_tpu.tiles.sink import SinkTile
from firedancer_tpu.tiles.synth import SynthTile, make_txn_pool
from firedancer_tpu.tiles.verify import (
    DevicePolicy,
    FallbackPolicy,
    VerifyTile,
    _DevicePool,
    _DeviceWorker,
)

N_DEV = 8


def _real_dev(digests, sigs, pubs):
    """Stub accelerator: the strict host verifier (bit-identical to the
    device kernel's accept set) — each pool domain gets its own 'chip'."""
    return hostpath.verify_batch_digest_host(digests, sigs, pubs)


def _wait(cond, deadline_s: float, fail=lambda: None, poll_s: float = 0.02):
    end = time.monotonic() + deadline_s
    while time.monotonic() < end:
        if cond():
            return
        fail()
        time.sleep(poll_s)
    raise TimeoutError("condition not reached")


def _run_pool_topology(pool_n, seed, faults=None, corrupt_frac=0.25,
                       **verify_kw):
    """synth -> verify(8-domain pool) -> sink; returns (expected in-order
    good tags, sink-recorded tags in publish order, verify counters)."""
    rows, szs, good = make_txn_pool(
        pool_n, corrupt_frac=corrupt_frac, seed=seed
    )
    synth = SynthTile(rows, szs, total=pool_n)
    kw = dict(
        msg_width=256, max_lanes=8, pre_dedup=False,
        device_fn=_real_dev, devices=N_DEV, async_depth=2,
    )
    kw.update(verify_kw)
    verify = VerifyTile(**kw)
    assert verify.n_devices == N_DEV
    sink = SinkTile(record=True)
    topo = Topology()
    topo.link("synth_verify", depth=256, mtu=wire.LINK_MTU)
    topo.link("verify_sink", depth=256, mtu=wire.LINK_MTU)
    topo.tile(synth, outs=["synth_verify"])
    topo.tile(verify, ins=[("synth_verify", True)], outs=["verify_sink"])
    topo.tile(sink, ins=[("verify_sink", True)])
    inj = faults and FaultInjector(seed=seed, faults=faults)
    sup = Supervisor(topo, RestartPolicy(hb_timeout_s=30.0), faults=inj)
    sup.start(batch_max=8)
    n_good = int(good.sum())
    try:
        _wait(
            lambda: topo.metrics("sink").counter("sunk_frags") >= n_good,
            120.0,
            topo.poll_failure,
        )
    finally:
        sup.halt()
    try:
        mv = topo.metrics("verify")
        counters = {
            c: mv.counter(c) for c in mv.schema.counters
        }
        expected = synth.tags[good].tolist()
        got = sink.all_sigs().tolist()
        return expected, got, counters, inj
    finally:
        topo.close()


# ---------------------------------------------------------------------------
# verify device pool: correctness + order + spread (tier-1)


def test_verify_pool_8dev_correctness_order_spread():
    """The 8-domain pool must (a) agree with the golden-signed ground
    truth, (b) publish strictly in arrival-seq order no matter how the
    devices interleave, and (c) actually spread work across devices."""
    expected, got, c, _ = _run_pool_topology(96, seed=43)
    # (a) exact accept set, (b) exact order: in-seq landing makes the
    # multi-device pipeline's output bit-identical to a serial stream
    assert got == expected
    assert c["verify_fail_txns"] == 96 - len(expected)
    assert c["fallback_batches"] == 0 and c["device_errors"] == 0
    # (c) least-in-flight/round-robin spread: >= 2 domains landed work
    landed = [c[f"dev{i}_landed"] for i in range(N_DEV)]
    assert sum(landed) == c["device_batches"] >= N_DEV / 2
    assert sum(1 for n in landed if n > 0) >= 2, landed
    assert all(c[f"dev{i}_degraded"] == 0 for i in range(N_DEV))


def test_verify_pool_device_kill_chaos():
    """Killing one device mid-run (scripted device_error on every one of
    its batches, faultinj device targeting) must quarantine it and
    resubmit its batches to healthy devices: zero lost, zero duplicated,
    order still in-seq, and the dead domain flagged degraded."""
    dead = 3
    expected, got, c, inj = _run_pool_topology(
        96, seed=47,
        faults=[Fault("verify", "device_error", at=0, count=1 << 30,
                      device=dead)],
        fallback_trip=2,
        # quarantine long enough that the dead device stays down (and
        # visibly degraded) for the whole test instead of re-probing
        dev_backoff_base_s=300.0, dev_backoff_max_s=300.0,
    )
    assert got == expected  # nothing lost, nothing duplicated, in order
    assert inj.count("device_error") >= 2
    assert c["device_errors"] >= 2
    assert c["device_trips"] >= 1
    assert c["pool_resubmits"] >= 1  # evicted batches went elsewhere
    assert c[f"dev{dead}_degraded"] == 1
    assert c[f"dev{dead}_landed"] == 0
    # the healthy domains carried the full load
    landed = [c[f"dev{i}_landed"] for i in range(N_DEV) if i != dead]
    assert sum(landed) == c["device_batches"]
    assert sum(1 for n in landed if n > 0) >= 2, landed


def test_verify_pool_all_devices_dead_falls_to_host():
    """Every domain erroring -> the host path is the last resort: the
    pipeline still completes, batches counted as fallback degradation."""
    expected, got, c, _ = _run_pool_topology(
        32, seed=53,
        faults=[Fault("verify", "device_error", at=0, count=1 << 30)],
        fallback_trip=1,
        dev_backoff_base_s=300.0, dev_backoff_max_s=300.0,
    )
    assert got == expected
    assert c["fallback_batches"] >= 1  # host served what devices couldn't


# ---------------------------------------------------------------------------
# per-device stall patience + in-order landing through recovery races


def test_pool_stall_patience_quarantines_only_stalled_device():
    """The stall patience is per device: a wedged device call degrades
    only ITS domain — in-flight batches move to healthy devices,
    publishing stays in seq order, and the late result from the
    recovered device is dropped (no duplicates)."""
    release = threading.Event()
    hit = threading.Event()

    def wedge_fn(d, s, p):
        hit.set()
        assert release.wait(30.0)
        return np.ones(len(d), bool)

    def fast_fn(d, s, p):
        return np.ones(len(d), bool)

    mk = lambda fn, i: DevicePolicy(  # noqa: E731
        fn, hostpath.verify_batch_digest_host, index=i,
        stall_patience_s=0.1, backoff_base_s=300.0, backoff_max_s=300.0,
    )
    policies = [mk(wedge_fn, 0), mk(fast_fn, 1), mk(fast_fn, 2)]
    pool = _DevicePool(policies, depth=2, name="t")
    try:
        args = (np.zeros((4, 64), np.uint8),) * 2 + (
            np.zeros((4, 32), np.uint8),
        )
        n = 8
        metas = [dict(lanes=4, i=i) for i in range(n)]
        submitted = 0
        landed = []
        deadline = time.monotonic() + 30.0
        while len(landed) < n and time.monotonic() < deadline:
            while submitted < n and pool.submit(metas[submitted], args):
                submitted += 1
            pool.poll()
            while pool.ready:
                landed.append(pool.ready.popleft()[0])
            time.sleep(0.005)
        # every batch landed exactly once, in pool-seq order
        assert [m["pool_seq"] for m in landed] == list(range(n))
        assert [m["i"] for m in landed] == list(range(n))
        # the wedged domain was caught by ITS patience and quarantined;
        # the others stayed healthy
        assert hit.is_set()
        assert policies[0].stalled and policies[0].device_stalls == 1
        assert not policies[1].stalled and not policies[2].stalled
        assert pool.resubmits >= 1
        # recovery race: releasing the wedge lands a LATE result for a
        # batch that was moved away — it must be dropped, not re-emitted
        release.set()
        _wait(lambda: pool.late_results >= 1, 10.0, pool.poll)
        assert not pool.ready  # no duplicate publish
        assert not policies[0].stalled  # the returned call clears it
    finally:
        release.set()
        pool.stop(timeout_s=5.0)


# ---------------------------------------------------------------------------
# abort() accounting: a wedged worker cannot orphan its queue


def test_device_worker_abort_drains_wedged_queue():
    """abort() on a worker wedged inside a device call must hand back
    every batch it never landed — the queued submissions AND the
    in-flight one — for resubmission elsewhere (the pre-fix abort lost
    queued metas when a land wedged)."""
    release = threading.Event()
    entered = threading.Event()

    def wedge_fn(x):
        entered.set()
        assert release.wait(30.0)
        return np.ones(1, bool)

    p = FallbackPolicy(wedge_fn, hostpath.verify_batch_digest_host)
    w = _DeviceWorker(p, depth=3, name="t-wedge")
    try:
        for i in range(3):
            w.submit({"lanes": 1, "i": i}, ("x",))
        assert entered.wait(10.0)  # batch 0 is wedged inside the device
        drained = w.abort(timeout_s=0.3)
        # nothing landed, nothing silently dropped: all 3 recoverable
        assert sorted(m["i"] for m, _, _ in drained) == [0, 1, 2]
        assert w.submitted_n == 3 and w.completed_n == 0
        assert w.thread.is_alive()  # the zombie is reported, not joined
    finally:
        release.set()


def test_device_worker_stop_timeout_bounded_when_wedged():
    """stop(timeout_s) on a worker wedged with a FULL queue must return
    within its bound (the pre-fix put-retry loop spun forever: the
    timeout only bounded the join, not the _STOP enqueue)."""
    release = threading.Event()
    entered = threading.Event()

    def wedge_fn(x):
        entered.set()
        assert release.wait(30.0)
        return np.ones(1, bool)

    p = FallbackPolicy(wedge_fn, hostpath.verify_batch_digest_host)
    w = _DeviceWorker(p, depth=2, name="t-stopwedge")
    try:
        for i in range(3):  # 1 wedged in flight + 2 filling the queue
            while w.reqq.full():
                time.sleep(0.001)
            w.submit({"lanes": 1, "i": i}, ("x",))
        assert entered.wait(10.0)
        _wait(lambda: w.reqq.full(), 10.0)
        t0 = time.monotonic()
        w.stop(timeout_s=0.5)
        assert time.monotonic() - t0 < 5.0
        assert w.thread.is_alive()  # abandoned daemon, not joined
    finally:
        release.set()


def test_pool_stalled_flag_cleared_when_watchdog_races_return():
    """mark_stalled() landing AFTER the wedged call already returned
    (and cleared the flag) must not quarantine the idle device forever:
    poll() clears an orphaned stalled flag when nothing is in flight."""
    p = DevicePolicy(
        lambda *a: np.ones(4, bool), hostpath.verify_batch_digest_host,
        index=0, stall_patience_s=60.0,
    )
    pool = _DevicePool([p], depth=2, name="t-race")
    try:
        p.mark_stalled()  # the stale watchdog shot; worker is idle
        assert p.stalled
        pool.poll()
        assert not p.stalled  # orphaned flag cleared; backoff still set
        assert p.tripped and p.backoff_s > 0
    finally:
        pool.stop(timeout_s=5.0)


def test_device_worker_abort_clean_exit_asserts_conservation():
    """The no-silent-drop assert on a cleanly exited worker: submitted
    == landed + drained."""
    p = FallbackPolicy(
        lambda x: np.ones(1, bool), hostpath.verify_batch_digest_host
    )
    w = _DeviceWorker(p, depth=2, name="t-clean")
    for i in range(4):
        while w.reqq.full():
            time.sleep(0.001)
        w.submit({"lanes": 1, "i": i}, ("x",))
    _wait(lambda: w.completed_n == 4, 10.0)
    drained = w.abort(timeout_s=5.0)
    assert drained == [] and not w.thread.is_alive()
    assert len(w.results) == 4


# ---------------------------------------------------------------------------
# wiring: device specs -> replica assignments, metrics rows, monitor


def test_device_assignments_partition():
    from firedancer_tpu.disco.topo import device_assignments

    # default / off: every replica on ordinal 0 (today's single stream)
    assert device_assignments(1, 3) == [[0], [0], [0]]
    assert device_assignments(None, 1) == [[0]]
    # int width, disjoint split across replicas
    assert device_assignments(8, 2) == [[0, 2, 4, 6], [1, 3, 5, 7]]
    assert device_assignments([4, 5, 6], 1) == [[4, 5, 6]]
    # fewer devices than replicas: shared round-robin, one each
    assert device_assignments([0, 1], 3) == [[0], [1], [0]]
    # disjointness whenever there are enough devices
    for spec, n in ((8, 3), ([1, 2, 3, 4, 5], 2)):
        parts = device_assignments(spec, n)
        flat = [d for p in parts for d in p]
        assert len(flat) == len(set(flat))


def test_device_counters_roundtrip_and_rows():
    from firedancer_tpu.disco.metrics import (
        DEVICE_METRICS,
        device_counters,
        device_rows,
        parse_device_counter,
    )

    names = device_counters(3)
    assert len(names) == 3 * len(DEVICE_METRICS)
    assert "dev0_depth" in names and "dev2_degraded" in names
    for n in names:
        idx, metric = parse_device_counter(n)
        assert 0 <= idx < 3 and metric in DEVICE_METRICS
    assert parse_device_counter("device_errors") is None
    assert parse_device_counter("dedup_drop_txns") is None
    rows = device_rows(
        {"dev0_landed": 7, "dev1_degraded": 1, "in_frags": 9}
    )
    assert rows == {0: {"landed": 7}, 1: {"degraded": 1}}


def test_monitor_surfaces_per_device_degradation():
    """verify_dev{i}_degraded reaches the operator: the monitor turns a
    degraded device row into an ALARM line and a health sub-row."""
    from firedancer_tpu.app.monitor import Monitor

    snap = {
        "verify0": {
            "signal": "RUN",
            "heartbeat": 1,
            "stale": False,
            "counters": {
                "in_frags": 10, "out_frags": 10,
                "dev0_depth": 0, "dev0_inflight": 1, "dev0_landed": 5,
                "dev0_failed": 0, "dev0_degraded": 0,
                "dev1_depth": 2, "dev1_inflight": 0, "dev1_landed": 0,
                "dev1_failed": 4, "dev1_degraded": 1,
            },
        }
    }
    mon = object.__new__(Monitor)  # alarms/render are pure over snap
    alarms = mon.alarms(snap)
    assert any("verify0_dev1_degraded" in a for a in alarms), alarms
    assert not any("dev0" in a for a in alarms), alarms
    out = mon.render(None, snap, 1.0)
    assert "dev1" in out and "DEGRADED" in out


def test_config_parses_verify_devices():
    pytest.importorskip("tomllib")  # app.config needs 3.11's parser
    from firedancer_tpu.app import config as C

    cfg = C.parse(
        "[tiles.verify]\ncount = 2\ndevices = 8\nstall_patience_s = 45.0\n"
    )
    assert cfg.verify_devices == 8
    assert cfg.verify_stall_patience_s == 45.0
    assert C.parse("").verify_devices == 1  # default: single stream
    cfg = C.parse('[tiles.verify]\ndevices = "auto"\n')
    assert cfg.verify_devices == "auto"
    cfg = C.parse("[tiles.verify]\ndevices = [0, 3]\n")
    assert cfg.verify_devices == [0, 3]


# ---------------------------------------------------------------------------
# the graft entry's dry run (parallel/dryrun.py): the pool, nothing else


@pytest.mark.parametrize("n", [1, 2, 8])
def test_dryrun_drives_the_verify_pool_in_order(monkeypatch, capsys, n):
    """dryrun_multichip(n) runs the verify pool on min(n, 2) devices,
    lands its 2 x width batches in order, and runs nothing else (there
    is no `firedancer_tpu.models`).  The pinned device fn is the strict
    host verifier here, so nothing compiles."""
    import importlib.util

    import __graft_entry__ as G
    from firedancer_tpu.ops.ed25519 import verify as fver

    def host_on(device):
        def fn(digests, sigs, pubs, n_lanes=None):
            return hostpath.verify_batch_digest_host(digests, sigs, pubs)

        fn.device = device
        return fn

    monkeypatch.setattr(fver, "verify_batch_digest_on", host_on)
    G.dryrun_multichip(n)
    w = min(n, 2)
    out = capsys.readouterr().out.splitlines()
    assert out[-2:] == [
        f"dryrun_verify_pool ok: {2 * w} batches in order over {w}/{w} "
        "devices",
        "dryrun_multichip ok",
    ]
    assert importlib.util.find_spec("firedancer_tpu.models") is None


# ---------------------------------------------------------------------------
# pool on REAL local devices (virtual 8-dev CPU mesh): the device="auto"
# path with per-device pinned executables — slow tier (one kernel
# compile per device PLACEMENT: ~95 s cold / ~12 s compilation-cache
# hit on this host; ONE shape per device)


@pytest.mark.slow
def test_verify_pool_real_devices_spread():
    import jax

    devs = jax.local_devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 local devices")
    expected, got, c, _ = _run_pool_topology(
        48, seed=59, device_fn=None, device="auto", devices="auto",
        max_lanes=16,
    )
    assert got == expected
    landed = [v for k, v in c.items()
              if k.startswith("dev") and k.endswith("_landed")]
    assert len(landed) == len(devs)
    assert sum(1 for n in landed if n > 0) >= 2, landed
