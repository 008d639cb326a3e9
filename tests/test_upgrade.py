"""fdt_upgrade tier-1 suite (ISSUE 16): zero-downtime hot code upgrade
with a runtime ring-ABI version handshake.

What is asserted, per the acceptance bar:

  * the abi digest is a stable, nonzero pure function of the ring
    contract, and every component move (C symbol set, ctypes sigs,
    cfg-word map, emit surface) changes it;
  * cbuild writes an `.hsk` ABI sidecar next to every built .so —
    byte-identical across rebuilds from the same sources, different the
    moment an exported symbol appears;
  * the shared_handshake word: owner init, operator approve ordering,
    joiner compatibility, refusal with BOTH digests on mismatch or a
    tampered header;
  * a hot upgrade of a mid-pipeline tile under live traffic lands zero
    lost / zero duplicated frags on BOTH runtimes (thread: mutate-based
    code swap; process: respawn into a COPIED module tree via
    version_root behind the same rings);
  * an ABI-skewed candidate is refused at pre-flight with zero downtime
    (the running tile is never touched), and a stale incarnation that
    would rejoin a retagged workspace is refused by the CHILD-side
    check_join gate before binding a single ring;
  * a failed new-version boot rolls back to the old recipe and is
    commanded-then-rollback to the supervisor — no breaker burn — and
    every outcome classifies as an explained `upgrade:<op>` incident.

Process topologies stay small: every child pays a fresh interpreter
import on this host, and the new-tree test pays one probe subprocess.
"""

from __future__ import annotations

import copy
import json
import os
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np
import pytest

from firedancer_tpu.disco import Topology, UpgradeRefused, UpgradeRolledBack
from firedancer_tpu.disco.handshake import (
    HANDSHAKE_FOOTPRINT,
    Handshake,
    HandshakeRefused,
    check_join,
    probe_digest,
)
from firedancer_tpu.tango import rings as R
from firedancer_tpu.tiles import wire
from firedancer_tpu.tiles.dedup import DedupTile
from firedancer_tpu.tiles.sink import SinkTile, read_siglog
from firedancer_tpu.tiles.synth import SynthTile, make_txn_pool
from firedancer_tpu.utils import cbuild

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_shm_leak(no_shm_leak):
    """Every test here runs under conftest's /dev/shm leak check."""
    yield


# ---------------------------------------------------------------------------
# units: digest


def test_abi_digest_stable_and_nonzero():
    """The handshake word is a pure function of the loaded tree: stable
    across recomputation, never the 0 uninitialized sentinel, and every
    component the spec names is populated."""
    d1, d2 = R.abi_digest(), R.abi_digest()
    assert d1 == d2
    assert d1 != 0
    comp = R.abi_components()
    assert comp["c"], "no exported C symbols folded in"
    assert comp["sigs"], "no ctypes sigs folded in"
    assert comp["cfg_words"], "no cfg-word constants folded in"
    assert comp["emit"], "no emit-body signatures folded in"
    # the stem cfg-word map and ring layout constants are in
    assert any(k.startswith("_SC_") for k in comp["cfg_words"])
    assert any(k.startswith("STEM_") or k.startswith("_STEM_")
               for k in comp["cfg_words"])
    assert R.digest_of(comp) == d1


def test_digest_detects_every_component_move():
    """Symbol add/remove, a sig retype, a cfg-word renumber, and an
    emit-surface change each flip the digest — no component is dead
    weight in the fold."""
    base = R.abi_components()
    d0 = R.digest_of(base)

    def mutated(fn):
        doc = copy.deepcopy(base)
        fn(doc)
        return R.digest_of(doc)

    ds = {
        "sym_add": mutated(lambda c: c["c"].append("void fdt_new_fn(void)")),
        "sym_del": mutated(lambda c: c["c"].pop()),
        "sig_retype": mutated(
            lambda c: c["sigs"][next(iter(c["sigs"]))].__setitem__(
                0, "c_double"
            )
        ),
        "cfg_renumber": mutated(
            lambda c: c["cfg_words"].__setitem__(
                next(iter(c["cfg_words"])),
                c["cfg_words"][next(iter(c["cfg_words"]))] + 1,
            )
        ),
        "emit_change": mutated(
            lambda c: c["emit"].__setitem__("fdt_stem_out_emit", ["None", []])
        ),
    }
    for what, d in ds.items():
        assert d != d0, f"{what} did not move the digest"
        assert d != 0
    # and the mutations are pairwise distinct (no trivial collision)
    assert len(set(ds.values())) == len(ds)


def test_probe_digest_identity_and_so_sidecar():
    """probe_digest with no overrides answers in-process and equals the
    live digest; pointing FDT_SO_PATH at the live artifact (probed in a
    throwaway interpreter, sidecar-driven) lands on the same digest."""
    assert probe_digest() == R.abi_digest()
    assert R._SO_PATH is not None
    side = cbuild.read_sidecar(Path(R._SO_PATH))
    assert side is not None and side["symbols"] == R.abi_components()["c"]
    assert probe_digest(so_path=R._SO_PATH) == R.abi_digest()


# ---------------------------------------------------------------------------
# units: cbuild sidecar


_C_V1 = """
#include <stdint.h>
int64_t fdt_probe_add(int64_t a, int64_t b) { return a + b; }
"""

_C_V2 = _C_V1 + """
int64_t fdt_probe_mul(int64_t a, int64_t b) { return a * b; }
"""


def test_cbuild_sidecar_tracks_symbol_set(tmp_path, monkeypatch):
    """Every build drops a .hsk sidecar; rebuilding identical sources
    reuses artifact AND sidecar byte-for-byte; adding one exported
    symbol yields a new artifact whose sidecar grew by exactly that
    prototype."""
    monkeypatch.setenv("FDT_CACHE_DIR", str(tmp_path / "cache"))
    src = tmp_path / "probe.c"
    src.write_text(_C_V1)
    so1 = cbuild.build("hsk_probe", [src])
    sc1 = cbuild.sidecar_path(so1)
    assert sc1.exists()
    doc1 = cbuild.read_sidecar(so1)
    assert doc1["symbols"] == ["int64_t fdt_probe_add(int64_t a, int64_t b)"]
    raw1 = sc1.read_bytes()
    # rebuild: cache hit, sidecar identical
    assert cbuild.build("hsk_probe", [src]) == so1
    assert sc1.read_bytes() == raw1
    # sidecar lost (foreign-artifact repair path): backfilled on reuse
    sc1.unlink()
    assert cbuild.build("hsk_probe", [src]) == so1
    assert cbuild.read_sidecar(so1) == doc1
    # symbol add: new artifact, sidecar superset
    src.write_text(_C_V2)
    so2 = cbuild.build("hsk_probe", [src])
    assert so2 != so1
    doc2 = cbuild.read_sidecar(so2)
    assert set(doc1["symbols"]) < set(doc2["symbols"])
    assert "int64_t fdt_probe_mul(int64_t a, int64_t b)" in doc2["symbols"]


# ---------------------------------------------------------------------------
# units: handshake word


def test_handshake_word_owner_joiner_and_tamper():
    mem = np.zeros(HANDSHAKE_FOOTPRINT, np.uint8)
    hs = Handshake(mem, join=False)
    d_old, d_new = R.abi_digest(), 0xFEEDFACECAFE0001
    hs.init(d_old)
    assert hs.digest() == d_old
    assert hs.compatible(d_old)
    assert not hs.compatible(d_new)
    check_join(mem, d_old)  # no raise
    with pytest.raises(HandshakeRefused) as ei:
        check_join(mem, d_new, tile="dedup")
    assert ei.value.shm_digest == d_old
    assert ei.value.my_digest == d_new
    assert "dedup" in str(ei.value)
    # operator approval admits the foreign digest; idempotent
    hs.approve(d_new)
    hs.approve(d_new)
    assert int(hs.words[2]) == 1
    assert hs.compatible(d_new)
    check_join(mem, d_new)
    # the 0 sentinel is never approvable-by-accident on the owner side
    with pytest.raises(AssertionError):
        hs.init(0)
    # a torn/tampered header (bad magic) refuses EVERYONE — a joiner
    # must never bind rings on a region it cannot prove is a handshake
    joiner_view = Handshake(mem, join=True)
    mem.view(np.uint64)[0] = 0
    assert not joiner_view.compatible(d_old)
    with pytest.raises(HandshakeRefused):
        check_join(mem, d_old)


# ---------------------------------------------------------------------------
# pipeline harness (relay: synth -> dedup -> sink)


def _relay_topo(name, runtime, pool_n, repeat, seed=7, shm_log=1 << 13):
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


def _await_sink(topo, n, deadline_s=120.0):
    deadline = time.monotonic() + deadline_s
    ms = topo.metrics("sink")
    while time.monotonic() < deadline:
        topo.poll_failure()
        if ms.counter("in_frags") >= n:
            return
        time.sleep(0.02)
    raise TimeoutError(f"sink stalled at {ms.counter('in_frags')}/{n}")


def _assert_exactly_once(topo, synth, pool_n):
    sigs = read_siglog(topo.tile_alloc_view("sink", "siglog"))
    uniq = set(sigs.tolist())
    assert len(uniq) == pool_n, f"lost {pool_n - len(uniq)} frags"
    assert len(sigs) == len(uniq), "duplicated frags past dedup"
    assert uniq <= set(synth.tags.tolist())


# ---------------------------------------------------------------------------
# thread runtime


def test_thread_hot_upgrade_zero_loss():
    """Hot upgrade of the mid-pipeline dedup under live traffic on the
    thread runtime: digest-gated mutate-based code swap, full survivor
    set lands exactly once, and the workspace word carries the building
    tree's digest."""
    pool_n, repeat = 512, 3
    topo, synth, total = _relay_topo(
        f"tut{os.getpid()}", "thread", pool_n, repeat
    )
    topo.build()
    assert topo.handshake().digest() == R.abi_digest()
    # version_root/so_path are a process-runtime contract
    with pytest.raises(ValueError, match="in-process"):
        topo.hot_upgrade("dedup", version_root="/nonexistent")
    topo.start(batch_max=64)
    try:
        _await_sink(topo, pool_n // 4)
        swapped = []
        topo.hot_upgrade(
            "dedup",
            mutate=lambda t: swapped.append(t) or setattr(t, "_v2", True),
            replay=256,
        )
        assert swapped and getattr(topo.tiles["dedup"].tile, "_v2", False)
        _await_sink(topo, pool_n)
        # let the synth finish so accounting below is closed
        deadline = time.monotonic() + 60.0
        md = topo.metrics("dedup")
        while md.counter("in_frags") < total and time.monotonic() < deadline:
            topo.poll_failure()
            time.sleep(0.02)
        _assert_exactly_once(topo, synth, pool_n)
        topo.halt()
    finally:
        topo.close()


def test_upgrade_refused_and_rollback_are_commanded(tmp_path):
    """Satellites 2+3: through the controller, a handshake refusal and
    a new-version boot-failure rollback are upgrade-kind events — BOTH
    version digests in the refusal bundle, explained `upgrade:<op>`
    classes, and ZERO supervisor breaker burn (breaker_n=2 would trip
    if the rollback's respawns were miscounted as crashes)."""
    from firedancer_tpu.disco import (
        ElasticConfig,
        ElasticController,
        FlightRecorder,
        RestartPolicy,
        Supervisor,
    )
    from scripts.fdtincident import classify_dir, load_bundle

    pool_n, repeat = 256, 3
    topo, synth, total = _relay_topo(
        f"tur{os.getpid()}", "thread", pool_n, repeat
    )
    topo.build()
    sup = Supervisor(topo, RestartPolicy(hb_timeout_s=5.0, breaker_n=2))
    inc_dir = str(tmp_path / "inc")
    flight = FlightRecorder(topo, inc_dir)
    flight.attach_supervisor(sup)
    ctl = ElasticController(topo, ElasticConfig(kinds={}), sup=sup)
    sup.start(batch_max=16)
    flight.start()
    d_live = R.abi_digest()
    skewed = (d_live ^ 0xDEADBEEF00000000) | 1
    try:
        _await_sink(topo, pool_n // 8)
        # 1) skewed digest: refused at pre-flight, zero downtime — the
        #    running incarnation is never signalled
        inc_before = topo.tiles["dedup"].ctx.incarnation
        with pytest.raises(UpgradeRefused) as ei:
            ctl.hot_upgrade("dedup", digest=skewed)
        assert ei.value.shm_digest == d_live
        assert ei.value.new_digest == skewed
        assert topo.tiles["dedup"].ctx.incarnation == inc_before
        # 2) new version whose boot fails: rolled back to the old
        #    recipe, pipeline still completes
        with pytest.raises(UpgradeRolledBack) as er:
            ctl.hot_upgrade(
                "dedup",
                mutate=lambda t: setattr(t, "depth", "boom"),
                replay=256,
            )
        assert er.value.tile == "dedup"
        assert topo.tiles["dedup"].tile.depth == 1 << 14, (
            "rollback must restore the pre-mutate tile snapshot"
        )
        # 3) a clean upgrade for the success bundle
        ctl.hot_upgrade(
            "dedup", mutate=lambda t: setattr(t, "_v2", True), replay=256
        )
        _await_sink(topo, pool_n)
        time.sleep(0.3)  # let the watcher drain pending events
    finally:
        flight.stop()
        sup.halt()
    try:
        # commanded-then-rollback: never a crash streak
        assert sup.restarts("dedup") == 0, "upgrade counted as crash"
        assert sup.degraded("dedup") is None, "breaker tripped"
        assert sup._state["dedup"].backoff_s == 0.0
        _assert_exactly_once(topo, synth, pool_n)
        rows = classify_dir(inc_dir)
        by_class = {}
        for r in rows:
            by_class.setdefault(r["class"], []).append(r)
        for cls in ("upgrade:refused", "upgrade:rollback",
                    "upgrade:hot-upgrade"):
            assert len(by_class.get(cls, [])) == 1, (cls, rows)
            assert by_class[cls][0]["explained"], (cls, rows)
        # the refusal bundle carries BOTH digests
        ref = load_bundle(by_class["upgrade:refused"][0]["path"])
        det = ref["trigger"]["detail"]
        assert int(det["shm_digest"], 16) == d_live
        assert int(det["new_digest"], 16) == skewed
        assert "cause" in load_bundle(
            by_class["upgrade:rollback"][0]["path"]
        )["trigger"]["detail"]
    finally:
        topo.close()


# ---------------------------------------------------------------------------
# process runtime


def _make_version_tree(dst: Path) -> str:
    """A COPY of the live package with one extra stem cfg-word constant
    appended to tango/rings.py — ring-ABI-identical in behavior but
    digest-distinct, exactly the 'new build' shape hot upgrade ships."""
    root = dst / "vnew"
    shutil.copytree(
        os.path.join(REPO, "firedancer_tpu"),
        root / "firedancer_tpu",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    with open(root / "firedancer_tpu" / "tango" / "rings.py", "a") as f:
        f.write("\n_SC_UPGRADE_PROBE = 299\n")
    return str(root)


def test_process_hot_upgrade_new_tree_refused_then_approved(tmp_path):
    """The tentpole, process runtime: a respawn into a DIFFERENT module
    tree behind the same rings.  The skewed tree is refused at
    pre-flight with zero downtime; after the operator retags the
    workspace to the new digest the upgrade lands, the NEW child passes
    the handshake the OLD tree would now fail (so the respawn provably
    imported the new tree), and the stream stays exactly-once."""
    pool_n, repeat = 256, 4
    topo, synth, total = _relay_topo(
        f"tup{os.getpid()}", "process", pool_n, repeat, shm_log=1 << 14
    )
    root = _make_version_tree(tmp_path)
    topo.build()
    d_old = R.abi_digest()
    assert topo.handshake().digest() == d_old
    d_new = probe_digest(version_root=root)
    assert d_new not in (0, d_old), "probe must see the new tree's digest"
    topo.start(batch_max=64, boot_timeout_s=300.0)
    try:
        _await_sink(topo, pool_n // 8)
        pid0 = topo.tile_pid("dedup")
        # un-approved: refused BEFORE the running child is touched
        with pytest.raises(UpgradeRefused) as ei:
            topo.hot_upgrade("dedup", version_root=root, replay=256)
        assert ei.value.shm_digest == d_old and ei.value.new_digest == d_new
        assert topo.tile_pid("dedup") == pid0, "refusal caused downtime"
        assert topo.tiles["dedup"].version_root is None
        # operator retags the workspace word to the NEW digest only: a
        # stale-tree incarnation (d_old) can no longer join, so the
        # upgrade completing proves the child ran the copied tree
        topo.handshake().init(d_new)
        topo.hot_upgrade(
            "dedup", version_root=root, digest=d_new, replay=256
        )
        assert topo.tile_pid("dedup") != pid0
        assert topo.tiles["dedup"].version_root == root
        _await_sink(topo, pool_n, deadline_s=180.0)
        deadline = time.monotonic() + 60.0
        md = topo.metrics("dedup")
        while md.counter("in_frags") < total and time.monotonic() < deadline:
            topo.poll_failure()
            time.sleep(0.02)
        _assert_exactly_once(topo, synth, pool_n)
        # the boot manifest advertises the new recipe to late joiners
        doc = json.loads(
            Path(f"/dev/shm/fdt_wksp_{topo.name}.dir").read_text()
        )
        boot = doc["extra"]["boot"]
        assert boot["tiles"]["dedup"]["version_root"] == root
        assert boot["handshake"] == "shared_handshake"
        topo.halt()
    finally:
        topo.close()


def test_process_child_refuses_tampered_word_then_recovers():
    """The child-side backstop (the half fdtlint pins): a rebinding
    incarnation checks the shm word ITSELF — a corrupted/foreign digest
    refuses the join before any ring bind, the parent surfaces the
    refusal from the err sidecar, and restoring the word lets the next
    incarnation rejoin and finish exactly-once."""
    pool_n, repeat = 192, 3
    topo, synth, total = _relay_topo(
        f"tuw{os.getpid()}", "process", pool_n, repeat
    )
    topo.build()
    hs = topo.handshake()
    d_live = hs.digest()
    topo.start(batch_max=64, boot_timeout_s=300.0)
    try:
        _await_sink(topo, pool_n // 8)
        hs.init(0x0DDBA11C0DE00001)
        with pytest.raises(RuntimeError, match="handshake refused"):
            topo.rolling_restart("dedup", replay=256)
        # repair the word: the NEXT incarnation joins and the stream
        # completes with zero loss despite the refused one in between
        hs.init(d_live)
        topo.rolling_restart("dedup", replay=256)
        _await_sink(topo, pool_n, deadline_s=180.0)
        deadline = time.monotonic() + 60.0
        md = topo.metrics("dedup")
        while md.counter("in_frags") < total and time.monotonic() < deadline:
            topo.poll_failure()
            time.sleep(0.02)
        _assert_exactly_once(topo, synth, pool_n)
        topo.halt()
    finally:
        topo.close()
