#!/usr/bin/env python3
"""proc_smoke — process-runtime smoke gate + threaded-vs-process A/B.

Smoke (default): run a small synth → verify(host) → dedup → sink
pipeline under `Topology.start(mode=...)`, assert end-to-end delivery
(every unique txn lands exactly once, counted via the sink's shm sig
log so the check works cross-process), assert clean shutdown, and
assert no /dev/shm/fdt_wksp_* leak.  `scripts/checkall.py` runs this as
its process-mode stage.

A/B (--ab): run PARALLEL RELAY CHAINS (synth → dedup → sink, pure
tango/interpreter work — the round-3b "host pipeline caps on pure GIL
contention" shape) with the run-loop profiler enabled in both runtimes
and print the contended-interpreter keys side by side — gil_wait_frac,
sched_lag_p99_us, relay tps — the measurement contract of the ISSUE 7
refactor.

Usage:
    scripts/proc_smoke.py [--runtime thread|process] [--txns N] [--json]
    scripts/proc_smoke.py --ab [--txns N] [--json]

Exit status: 0 ok, 1 check failed, 2 crashed.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def run_pipeline(
    runtime: str,
    n_txns: int = 2048,
    repeat: int = 2,
    profile: bool = False,
    deadline_s: float = 180.0,
    stem: str = "python",
) -> dict:
    """One pipeline run; returns {ok, tps, landed, unique, ...}."""
    import numpy as np  # noqa: F401  (env sanity before topology work)

    from firedancer_tpu.disco import Topology
    from firedancer_tpu.tiles import wire
    from firedancer_tpu.tiles.dedup import DedupTile
    from firedancer_tpu.tiles.sink import SinkTile, read_siglog
    from firedancer_tpu.tiles.synth import SynthTile, make_txn_pool
    from firedancer_tpu.tiles.verify import VerifyTile

    total = n_txns * repeat
    rows, szs, _ = make_txn_pool(n_txns, seed=7)
    topo = Topology(
        name=f"smoke{os.getpid()}_{runtime[:4]}", runtime=runtime
    )
    if profile:
        topo.enable_profile()
    topo.link("synth_verify", depth=1 << 12, mtu=wire.LINK_MTU)
    topo.link("verify_dedup", depth=1 << 12, mtu=wire.LINK_MTU)
    topo.link("dedup_sink", depth=1 << 12, mtu=wire.LINK_MTU)
    synth = SynthTile(rows, szs, total=total, repeat=repeat)
    verify = VerifyTile(
        msg_width=256, max_lanes=512, pre_dedup=False, device="off"
    )
    topo.tile(synth, outs=["synth_verify"])
    topo.tile(verify, ins=[("synth_verify", True)], outs=["verify_dedup"])
    topo.tile(
        DedupTile(depth=1 << 14), ins=[("verify_dedup", True)],
        outs=["dedup_sink"],
    )
    topo.tile(
        SinkTile(shm_log=max(2 * n_txns, 1 << 12)),
        ins=[("dedup_sink", True)],
    )
    out: dict = {"runtime": runtime, "stem": stem, "sent": total, "ok": False}
    topo.build()
    t0 = time.perf_counter()
    topo.start(batch_max=512, boot_timeout_s=600.0, stem=stem)
    boot_s = time.perf_counter() - t0
    try:
        t0 = time.perf_counter()
        deadline = t0 + deadline_s
        md = topo.metrics("dedup")
        ms = topo.metrics("sink")
        while time.perf_counter() < deadline:
            topo.poll_failure()
            # gate on the SINK too: reading the siglog on dedup
            # progress alone races the last dedup->sink hop
            if (
                md.counter("in_frags") >= total
                and ms.counter("in_frags") >= n_txns
            ):
                break
            time.sleep(0.02)
        dt = time.perf_counter() - t0
        sigs = read_siglog(topo.tile_alloc_view("sink", "siglog"))
        uniq = set(sigs.tolist())
        topo.halt()
        out.update(
            boot_s=round(boot_s, 2),
            seconds=round(dt, 3),
            tps=round(md.counter("in_frags") / dt, 1) if dt else 0.0,
            landed=len(sigs),
            unique=len(uniq),
            dups_dropped=topo.metrics("dedup").counter("dup_txns"),
            stem_frags=md.counter("stem_frags"),
            verify_fail=topo.metrics("verify").counter(
                "verify_fail_txns"
            ),
        )
        if profile:
            from firedancer_tpu.disco.profile import aggregate

            agg = aggregate(topo.profile_metrics())
            out["gil_wait_frac"] = agg["gil_wait_frac"]
            out["sched_lag_p99_us"] = agg["sched_lag_p99_us"]
        out["ok"] = (
            md.counter("in_frags") >= total
            and len(uniq) == n_txns
            and len(sigs) == len(uniq)
        )
    finally:
        topo.close()
    leaked = glob.glob(f"/dev/shm/fdt_wksp_{topo.name}*")
    out["shm_leak"] = leaked
    if leaked:
        out["ok"] = False
    return out


from firedancer_tpu.disco.mux import Tile as _Tile  # noqa: E402


class _CompletionEcho(_Tile):
    """Consumes pack's microblocks, echoes (bank, handle) sigs back on
    the completion ring — a zero-work stand-in for the bank.  Module
    level (not nested in the harness) so the process runtime's spawn
    pickle can resolve the class in tile children."""

    name = "echo"

    def on_frags(self, ctx, i, frags):
        ctx.outs[0].publish(frags["sig"].copy())


def run_pack_pipeline(
    runtime: str,
    n_txns: int = 1024,
    deadline_s: float = 180.0,
    stem: str = "python",
) -> dict:
    """Pack-scheduler smoke (ISSUE 11): synth → pack → completion echo
    under the chosen runtime/stem.  Every unique txn must be inserted
    AND scheduled exactly once (microblock_txns == inserted_txns), and
    every scheduled microblock completed (completions == microblocks) —
    end-to-end through child processes when runtime=process, with the
    native after-credit hook doing the scheduling when stem=native."""
    import numpy as np

    from firedancer_tpu.ballet import txn as BT
    from firedancer_tpu.disco import Topology
    from firedancer_tpu.tiles import wire
    from firedancer_tpu.tiles.pack import PackTile
    from firedancer_tpu.tiles.synth import SynthTile

    rng = np.random.default_rng(19)
    payers = [bytes(rng.integers(0, 256, 32, np.uint8)) for _ in range(32)]
    rows = np.zeros((n_txns, wire.LINK_MTU), np.uint8)
    szs = np.zeros(n_txns, np.uint16)
    for i in range(n_txns):
        data = (2).to_bytes(4, "little") + int(
            1 + rng.integers(1, 999)
        ).to_bytes(8, "little")
        raw = BT.build(
            [bytes(rng.integers(0, 256, 64, np.uint8))],
            [payers[i % 32], payers[(i * 7 + 3) % 32], bytes(32)],
            bytes(32), [(2, [0, 1], data)], readonly_unsigned_cnt=1,
        )
        pl = wire.append_trailer(raw, BT.parse(raw))
        rows[i, : len(pl)] = np.frombuffer(pl, np.uint8)
        szs[i] = len(pl)

    topo = Topology(
        name=f"psmoke{os.getpid()}_{runtime[:4]}", runtime=runtime,
    )
    topo.link("synth_pack", depth=1 << 10, mtu=wire.LINK_MTU)
    topo.link("pack_bank0", depth=256, mtu=65_535)
    topo.link("bank0_pack", depth=256)
    topo.tile(SynthTile(rows, szs, total=n_txns, repeat=1),
              outs=["synth_pack"])
    topo.tile(
        PackTile(1, depth=1 << 12, mb_inflight=4, microblock_ns=0,
                 slot_ns=10**15),
        ins=[("synth_pack", True), ("bank0_pack", True)],
        outs=["pack_bank0"],
    )
    topo.tile(_CompletionEcho(), ins=[("pack_bank0", True)],
              outs=["bank0_pack"])
    out: dict = {"runtime": runtime, "stem": stem, "ok": False}
    topo.build()
    topo.start(batch_max=256, boot_timeout_s=600.0, stem=stem)
    try:
        mp = topo.metrics("pack")
        deadline = time.perf_counter() + deadline_s
        while time.perf_counter() < deadline:
            topo.poll_failure()
            if (
                mp.counter("microblock_txns") >= n_txns
                and mp.counter("completions") >= mp.counter("microblocks")
            ):
                break
            time.sleep(0.02)
        topo.halt()
        out.update(
            pack_inserted=mp.counter("inserted_txns"),
            pack_mbs=mp.counter("microblocks"),
            pack_mb_txns=mp.counter("microblock_txns"),
            pack_completions=mp.counter("completions"),
            pack_stem_frags=mp.counter("stem_frags"),
            ok=(
                mp.counter("inserted_txns") == n_txns
                and mp.counter("microblock_txns") == n_txns
                and mp.counter("completions") == mp.counter("microblocks")
                and mp.counter("microblocks") > 0
                and (stem != "native" or mp.counter("stem_frags") > 0)
            ),
        )
    finally:
        topo.close()
    leaked = glob.glob(f"/dev/shm/fdt_wksp_{topo.name}*")
    out["shm_leak"] = leaked
    if leaked:
        out["ok"] = False
    return out


class _MbFeeder(_Tile):
    """Publishes deterministic microblock payloads, credit-gated.
    Module level so the process runtime's spawn pickle resolves it."""

    name = "feeder"

    def __init__(self, payloads):
        self.payloads = payloads
        self.sent = 0

    def after_credit(self, ctx):
        import numpy as np

        while self.sent < len(self.payloads) and ctx.outs[0].cr_avail():
            pl = self.payloads[self.sent]
            ctx.outs[0].publish(
                np.array([self.sent], np.uint64), pl[None, :],
                np.array([len(pl)], np.uint16),
            )
            self.sent += 1


def _egress_signer(root) -> bytes:
    """Deterministic local signer (module level: spawn-picklable)."""
    import hashlib

    return (hashlib.sha256(root).digest()
            + hashlib.sha256(root + b"s").digest())


def run_egress_pipeline(
    runtime: str,
    n_mbs: int = 256,
    deadline_s: float = 180.0,
    stem: str = "python",
) -> dict:
    """Block-egress smoke (ISSUE 12): microblock feeder → poh → shred
    (local signer) → sink under the chosen runtime/stem.  Every
    microblock mixes into the chain exactly once, slot boundaries shred
    into signed shreds, and every published shred lands downstream with
    a unique (slot, idx) tag — with the mixin ladder and queue drains
    running as native stem bursts when stem=native."""
    import numpy as np

    from firedancer_tpu.ballet import shred as SH
    from firedancer_tpu.disco import Topology
    from firedancer_tpu.tiles.poh import ENTRY_SZ, PohTile
    from firedancer_tpu.tiles.shred import ShredTile
    from firedancer_tpu.tiles.sink import SinkTile, read_siglog

    rng = np.random.default_rng(29)
    payloads = [
        np.frombuffer(
            bytes(rng.integers(0, 256, 160, np.uint8)), np.uint8
        ).copy()
        for _ in range(n_mbs)
    ]
    topo = Topology(
        name=f"esmoke{os.getpid()}_{runtime[:4]}", runtime=runtime,
    )
    topo.link("fb", depth=256, mtu=256)
    topo.link("poh_shred", depth=1 << 12, mtu=ENTRY_SZ)
    topo.link("shred_sink", depth=1 << 12, mtu=SH.MAX_SZ)
    topo.tile(_MbFeeder(payloads), outs=["fb"])
    # free-running clock with short slots so boundaries (and therefore
    # FEC sets) occur continuously during the smoke window
    topo.tile(
        PohTile(tick_batch=8, ticks_per_slot=32, slot_ms=0),
        ins=[("fb", True)], outs=["poh_shred"],
    )
    topo.tile(
        ShredTile(signer=_egress_signer),
        ins=[("poh_shred", True)], outs=["shred_sink"],
    )
    topo.tile(SinkTile(shm_log=1 << 14), ins=[("shred_sink", True)])
    out: dict = {"runtime": runtime, "stem": stem, "ok": False}
    topo.build()
    topo.start(batch_max=256, boot_timeout_s=600.0, stem=stem)
    try:
        mpoh = topo.metrics("poh")
        msh = topo.metrics("shred")
        deadline = time.perf_counter() + deadline_s
        while time.perf_counter() < deadline:
            topo.poll_failure()
            if (
                mpoh.counter("mixins") >= n_mbs
                and topo.metrics("sink").counter("in_frags") >= 40
            ):
                break
            time.sleep(0.02)
        topo.halt()
        tags = read_siglog(topo.tile_alloc_view("sink", "siglog"))
        out.update(
            egress_mixins=mpoh.counter("mixins"),
            egress_entries=mpoh.counter("entries"),
            egress_shreds=len(tags),
            egress_stem_frags=(
                mpoh.counter("stem_frags") + msh.counter("stem_frags")
            ),
            ok=(
                mpoh.counter("mixins") == n_mbs
                and len(tags) >= 40
                # exactly-once at the shred layer: no duplicate
                # (slot, idx) tag ever lands
                and len(set(tags.tolist())) == len(tags)
                and (stem != "native"
                     or (mpoh.counter("stem_frags") > 0
                         and msh.counter("stem_frags") > 0))
            ),
        )
    finally:
        topo.close()
    leaked = glob.glob(f"/dev/shm/fdt_wksp_{topo.name}*")
    out["shm_leak"] = leaked
    if leaked:
        out["ok"] = False
    return out


def run_relay_ab(
    runtime: str,
    n_chains: int = 2,
    total: int = 200_000,
    deadline_s: float = 180.0,
) -> dict:
    """Parallel relay chains, profiled: every tile's per-iteration work
    is Python/tango bytecode (no numpy heavy ops that would release the
    GIL), so the threaded runtime serializes the chains on the
    interpreter while the process runtime runs them on real cores.
    idle_sleep is coarsened to 1 ms: the loop's default 50 µs sleep-spin
    is GIL-throttled under threads but burns REAL cores as processes —
    idle wakeup rate is a bench knob, not a protocol constant."""
    from firedancer_tpu.disco import Topology
    from firedancer_tpu.tiles import wire
    from firedancer_tpu.tiles.dedup import DedupTile
    from firedancer_tpu.tiles.sink import SinkTile
    from firedancer_tpu.tiles.synth import SynthTile, make_txn_pool

    pool_n = 256
    rows, szs, _ = make_txn_pool(pool_n, seed=7)
    topo = Topology(name=f"ab{os.getpid()}_{runtime[:4]}", runtime=runtime)
    topo.enable_profile()
    for c in range(n_chains):
        topo.link(f"s{c}", depth=1 << 12, mtu=wire.LINK_MTU)
        topo.link(f"d{c}", depth=1 << 12, mtu=wire.LINK_MTU)
        topo.tile(
            SynthTile(rows, szs, total=total, name=f"synth{c}"),
            outs=[f"s{c}"],
        )
        topo.tile(
            DedupTile(depth=1 << 20, name=f"dedup{c}"),
            ins=[(f"s{c}", True)], outs=[f"d{c}"],
        )
        topo.tile(SinkTile(name=f"sink{c}"), ins=[(f"d{c}", True)])
    out: dict = {"runtime": runtime, "chains": n_chains, "ok": False}
    topo.build()
    topo.start(batch_max=1024, boot_timeout_s=600.0, idle_sleep_s=1e-3)
    try:
        t0 = time.perf_counter()
        deadline = t0 + deadline_s
        while time.perf_counter() < deadline:
            topo.poll_failure()
            if all(
                topo.metrics(f"dedup{c}").counter("in_frags") >= total
                for c in range(n_chains)
            ):
                break
            time.sleep(0.02)
        dt = time.perf_counter() - t0
        from firedancer_tpu.disco.profile import aggregate

        agg = aggregate(topo.profile_metrics())
        topo.halt()
        done = sum(
            topo.metrics(f"dedup{c}").counter("in_frags")
            for c in range(n_chains)
        )
        out.update(
            tps=round(done / dt, 1),
            gil_wait_frac=agg["gil_wait_frac"],
            sched_lag_p99_us=agg["sched_lag_p99_us"],
            ok=done >= n_chains * total,
        )
    finally:
        topo.close()
    leaked = glob.glob(f"/dev/shm/fdt_wksp_{topo.name}*")
    out["shm_leak"] = leaked
    if leaked:
        out["ok"] = False
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="proc_smoke", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--runtime", default="process",
                    choices=["thread", "process"])
    ap.add_argument("--txns", type=int, default=2048)
    ap.add_argument("--stem", default="python",
                    choices=["python", "native"],
                    help="data-plane inner loop: native = GIL-released "
                         "fdt_stem bursts on tiles with a registered "
                         "handler (ISSUE 10 combined smoke)")
    ap.add_argument("--repeat", type=int, default=2)
    ap.add_argument("--ab", action="store_true",
                    help="run BOTH runtimes with profiling; print the "
                         "gil_wait/sched_lag/tps A/B")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    if args.ab:
        doc = {
            rt: run_relay_ab(rt) for rt in ("thread", "process")
        }
        t, p = doc["thread"], doc["process"]
        doc["speedup"] = (
            round(p["tps"] / t["tps"], 2) if t.get("tps") else None
        )
        doc["ok"] = t["ok"] and p["ok"]
        if args.json:
            print(json.dumps(doc, sort_keys=True))
        else:
            for rt in ("thread", "process"):
                r = doc[rt]
                print(
                    f"{rt:>8}: tps={r['tps']:,.0f} "
                    f"gil_wait_frac={r.get('gil_wait_frac')} "
                    f"sched_lag_p99_us={r.get('sched_lag_p99_us'):,.0f} "
                    f"ok={r['ok']}"
                )
            print(f"speedup: {doc['speedup']}x")
        return 0 if doc["ok"] else 1

    r = run_pipeline(
        args.runtime, n_txns=args.txns, repeat=args.repeat,
        stem=args.stem,
    )
    # pack-scheduler leg (ISSUE 11): insert -> schedule -> complete,
    # exactly once, under the same runtime/stem combination
    pr = run_pack_pipeline(args.runtime, stem=args.stem)
    for k in ("pack_inserted", "pack_mbs", "pack_mb_txns",
              "pack_completions", "pack_stem_frags"):
        r[k] = pr.get(k)
    r["pack_ok"] = pr["ok"]
    r["ok"] = r["ok"] and pr["ok"]
    if pr["shm_leak"]:
        r["shm_leak"] = r["shm_leak"] + pr["shm_leak"]
    # block-egress leg (ISSUE 12): feeder -> poh -> shred -> sink,
    # exactly-once mixins + unique shred tags, same runtime/stem combo
    er = run_egress_pipeline(args.runtime, stem=args.stem)
    for k in ("egress_mixins", "egress_entries", "egress_shreds",
              "egress_stem_frags"):
        r[k] = er.get(k)
    r["egress_ok"] = er["ok"]
    r["ok"] = r["ok"] and er["ok"]
    if er["shm_leak"]:
        r["shm_leak"] = r["shm_leak"] + er["shm_leak"]
    if args.json:
        print(json.dumps(r, sort_keys=True))
    else:
        print(
            f"proc_smoke [{r['runtime']}/{r['stem']}]: "
            f"{'ok' if r['ok'] else 'FAILED'} — landed {r['landed']} "
            f"({r['unique']} unique of {args.txns}) at {r['tps']:,.0f} "
            f"frags/s, pack {r['pack_mbs']} mbs/"
            f"{r['pack_completions']} comp, egress "
            f"{r['egress_mixins']} mixins/{r['egress_shreds']} shreds, "
            f"boot {r['boot_s']}s, leak={r['shm_leak']}"
        )
    return 0 if r["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
