"""Declarative topology: links + tiles + workspace layout + a runner.

Reference model: src/disco/topo/fd_topo.h:28-230 (fd_topo_t = wksps,
links, tiles, objs; built by fd_topob_*) and fd_topo_run.c (join
workspaces → init → run loop).  The reference runs each tile as a
sandboxed PROCESS over hugetlbfs shared memory (fd_topo_run_tile_t);
this build supports both shapes over the same /dev/shm-backed objects:

  * runtime="thread" (default): one thread per tile in one interpreter
    — the shape the reference's own tests use (e.g.
    src/disco/dedup/test_dedup.c:654-660), bit-identical to the
    pre-process-runtime behavior, and what tier-1 runs.
  * runtime="process": one OS process per tile.  The parent builds the
    named workspace and publishes a boot manifest; each child
    re-attaches via tango.rings.Workspace.attach(), rebinds its
    mcache/dcache/fseq/cnc views and metrics/trace/profile regions by
    manifest name, and enters the same disco/mux.py run loop unchanged
    — the ring protocol is process-safe (fdtmc-verified, PR 3).  The
    control plane (boot acks, heartbeats, incarnation, boot-vs-run
    failure classification) lives entirely in shared-memory words
    (cnc + a per-tile pstat region), so the supervisor can watchdog,
    SIGKILL, and in-place restart a child with the same rejoin
    discipline as thread restarts.  This is what escapes the GIL: with
    more tile threads than the one interpreter lock can serve, a tile
    spends most of its non-sleeping wall time runnable but not running
    (disco/profile.py's gil_wait_frac is that share).

Runtime selection: Topology(runtime=...) / start(mode=...) >
FDT_RUNTIME env > "thread".  Observer tiles that close over parent
state (metric/rpc) declare proc_safe=False and stay threads in the
parent even in process mode — they only read shared memory.

Fail-stop supervision mirrors run/run.c:264-270: any tile failure halts
the whole topology.
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from firedancer_tpu.tango import rings as R

from .metrics import Metrics, MetricsSchema
from .mux import InLink, MuxCtx, OutLink, Tile, link_hist_names, run_loop
from .trace import SpanRing, TraceConfig, Tracer

#: per-tile process-control shm words ("pstat" region): the control
#: plane a child and its parent share beyond the cnc.  Single writer
#: per word: the parent owns INCARNATION (set before each spawn), the
#: child owns PID and BOOTED (its crash handler records whether
#: on_boot had completed, so the parent can classify FAIL as a
#: construction error vs a post-RUN crash without any Python-object
#: channel).
PSTAT_INCARNATION, PSTAT_PID, PSTAT_BOOTED = 0, 1, 2
#: elastic retirement (disco/elastic.py): the epoch a retired member
#: completed its drain at, mirrored here by the PARENT after it
#: observes the member's canonical drained marker in the shard-map
#: region (the region is the cross-runtime home; pstat exists only
#: under the process runtime)
PSTAT_DRAINED = 3
_PSTAT_BYTES = 64
#: per-tile faultinj cumulative-trigger state (TileFaults.bind_shm):
#: 2 counter words + up to 62 per-fault fired flags
_FSTAT_BYTES = 512

#: hot upgrade: serializes the scoped sys.path/FDT_SO_PATH mutation a
#: version-carrying spawn performs around Process.start (the spawn
#: method snapshots both into the child)
_SPAWN_ENV_LOCK = threading.Lock()


def _err_path(wksp_name: str, tile: str) -> str:
    """Child-crash report sidecar: the process analog of TileSpec.error
    (a traceback cannot cross the process boundary as an object)."""
    return f"/dev/shm/fdt_wksp_{wksp_name}.err_{tile}"


def _read_err(wksp_name: str | None, tile: str) -> str:
    if wksp_name is None:
        return ""
    try:
        with open(_err_path(wksp_name, tile)) as f:
            return f.read()[-4000:]
    except OSError:
        return ""


def device_assignments(spec, n_tiles: int) -> list[list[int]]:
    """Partition a `verify_devices` spec (auto | N | [ordinals]) across
    n_tiles seq-sharded verify replicas.

    Each replica gets a DISJOINT device-ordinal list so two workers
    never contend for one accelerator (the reference pins each
    wiredancer lane to one FPGA slot for the same reason).  With fewer
    devices than replicas the devices are shared round-robin.  Sharing
    is valid only between THREADS of one process: an accelerator
    belongs to one process at a time, so under the process runtime
    Topology.build refuses two tile processes on one real ordinal
    (_check_device_owners).  "auto" needs the local inventory at build
    time (the partition needs the count); hostdev.local_device_count
    takes it in a child that exits, so a process-runtime parent stays
    off the backend.  Host-only topologies should pass an explicit
    spec, not "auto".
    """
    assert n_tiles >= 1
    if spec in (None, 1, "off"):
        return [[0] for _ in range(n_tiles)]
    if spec == "auto":
        from firedancer_tpu.utils.hostdev import local_device_count

        indices = list(range(local_device_count()))
    elif isinstance(spec, int):
        indices = list(range(max(spec, 1)))
    else:
        indices = [int(d) for d in spec] or [0]
    if len(indices) < n_tiles:
        return [[indices[i % len(indices)]] for i in range(n_tiles)]
    return [indices[i::n_tiles] for i in range(n_tiles)]


@dataclass
class LinkSpec:
    name: str
    depth: int
    mtu: int = 0  # 0 = metadata-only link (no dcache)
    producer: str | None = None
    consumers: list[tuple[str, bool]] = field(default_factory=list)


@dataclass
class TileSpec:
    tile: Tile
    ins: list[tuple[str, bool]]  # (link name, reliable)
    outs: list[str]
    ctx: MuxCtx | None = None
    thread: threading.Thread | None = None
    #: process runtime: the tile's child process (multiprocessing
    #: handle).  None for thread tiles and proc_safe=False observers.
    proc: object | None = None
    error: BaseException | None = None
    #: elastic topology (disco/elastic.py): False = a PROVISIONED but
    #: inactive shard member — its rings/metrics/cnc exist (layout is
    #: fixed at build), but it is not spawned, supervised, or halted
    #: until Topology.add_shard activates it.  Its reliable in-fseqs
    #: are parked in the far seq future so producers never gate on it.
    active: bool = True
    #: hot code upgrade (fdt_upgrade): when set, this tile's NEXT
    #: process-runtime incarnation imports firedancer_tpu from this
    #: module tree (prepended to the child's sys.path at spawn) and/or
    #: loads this prebuilt native artifact (FDT_SO_PATH) instead of
    #: rebuilding.  None = the parent's own tree/.so.  Thread tiles
    #: cannot swap module trees (one interpreter); their hot upgrade is
    #: the mutate-based tile-object swap.
    version_root: str | None = None
    so_path: str | None = None


class UpgradeRefused(RuntimeError):
    """hot_upgrade pre-flight: the candidate version's ABI digest is
    neither the workspace word nor compat-approved — the running tile
    was NOT touched (zero downtime on refusal)."""

    def __init__(self, shm_digest: int, new_digest: int, tile: str):
        self.shm_digest = shm_digest
        self.new_digest = new_digest
        self.tile = tile
        super().__init__(
            f"hot upgrade of {tile!r} refused: candidate ABI digest "
            f"{new_digest:#018x} vs workspace {shm_digest:#018x} — "
            f"approve_version() it after an out-of-band compatibility "
            f"proof, or rebuild from a ring-compatible tree"
        )


class UpgradeRolledBack(RuntimeError):
    """hot_upgrade: the new-version incarnation failed to reach RUN;
    the tile was respawned on its OLD recipe (which reached RUN before
    this raised).  `cause` is the new version's boot failure."""

    def __init__(self, tile: str, cause: BaseException):
        self.tile = tile
        self.cause = cause
        super().__init__(
            f"hot upgrade of {tile!r} rolled back to the old "
            f"incarnation recipe: new version failed to boot ({cause!r})"
        )


class Topology:
    """Build links and tiles, then run them on threads.

    Usage:
        topo = Topology()
        topo.link("synth_verify", depth=1024, mtu=1280)
        topo.tile(SynthTile(...), outs=["synth_verify"])
        topo.tile(VerifyTile(...), ins=[("synth_verify", True)], outs=[...])
        topo.start(); ...; topo.halt()
    """

    def __init__(
        self,
        name: str | None = None,
        trace: TraceConfig | None = None,
        runtime: str | None = None,
        stem: str | None = None,
    ):
        self.name = name
        #: tile runtime: "thread" | "process" | None (resolve from the
        #: FDT_RUNTIME env at build/start).  Must be settled before
        #: build() — the process runtime adds workspace regions
        #: (per-tile arenas/pstat, per-dcache shm cursors).
        self.runtime = runtime
        #: data-plane inner loop: "python" | "native" | None (resolve
        #: from the FDT_STEM env at start).  "native" lets tiles with a
        #: registered native handler (Tile.native_handler) run their
        #: drain→handle→publish cycle in one GIL-released fdt_stem call
        #: per burst; tiles without one keep the Python loop either way.
        self.stem = stem
        self._runtime: str | None = None  # resolved at build()
        #: process runtime: fault-injection schedule that rides the
        #: spawn args so children reconstruct IDENTICAL injector
        #: behavior deterministically — (seed, [Fault, ...]).  Set by
        #: Supervisor.start (from its FaultInjector) or directly by
        #: chaos harnesses.
        self.faults_spec: tuple[int, list] | None = None
        #: loop kwargs captured at start() so the supervisor can
        #: respawn children with identical run-loop parameters
        self._loop_kw: dict = {}
        self.links: dict[str, LinkSpec] = {}
        self.tiles: dict[str, TileSpec] = {}
        self.wksp: R.Workspace | None = None
        # sample <= 0 means OFF (TraceConfig contract) — normalize here
        # so build() installs no tracer regardless of which entry point
        # (constructor arg or enable_trace) carried the config in
        self.trace = trace if trace is not None and trace.sample > 0 else None
        #: run-loop profiling (disco/profile.py): None = off; set via
        #: enable_profile() before build()
        self.profile = None
        #: flight recorder black boxes (disco/flight.py): None = off;
        #: set via enable_flight() before build()
        self.flight = None
        #: asserted SLOs (disco/slo.py SloConfig): None = none asserted.
        #: When set before build(), a shared `slo` gauge region is
        #: allocated (metrics_registry()["slo"]) and the config rides
        #: the manifest so attached monitors evaluate the same SLOs.
        self.slo = None
        #: elastic shard groups (disco/elastic.py): kind -> {"slot",
        #: "members" (tile names, member-index order), "producer",
        #: "base_active"}.  Declared via declare_shards() before build().
        self._shard_groups: dict[str, dict] = {}
        self._shardmap = None  # elastic.ShardMap, bound at build
        self._handshake = None  # handshake.Handshake, bound at build
        self._mcaches: dict[str, R.MCache] = {}
        self._dcaches: dict[str, R.DCache] = {}
        self._fseqs: dict[tuple[str, str], R.FSeq] = {}
        self._cncs: dict[str, R.CNC] = {}
        self._metrics: dict[str, Metrics] = {}
        self._schemas: dict[str, MetricsSchema] = {}
        self._tracers: dict[str, Tracer] = {}
        self._profilers: dict = {}
        self._flightboxes: dict = {}

    def enable_trace(self, sample: int = 64, depth: int = 1 << 14) -> None:
        """Turn on fdttrace span rings for every tile (must run before
        build()).  sample <= 0 disables — no tracer is installed and
        the hot path pays only the per-phase None checks."""
        assert self.wksp is None, "enable_trace before build()"
        self.trace = (
            TraceConfig(sample=sample, depth=depth) if sample > 0 else None
        )

    def enable_profile(self, on: bool = True) -> None:
        """Turn on the per-tile run-loop profiler (disco/profile.py):
        sampled wall/CPU phase attribution, GIL-wait fraction, and the
        scheduler-lag histogram, in per-tile workspace regions.  Must
        run before build(); off = one None check per loop hook."""
        assert self.wksp is None, "enable_profile before build()"
        self.profile = True if on else None

    def enable_flight(self, depth: int = 64, timeline_n: int = 256) -> None:
        """Allocate per-tile flight-recorder black boxes
        (disco/flight.py BlackBox) in the workspace.  Must run before
        build().  The boxes are written by a FlightRecorder's watcher
        thread, not by the tiles — enabling this costs the hot path
        nothing."""
        assert self.wksp is None, "enable_flight before build()"
        from .flight import FlightConfig

        self.flight = FlightConfig(depth=depth, timeline_n=timeline_n)

    # ---- declaration ----------------------------------------------------

    def link(self, name: str, depth: int, mtu: int = 0) -> None:
        assert name not in self.links, f"duplicate link {name!r}"
        self.links[name] = LinkSpec(name, depth, mtu)

    def tile(
        self,
        tile: Tile,
        ins: list[tuple[str, bool]] | None = None,
        outs: list[str] | None = None,
    ) -> None:
        name = tile.name
        assert name not in self.tiles, f"duplicate tile {name!r}"
        ins = list(ins or [])
        outs = list(outs or [])
        for ln, reliable in ins:
            self.links[ln].consumers.append((name, reliable))
        for ln in outs:
            spec = self.links[ln]
            assert spec.producer is None, f"link {ln!r} has two producers"
            spec.producer = name
        self.tiles[name] = TileSpec(tile, ins, outs)

    def declare_shards(
        self,
        kind: str,
        members: list[str],
        *,
        producer: str | None = None,
        producer_link: str | None = None,
        member_links: list[str] | None = None,
        active: int | None = None,
    ) -> None:
        """Declare an elastic shard group (disco/elastic.py): `members`
        are already-declared tiles, in member-index order; the first
        `active` (default: all) start live, the rest are PROVISIONED
        (rings and metrics built, fseqs parked) but not spawned until
        add_shard().

        producer/producer_link: the seq-sharded link's single producer
        tile and the link it writes — it appends flip-journal entries
        at every epoch it observes, which is what makes assignment a
        pure function of (seq, journal) across a membership flip.
        Omit both for producer-ASSIGNED kinds (bank shards: pack picks
        the out ring, so the mask alone gates the scheduler — pass
        `producer` without a link so it still acks epochs).

        member_links: each member's sharded in-link (default: the
        producer_link for every member — the quic_verify shape)."""
        from .elastic import MAX_KINDS, MAX_MEMBERS, ElasticBinding

        assert self.wksp is None, "declare_shards before build()"
        assert kind not in self._shard_groups, f"duplicate kind {kind!r}"
        assert len(members) <= MAX_MEMBERS
        slot = len(self._shard_groups)
        assert slot < MAX_KINDS
        n_active = len(members) if active is None else int(active)
        assert 1 <= n_active <= len(members)
        if member_links is None:
            member_links = [producer_link] * len(members)
        for i, name in enumerate(members):
            ts = self.tiles[name]
            assert getattr(ts.tile, "elastic", None) is None, (
                f"tile {name!r} already bound to a shard kind"
            )
            ts.tile.elastic = ElasticBinding(
                kind, slot, "member", index=i, link=member_links[i],
                base_active=n_active,
            )
            if i >= n_active:
                ts.active = False
        if producer is not None:
            pt = self.tiles[producer].tile
            assert getattr(pt, "elastic", None) is None, (
                f"tile {producer!r} already bound to a shard kind"
            )
            pt.elastic = ElasticBinding(
                kind, slot, "producer", link=producer_link,
                base_active=n_active,
            )
        self._shard_groups[kind] = {
            "slot": slot,
            "members": list(members),
            "producer": producer,
            "base_active": n_active,
        }

    def shardmap(self):
        """The built topology's elastic.ShardMap view (parent side)."""
        assert self._shardmap is not None, "no shard groups declared"
        return self._shardmap

    # ---- build ----------------------------------------------------------

    def _resolve_runtime(self, mode: str | None = None) -> str:
        rt = mode or self.runtime or os.environ.get("FDT_RUNTIME") or "thread"
        if rt not in ("thread", "process"):
            raise ValueError(
                f"unknown tile runtime {rt!r} (thread|process; from "
                f"start(mode=), Topology(runtime=), or FDT_RUNTIME)"
            )
        return rt

    def _resolve_stem(self, mode: str | None = None) -> str:
        sm = mode or self.stem or os.environ.get("FDT_STEM") or "python"
        if sm not in ("python", "native"):
            raise ValueError(
                f"unknown stem mode {sm!r} (python|native; from "
                f"start(stem=), Topology(stem=), or FDT_STEM)"
            )
        return sm

    @staticmethod
    def _spawn_method() -> str:
        """multiprocessing start method for tile children.  Default
        "spawn": a pristine interpreter per tile — no inherited GIL
        state, locks, or jax runtime; the child reconstructs everything
        from the manifest + pickled TileSpec, which is exactly what the
        fdtlint proc-safe-tile rule keeps honest.  FDT_SPAWN=fork opts
        into fork for import-cost-sensitive hosts (unsafe if the parent
        already initialized a device runtime)."""
        return os.environ.get("FDT_SPAWN", "spawn")

    def _check_device_owners(self) -> None:
        """One process per chip: under the process runtime every
        boot-active tile that dispatches to a real accelerator
        (Tile.device_ordinals) must own its ordinals alone.  A second
        process on the same chip does not contend for it — it fails or
        hangs at boot — so the misconfiguration is refused HERE, with a
        sentence.  On a CPU-pinned platform (tests, rehearsals) the
        devices are virtual and processes may share them."""
        from firedancer_tpu.utils.hostdev import cpu_pinned

        if cpu_pinned():
            return
        owner: dict[int, str] = {}
        for name, ts in self.tiles.items():
            if not (ts.active and ts.tile.proc_safe):
                continue
            for d in ts.tile.device_ordinals():
                if d in owner:
                    raise ValueError(
                        f"tiles {owner[d]!r} and {name!r} are both "
                        f"assigned accelerator {d}: under the process "
                        f"runtime each tile is its own process and a "
                        f"chip belongs to one process at a time — give "
                        f"each verify replica its own [tiles.verify] "
                        f"devices ordinals, or run one replica"
                    )
                owner[d] = name

    def _tile_schema(self, ts: TileSpec) -> MetricsSchema:
        """The tile's own schema + base + the per-in-link latency
        attribution hists (qwait/svc/e2e per consumed link) the run
        loop records.  Everything that reads a tile's metrics region —
        build, manifest export, monitor, metric tile — must agree on
        this one layout.

        The link hists are WIDE (WIDE_HIST_BUCKETS + explicit overflow
        bucket, ISSUE 15): the 16-bucket domain capped every latency
        SLO at 2^16 µs (the documented fdtflight observability bound) —
        an e2e or queue-wait ceiling above 65.5 ms was rejected as
        unobservable.  slo.py derives its ceiling bound from the
        storage, so widening here lifts the bound to 2^24 µs (~16.8 s)
        with the overflow bucket catching the rest."""
        base = ts.tile.schema.with_base()
        link_hists = tuple(
            h for ln, _rel in ts.ins for h in link_hist_names(ln)
        )
        return MetricsSchema(
            base.counters, base.hists + link_hists,
            wide_hists=base.wide_hists + link_hists,
        )

    def _shared_regions(self) -> dict[str, int]:
        """Topology-wide shared regions declared by tiles
        (Tile.shared_wksp_footprints): {name: footprint}.  Tiles naming
        the same region must agree on its size — the whole point is
        that every bank shard maps ONE account table."""
        shared: dict[str, int] = {}
        for name, ts in self.tiles.items():
            for nm, fp in ts.tile.shared_wksp_footprints().items():
                if nm in shared and shared[nm] != fp:
                    raise ValueError(
                        f"shared region {nm!r}: tile {name!r} declares "
                        f"footprint {fp} but another tile declared "
                        f"{shared[nm]} (shards must agree)"
                    )
                shared[nm] = fp
        return shared

    def _footprint(self) -> int:
        from .handshake import HANDSHAKE_FOOTPRINT

        # version-handshake word region (every topology has one)
        total = 4096 + HANDSHAKE_FOOTPRINT + 256
        for fp in self._shared_regions().values():
            total += fp + 256
        for ls in self.links.values():
            total += R.MCache.footprint(ls.depth) + 256
            if ls.mtu:
                total += R.DCache.footprint(ls.mtu, ls.depth) + 256
            total += (R.FSeq.footprint() + 128) * max(len(ls.consumers), 1)
        for ts in self.tiles.values():
            total += R.CNC.footprint() + 128
            total += Metrics.footprint(self._tile_schema(ts)) + 256
            if not (self._runtime == "process" and ts.tile.proc_safe):
                # process-runtime children allocate tile state from
                # their arena (budgeted below), not the workspace —
                # budgeting both would double-size /dev/shm
                total += ts.tile.wksp_footprint() + 256
            if self.trace is not None:
                total += SpanRing.footprint(self.trace.depth) + 256
            if self.profile is not None:
                from .profile import PROFILE_SCHEMA

                total += Metrics.footprint(PROFILE_SCHEMA) + 256
            if self.flight is not None:
                from .flight import BlackBox, box_rec_words

                total += BlackBox.footprint(
                    self.flight.depth,
                    box_rec_words(len(ts.ins), len(ts.outs)),
                ) + 256
        if self.slo is not None:
            from .slo import slo_metrics_schema

            total += Metrics.footprint(slo_metrics_schema(self.slo)) + 256
        if self._shard_groups:
            from .elastic import SHARDMAP_FOOTPRINT, elastic_metrics_schema

            total += SHARDMAP_FOOTPRINT + 256
            total += Metrics.footprint(
                elastic_metrics_schema(list(self._shard_groups))
            ) + 256
        if self._runtime == "process":
            # process-runtime control plane + child-side allocation
            # arenas (ctx.alloc cannot bump an attached workspace).
            # proc_safe=False observers stay parent threads and use
            # none of it — budgeting theirs would just waste /dev/shm.
            for ls in self.links.values():
                if ls.mtu:
                    total += 64 + 128  # shm dcache cursor word
            for ts in self.tiles.values():
                if not ts.tile.proc_safe:
                    continue
                total += _PSTAT_BYTES + 128
                total += _FSTAT_BYTES + 128
                total += R.WkspArena.footprint(ts.tile.wksp_footprint())
                total += 256
        return total

    def build(self, runtime: str | None = None) -> None:
        assert self.wksp is None, "already built"
        self._runtime = self._resolve_runtime(runtime)
        if self._runtime == "process":
            self._check_device_owners()
            if self.name is None:
                # children attach by name; auto-name anonymous topologies
                self.name = f"p{os.getpid()}_{os.urandom(3).hex()}"
        self.wksp = R.Workspace(self._footprint(), name=self.name)
        for ls in self.links.values():
            self._mcaches[ls.name] = R.MCache.create(
                self.wksp, f"mc_{ls.name}", ls.depth
            )
            if ls.mtu:
                self._dcaches[ls.name] = R.DCache.create(
                    self.wksp, f"dc_{ls.name}", ls.mtu, ls.depth
                )
            for cons, _rel in ls.consumers:
                self._fseqs[(ls.name, cons)] = R.FSeq.create(
                    self.wksp, f"fs_{ls.name}_{cons}"
                )
        if self._runtime == "process":
            # shm-backed dcache producer cursors: a restarted producer
            # CHILD must resume at its published chunk, not rewind to 0
            # over payloads in-flight frags still reference (thread
            # restarts keep the DCache object, so only the process
            # runtime needs the shared word)
            for ls in self.links.values():
                if ls.mtu:
                    self._dcaches[ls.name].bind_cursor(
                        self.wksp.alloc(f"dcur_{ls.name}", 64, align=64)
                    )
        # topology-wide shared regions (bank account table): allocated
        # HERE, before any tile boots and before the directory publish,
        # so process-runtime children can join them by name (an attached
        # workspace resolves, never allocates)
        for nm, fp in sorted(self._shared_regions().items()):
            self.wksp.alloc(f"shared_{nm}", fp)
        # version-handshake word (disco/handshake.py): written ONCE by
        # the building tree with its own ring-ABI digest, read by every
        # joining incarnation before it binds a ring.  Allocated before
        # any tile boots so process children can join it by name.
        from .handshake import HANDSHAKE_FOOTPRINT, Handshake

        self._handshake = Handshake(
            self.wksp.alloc("shared_handshake", HANDSHAKE_FOOTPRINT),
            join=False,
        )
        self._handshake.init(R.abi_digest())
        if self._shard_groups:
            # elastic shard map + gauge region: allocated before any
            # tile boots (children join both by name), initialized
            # before the first spawn so every epoch observer sees a
            # complete header
            from .elastic import (
                SHARDMAP_FOOTPRINT, ShardMap, elastic_metrics_schema,
            )

            self._shardmap = ShardMap(
                self.wksp.alloc("shared_shardmap", SHARDMAP_FOOTPRINT),
                join=False,
            )
            for kind, grp in self._shard_groups.items():
                mask = (1 << grp["base_active"]) - 1
                self._shardmap.init_kind(
                    grp["slot"], len(grp["members"]), mask
                )
            eschema = elastic_metrics_schema(list(self._shard_groups))
            emem = self.wksp.alloc(
                "metrics_elastic", Metrics.footprint(eschema)
            )
            # a pseudo-tile region like "slo": the metric tile renders
            # it as fdt_elastic_* gauges; parent-side reconfig code
            # (topology ops + ElasticController) is the single writer
            self._metrics["elastic"] = Metrics(emem, eschema)
            # park every inactive member's reliable in-fseqs in the far
            # seq future: cr_avail reads a consumer AHEAD of the
            # producer as fresh credit, so a provisioned-but-idle
            # member never backpressures the link, and activation lands
            # at the live head via consumer_rejoin's wrap-safe min
            for grp in self._shard_groups.values():
                for i, name in enumerate(grp["members"]):
                    if not self.tiles[name].active:
                        self._park_member_fseqs(name)
        # link ids: declaration-order small ints, shared with the span
        # events (u8 field) and the manifest's id -> name table
        link_ids = {ln: i for i, ln in enumerate(self.links)}
        assert len(link_ids) <= 256, "span events carry a u8 link id"
        for name, ts in self.tiles.items():
            self._cncs[name] = R.CNC.create(self.wksp, f"cnc_{name}")
            schema = self._tile_schema(ts)
            self._schemas[name] = schema
            mem = self.wksp.alloc(f"metrics_{name}", Metrics.footprint(schema))
            self._metrics[name] = Metrics(mem, schema)
            if self.trace is not None:
                ring = SpanRing(
                    self.wksp.alloc(
                        f"trace_{name}", SpanRing.footprint(self.trace.depth)
                    ),
                    self.trace.depth,
                    self.trace.sample,
                )
                self._tracers[name] = Tracer(
                    ring, self.trace.sample, name=name
                )
            if self.profile is not None:
                from .profile import PROFILE_SCHEMA, TileProfiler

                pmem = self.wksp.alloc(
                    f"profile_{name}", Metrics.footprint(PROFILE_SCHEMA)
                )
                self._profilers[name] = TileProfiler(
                    Metrics(pmem, PROFILE_SCHEMA)
                )
            if self.flight is not None:
                from .flight import BlackBox, box_rec_words

                rw = box_rec_words(len(ts.ins), len(ts.outs))
                bmem = self.wksp.alloc(
                    f"flight_{name}",
                    BlackBox.footprint(self.flight.depth, rw),
                )
                self._flightboxes[name] = BlackBox(
                    bmem, self.flight.depth, rw
                )
        if self._runtime == "process":
            for name, ts in self.tiles.items():
                if not ts.tile.proc_safe:
                    continue  # parent-thread observers use the wksp path
                self.wksp.alloc(f"pstat_{name}", _PSTAT_BYTES, align=64)
                # cumulative faultinj trigger state (ticks/frags/fired
                # flags) — survives child restarts so scripted faults
                # fire once, as in the threaded runtime
                self.wksp.alloc(f"fstat_{name}", _FSTAT_BYTES, align=64)
                self.wksp.alloc(
                    f"arena_{name}",
                    R.WkspArena.footprint(ts.tile.wksp_footprint()),
                )
        if self.slo is not None:
            from .slo import slo_metrics_schema

            sschema = slo_metrics_schema(self.slo)
            smem = self.wksp.alloc(
                "metrics_slo", Metrics.footprint(sschema)
            )
            # a pseudo-tile entry: the Prometheus metric tile renders it
            # as fdt_slo_* gauges; the flight recorder's watcher is the
            # single writer
            self._metrics["slo"] = Metrics(smem, sschema)
        for name, ts in self.tiles.items():
            tracer = self._tracers.get(name)
            ins = [
                InLink(
                    ln,
                    self._mcaches[ln],
                    self._dcaches.get(ln),
                    self._fseqs[(ln, name)],
                    reliable,
                    link_id=link_ids[ln],
                    h_qwait=f"qwait_us_{ln}",
                    h_svc=f"svc_us_{ln}",
                    h_e2e=f"e2e_us_{ln}",
                )
                for ln, reliable in ts.ins
            ]
            outs = [
                OutLink(
                    ln,
                    self._mcaches[ln],
                    self._dcaches.get(ln),
                    [
                        self._fseqs[(ln, cons)]
                        for cons, rel in self.links[ln].consumers
                        if rel
                    ],
                    link_id=link_ids[ln],
                    tracer=tracer,
                )
                for ln in ts.outs
            ]
            ts.ctx = MuxCtx(
                name, self._cncs[name], ins, outs, self._metrics[name],
                wksp=self.wksp,
            )
            ts.ctx.tracer = tracer
            ts.ctx.profiler = self._profilers.get(name)

    def export_manifest(self) -> None:
        """Publish the workspace directory + a monitor manifest (tile
        schemas, metrics/cnc alloc names, link fseq names) so a separate
        process can attach and observe (app/monitor.py).  No-op for
        anonymous (in-process) workspaces."""
        if self.wksp is None or self.wksp.name is None:
            return
        tiles = {}
        for name, ts in self.tiles.items():
            schema = self._schemas.get(name) or self._tile_schema(ts)
            tiles[name] = {
                "metrics": f"metrics_{name}",
                "cnc": f"cnc_{name}",
                "counters": list(schema.counters),
                "hists": list(schema.hists),
                # layout-affecting (wide hists store more buckets):
                # attached readers must reconstruct the same schema
                "wide_hists": list(schema.wide_hists),
                "ins": [ln for ln, _rel in ts.ins],
                "outs": list(ts.outs),
            }
        links = {
            ls.name: {
                "depth": ls.depth,
                "mcache": f"mc_{ls.name}",
                "consumers": [
                    {"tile": cons, "fseq": f"fs_{ls.name}_{cons}"}
                    for cons, _rel in ls.consumers
                ],
            }
            for ls in self.links.values()
        }
        extra = {"tiles": tiles, "links": links}
        # resolved stem mode (python|native): monitors key their
        # stem-coverage rows and the pinned-to-Python alarm off it
        extra["stem"] = self._loop_kw.get("stem") or self._resolve_stem()
        if self.trace is not None:
            # fdttrace attach surface: per-tile span ring alloc names +
            # the link id -> name table the u8 link field indexes
            extra["trace"] = {
                "sample": self.trace.sample,
                "depth": self.trace.depth,
                "links": list(self.links),
                "tiles": {name: f"trace_{name}" for name in self.tiles},
            }
        if self.profile is not None:
            # fdtflight attach surface: per-tile profiler regions
            extra["profile"] = {
                "tiles": {name: f"profile_{name}" for name in self.tiles},
            }
        if self.flight is not None:
            extra["flight"] = {
                "depth": self.flight.depth,
                "tiles": {name: f"flight_{name}" for name in self.tiles},
            }
        if self.slo is not None:
            # attached monitors evaluate the SAME objectives from the
            # same shared histograms (disco/slo.py SloEngine)
            extra["slo"] = {
                "config": self.slo.to_dict(),
                "metrics": "metrics_slo",
            }
        if self._shard_groups and self._shardmap is not None:
            # elastic attach surface: kinds, live membership, and the
            # gauge-region schema — REWRITTEN (atomic rename, see
            # publish_directory) on every add/retire so a child booting
            # mid-reconfig or an attached monitor never reads a torn
            # or stale membership table
            m = self._metrics.get("elastic")
            extra["elastic"] = {
                "metrics": "metrics_elastic",
                "counters": (
                    list(m.schema.counters) if m is not None else []
                ),
                "kinds": {
                    kind: {
                        "slot": grp["slot"],
                        "members": grp["members"],
                        "producer": grp["producer"],
                        "base_active": grp["base_active"],
                        "epoch": self._shardmap.epoch(grp["slot"]),
                        "active_mask": self._shardmap.mask(grp["slot"]),
                        "active": [
                            n
                            for j, n in enumerate(grp["members"])
                            if self.tiles[n].active
                            and (self._shardmap.mask(grp["slot"]) >> j)
                            & 1
                        ],
                    }
                    for kind, grp in self._shard_groups.items()
                },
            }
        if self._runtime == "process":
            extra["boot"] = self._boot_manifest()
        self.wksp.publish_directory(extra)

    def _boot_manifest(self) -> dict:
        """The child-side reconstruction contract: everything a spawned
        tile process needs to rebind its endpoints by name — link
        geometry (depth/mtu/ids, mcache/dcache/fseq alloc names, the
        shm dcache-cursor words), per-tile cnc/metrics/arena/pstat
        names, the flattened metrics schemas (including wide-hist
        widths — layout-affecting), and trace/profile enables.  Faultinj
        schedules and the replay window ride the spawn args instead
        (they are per-spawn, the manifest is per-build)."""
        link_ids = {ln: i for i, ln in enumerate(self.links)}
        links = {}
        for ls in self.links.values():
            links[ls.name] = {
                "id": link_ids[ls.name],
                "depth": ls.depth,
                "mtu": ls.mtu,
                "producer": ls.producer,
                "mcache": f"mc_{ls.name}",
                "dcache": f"dc_{ls.name}" if ls.mtu else None,
                "dcur": f"dcur_{ls.name}" if ls.mtu else None,
                "consumers": [
                    [cons, rel, f"fs_{ls.name}_{cons}"]
                    for cons, rel in ls.consumers
                ],
            }
        tiles = {}
        for name, ts in self.tiles.items():
            schema = self._schemas.get(name) or self._tile_schema(ts)
            proc = ts.tile.proc_safe  # observers have no child regions
            tiles[name] = {
                "ins": [[ln, rel] for ln, rel in ts.ins],
                "outs": list(ts.outs),
                "cnc": f"cnc_{name}",
                "metrics": f"metrics_{name}",
                "schema": {
                    "counters": list(schema.counters),
                    "hists": list(schema.hists),
                    "wide_hists": list(schema.wide_hists),
                },
                "arena": f"arena_{name}" if proc else None,
                "pstat": f"pstat_{name}" if proc else None,
                "fstat": f"fstat_{name}" if proc else None,
                "trace": f"trace_{name}" if self.trace is not None else None,
                "profile": (
                    f"profile_{name}" if self.profile is not None else None
                ),
                # hot upgrade: the module tree / native artifact the
                # NEXT incarnation of this tile runs (None = parent's)
                "version_root": ts.version_root,
                "so_path": ts.so_path,
            }
        return {
            "runtime": "process",
            "spawn": self._spawn_method(),
            "handshake": "shared_handshake",
            "links": links,
            "tiles": tiles,
            "trace": (
                {"sample": self.trace.sample, "depth": self.trace.depth}
                if self.trace is not None
                else None
            ),
        }

    # ---- run ------------------------------------------------------------

    def _tile_main(self, ts: TileSpec, loop_kw: dict) -> None:
        from firedancer_tpu.utils import log

        log.set_tile(ts.ctx.name)
        log.info("tile booting")
        try:
            run_loop(ts.tile, ts.ctx, **loop_kw)
            log.info("tile halted")
        except BaseException as e:  # noqa: BLE001 — fail-stop supervision
            import traceback

            log.err("tile failed: %r\n%s", e, traceback.format_exc())
            ts.error = e

    def start(
        self,
        boot_timeout_s: float = 600.0,
        mode: str | None = None,
        **loop_kw,
    ) -> None:
        # default boot budget is generous: tile on_boot warms device
        # compile caches, and first compiles are slow (tens of seconds)
        runtime = self._resolve_runtime(mode)
        if self.wksp is None:
            self.build(runtime=runtime)
        elif runtime != self._runtime:
            raise RuntimeError(
                f"topology built for runtime {self._runtime!r}; cannot "
                f"start as {runtime!r} (the process runtime changes the "
                f"workspace layout — set it before build())"
            )
        self._loop_kw = dict(loop_kw)
        # stem mode rides the loop kwargs: the same dict reaches thread
        # tiles, process children (spawn args) and supervisor respawns,
        # so every incarnation runs the same inner loop
        self._loop_kw["stem"] = self._resolve_stem(loop_kw.get("stem"))
        if runtime == "process":
            self._start_process(boot_timeout_s)
            return
        for name, ts in self.tiles.items():
            if ts.active:
                self._spawn_tile(name)
        # wait for every tile to reach RUN (or fail during boot)
        deadline = time.monotonic() + boot_timeout_s
        for name, ts in self.tiles.items():
            if not ts.active:
                continue
            while self._cncs[name].signal_query() == R.CNC_BOOT:
                if ts.error is not None:
                    self.halt()
                    raise ts.error
                if time.monotonic() > deadline:
                    self.halt()
                    raise TimeoutError(f"tile {name!r} stuck in BOOT")
                time.sleep(1e-3)
            if self._cncs[name].signal_query() == R.CNC_FAIL:
                # run_loop signals FAIL before the exception reaches
                # _tile_main, so give the error a moment to land
                if ts.thread is not None:
                    ts.thread.join(timeout=10.0)
                if not ts.ctx.booted:
                    # died DURING on_boot (bad config, missing device):
                    # that is a construction error — raise now.  A tile
                    # that reached RUN and then crashed (a race with
                    # fast-failing workloads) stays fail-stop via
                    # poll_failure, as before this supervision work.
                    self.halt()
                    if ts.error is not None:
                        raise ts.error
                    raise RuntimeError(
                        f"tile {name!r} failed during boot"
                    )
        # publish AFTER boot: tile on_boot workspace allocations (tcaches
        # etc.) must appear in the directory the monitor attaches to
        self.export_manifest()

    # ---- process runtime -------------------------------------------------

    def _start_process(self, boot_timeout_s: float) -> None:
        # publish BEFORE spawn: children reconstruct their endpoints
        # from the directory's boot manifest (child on_boot allocations
        # land in per-tile shm arenas, so no re-publish is needed for
        # monitors — the arena name tables live in shared memory)
        self.export_manifest()
        for name, ts in self.tiles.items():
            if ts.active:
                self._spawn_tile(name)
        deadline = time.monotonic() + boot_timeout_s
        for name, ts in self.tiles.items():
            if not ts.active:
                continue
            cnc = self._cncs[name]
            while cnc.signal_query() == R.CNC_BOOT:
                if ts.error is not None:  # proc_safe=False thread tile
                    self.halt()
                    raise ts.error
                p = ts.proc
                if p is not None and not p.is_alive():
                    # died before reaching RUN or FAIL (spawn/import
                    # crash): the err sidecar carries the traceback
                    err = _read_err(self.name, name)
                    rc = p.exitcode  # before halt() reaps/closes it
                    self.halt()
                    raise RuntimeError(
                        f"tile {name!r} process died during boot "
                        f"(exitcode {rc})"
                        + (f":\n{err}" if err else "")
                    )
                if time.monotonic() > deadline:
                    self.halt()
                    raise TimeoutError(f"tile {name!r} stuck in BOOT")
                time.sleep(1e-3)
            if cnc.signal_query() == R.CNC_FAIL:
                p = ts.proc
                if p is not None:
                    p.join(timeout=10.0)
                    booted = bool(self._pstat(name)[PSTAT_BOOTED])
                elif ts.thread is not None:
                    ts.thread.join(timeout=10.0)
                    booted = ts.ctx.booted
                else:
                    booted = False
                if not booted:
                    # construction error (bad config, missing device) —
                    # same classification as the thread runtime, read
                    # from the pstat shm word instead of ctx.booted
                    err = _read_err(self.name, name)
                    self.halt()
                    if ts.error is not None:
                        raise ts.error
                    raise RuntimeError(
                        f"tile {name!r} failed during boot"
                        + (f":\n{err}" if err else "")
                    )
        # re-publish after boot (atomic rename, safe under concurrent
        # attaches): parent-thread OBSERVER tiles' on_boot allocations
        # go to the workspace alloc table — the same post-boot
        # re-export invariant the thread runtime keeps.  Child-side
        # allocations need no re-export (arena name tables are in shm).
        self.export_manifest()

    def _pstat(self, name: str) -> np.ndarray:
        return self.wksp.view(f"pstat_{name}")[: 4 * 8].view(np.uint64)

    def tile_pid(self, name: str) -> int | None:
        """The tile's child pid (process runtime; None for threads)."""
        ts = self.tiles[name]
        if ts.proc is None:
            return None
        pid = int(self._pstat(name)[PSTAT_PID])
        return pid or ts.proc.pid

    def _spawn_tile(
        self, name: str, replay: int = 0, rejoin: bool | None = None
    ) -> None:
        """Spawn one tile in the resolved runtime (process children, or
        threads for proc_safe=False observers).  Shared by start() and
        the supervisor's restart path; `replay` is the reliable-link
        rejoin rewind the CHILD applies (tango.rings.consumer_rejoin)
        when its incarnation > 0.  `rejoin=True` forces the child-side
        ring rejoin even on a first incarnation — the elastic add_shard
        path, where a provisioned member's parked fseqs must resolve to
        the live producer head."""
        ts = self.tiles[name]
        ts.error = None
        if self._runtime != "process" or not ts.tile.proc_safe:
            t = threading.Thread(
                target=self._tile_main,
                args=(ts, self._loop_kw),
                name=f"tile:{name}",
            )
            t.daemon = True
            ts.thread = t
            t.start()
            return
        import multiprocessing as mp

        # fresh incarnation contract: parent owns the incarnation word,
        # child owns pid/booted — clear the child-owned words and the
        # stale crash report before the new incarnation starts
        pstat = self._pstat(name)
        pstat[PSTAT_INCARNATION] = np.uint64(ts.ctx.incarnation)
        pstat[PSTAT_PID] = 0
        pstat[PSTAT_BOOTED] = 0
        try:
            os.unlink(_err_path(self.name, name))
        except OSError:
            pass
        mpctx = mp.get_context(self._spawn_method())
        p = mpctx.Process(
            target=_tile_process_main,
            args=(
                self.name,
                name,
                ts.tile,
                self._loop_kw,
                ts.ctx.incarnation,
                replay,
                self.faults_spec,
                bool(rejoin) if rejoin is not None
                else ts.ctx.incarnation > 0,
            ),
            name=f"tile:{name}",
            daemon=True,
        )
        ts.proc = p
        if ts.version_root is None and ts.so_path is None:
            p.start()
            return
        # hot upgrade: the spawn method captures the parent's sys.path
        # in its preparation data and the environment at exec, so a
        # scoped mutation around start() is exactly "this child imports
        # firedancer_tpu from the new tree / loads the prebuilt .so".
        # Serialized: concurrent spawns must not see each other's tree.
        with _SPAWN_ENV_LOCK:
            import sys

            saved_env = os.environ.get("FDT_SO_PATH")
            if ts.version_root is not None:
                sys.path.insert(0, ts.version_root)
            if ts.so_path is not None:
                os.environ["FDT_SO_PATH"] = ts.so_path
            try:
                p.start()
            finally:
                if ts.version_root is not None:
                    sys.path.remove(ts.version_root)
                if ts.so_path is not None:
                    if saved_env is None:
                        os.environ.pop("FDT_SO_PATH", None)
                    else:
                        os.environ["FDT_SO_PATH"] = saved_env

    def _reap(self, ts: TileSpec, timeout_s: float) -> None:
        """Join a child with bounded escalation: HALT should have ended
        it; a survivor gets SIGTERM then SIGKILL, and the handle is
        always closed so no zombie outlives the topology (children that
        died mid-boot are reaped the same way — join on a dead process
        returns immediately)."""
        p = ts.proc
        if p is None:
            return
        p.join(timeout=timeout_s)
        if p.is_alive():
            p.terminate()
            p.join(timeout=5.0)
        if p.is_alive():
            p.kill()
            p.join(timeout=5.0)
        try:
            p.close()
        except ValueError:
            pass  # still alive after SIGKILL: unkillable (D-state); leak
        ts.proc = None

    def poll_failure(self) -> None:
        """Fail-stop check: if any tile died, halt everything and re-raise."""
        for name, ts in self.tiles.items():
            if not ts.active:
                continue
            if ts.error is not None:
                self.halt()
                raise RuntimeError(f"tile {name!r} failed") from ts.error
            p = ts.proc
            if p is None:
                continue
            sig = self._cncs[name].signal_query()
            if sig == R.CNC_FAIL or (sig == R.CNC_RUN and not p.is_alive()):
                err = _read_err(self.name, name)
                self.halt()
                raise RuntimeError(
                    f"tile {name!r} process failed"
                    + (f":\n{err}" if err else "")
                )

    # ---- elastic reconfiguration (disco/elastic.py) ----------------------

    def _park_member_fseqs(self, name: str) -> None:
        """Park an (inactive/reaped) member's reliable in-fseqs ahead of
        each producer so the link never gates on it; see build()."""
        from .elastic import PARK_OFFSET

        for ln, rel in self.tiles[name].ins:
            if not rel:
                continue
            fs = self._fseqs[(ln, name)]
            head = self._mcaches[ln].seq_query()
            fs.update(R.seq_u64(head + PARK_OFFSET))

    def _elastic_gauge(self, kind: str) -> None:
        m = self._metrics.get("elastic")
        if m is None or self._shardmap is None:
            return
        grp = self._shard_groups[kind]
        known = set(m.schema.counters)
        for key, v in (
            (f"{kind}_shards", self._shardmap.n_active(grp["slot"])),
            (f"{kind}_epoch", self._shardmap.epoch(grp["slot"])),
        ):
            if key in known:
                m.set(key, v)

    def _wait_run(self, name: str, timeout_s: float) -> None:
        """Wait for one (re)spawned tile to reach RUN; raise on a boot
        crash or timeout (the tile's error/err-sidecar attached).

        Deliberately NOT shared with start()'s boot-waits: those are
        fail-stop (any boot failure halts the WHOLE topology and
        classifies construction errors via pstat), while an elastic op
        failing to boot one member must leave the rest of the topology
        running and surface only its own error."""
        ts = self.tiles[name]
        cnc = self._cncs[name]
        deadline = time.monotonic() + timeout_s
        while cnc.signal_query() in (R.CNC_BOOT,):
            p = ts.proc
            if ts.error is not None:
                raise ts.error
            if p is not None and not p.is_alive():
                err = _read_err(self.name, name)
                raise RuntimeError(
                    f"tile {name!r} died during elastic boot"
                    + (f":\n{err}" if err else "")
                )
            if time.monotonic() > deadline:
                raise TimeoutError(f"tile {name!r} stuck in BOOT")
            time.sleep(1e-3)
        if cnc.signal_query() == R.CNC_FAIL:
            err = _read_err(self.name, name)
            if ts.error is not None:
                raise ts.error
            raise RuntimeError(
                f"tile {name!r} failed during elastic boot"
                + (f":\n{err}" if err else "")
            )

    def add_shard(
        self, kind: str, i: int | None = None, *, timeout_s: float = 300.0
    ) -> int:
        """Activate one provisioned member of an elastic shard group at
        RUNTIME: spawn its tile (thread or process), land its consumer
        cursors at the live producer head (consumer_rejoin unparks the
        far-future fseq), extend the boot manifest (atomic rename), and
        flip the shard-map epoch only AFTER the new member has rejoined
        its rings and reached RUN — so the first frag assigned to it
        finds it consuming.  Returns the member index."""
        grp = self._shard_groups[kind]
        smv = self.shardmap()
        mask = smv.mask(grp["slot"])
        if i is None:
            free = [
                j
                for j in range(len(grp["members"]))
                if not (mask >> j) & 1 and not self.tiles[
                    grp["members"][j]
                ].active
            ]
            if not free:
                raise RuntimeError(f"shard kind {kind!r}: no free member")
            i = free[0]
        name = grp["members"][i]
        ts = self.tiles[name]
        assert not ts.active and not (mask >> i) & 1, (
            f"member {name!r} already active"
        )
        ts.active = True
        is_proc = self._runtime == "process" and ts.tile.proc_safe
        try:
            if is_proc:
                # the CHILD rejoins at boot (rejoin=True even on the
                # first incarnation): consumer_rejoin reads the parked
                # fseq and lands at the producer head
                self._spawn_tile(name, rejoin=True)
            else:
                from .supervisor import rejoin_links

                rejoin_links(ts.ctx.ins, ts.ctx.outs, replay=0)
                self._spawn_tile(name)
            self._wait_run(name, timeout_s)
        except BaseException:
            ts.active = False
            self._park_member_fseqs(name)
            raise
        # flip AFTER the member is live: the producer's next burst
        # boundary appends the flip entry, and every seq it governs
        # lands on a consuming member
        smv.flip(grp["slot"], mask | (1 << i))
        self._elastic_gauge(kind)
        self.export_manifest()
        return i

    def retire_shard(
        self,
        kind: str,
        i: int,
        *,
        timeout_s: float = 300.0,
        replay: int = 0,
    ) -> None:
        """Retire one active member: drain -> handover -> reap.  The
        epoch flips first (no new seqs are assigned past the flip
        entry); the member then drains its in-flight window and
        publishes a DRAINED marker (the epoch) in the shard map; only
        then is it halted and reaped, its fseqs parked so the producer
        never gates on the corpse.  A member that dies mid-drain (chaos
        SIGKILL) is respawned — ring rejoin + `replay`, the crash-
        restart machinery — until the drain completes: the same
        zero-loss/zero-dup bar as crashes."""
        grp = self._shard_groups[kind]
        smv = self.shardmap()
        name = grp["members"][i]
        ts = self.tiles[name]
        assert ts.active and (smv.mask(grp["slot"]) >> i) & 1, (
            f"member {name!r} not active"
        )
        ep = smv.flip(grp["slot"], smv.mask(grp["slot"]) & ~(1 << i))
        self._elastic_gauge(kind)
        self.export_manifest()
        deadline = time.monotonic() + timeout_s
        while smv.drained(grp["slot"], i) < ep:
            if time.monotonic() > deadline:
                # ROLL BACK: the member is still running and was never
                # reaped — re-admit it under a fresh epoch so the mask
                # and ts.active stay consistent (a half-retired member
                # would otherwise wedge every future scale-out of this
                # kind) and surface the failure to the caller
                smv.flip(grp["slot"], smv.mask(grp["slot"]) | (1 << i))
                self._elastic_gauge(kind)
                self.export_manifest()
                raise TimeoutError(
                    f"member {name!r} failed to drain for epoch {ep}; "
                    f"membership rolled back"
                )
            self._revive_if_dead(name, replay)
            time.sleep(2e-3)
        # drained: deliberate halt (on_halt runs; halt-ack -> BOOT)
        self._cncs[name].signal(R.CNC_HALT)
        if ts.proc is not None:
            self._reap(ts, timeout_s=30.0)
        elif ts.thread is not None:
            ts.thread.join(timeout=30.0)
        ts.active = False
        if self._runtime == "process" and ts.tile.proc_safe:
            # observability mirror: the drained epoch into the pstat
            # words (the parent owns this word; the member's canonical
            # marker lives in the shard-map region, which works in both
            # runtimes)
            pstat = self._pstat(name)
            pstat[PSTAT_DRAINED] = np.uint64(ep)
        self._park_member_fseqs(name)
        self._elastic_gauge(kind)
        self.export_manifest()

    def _respawn_incarnation(
        self, name: str, replay: int, *, crashed: bool
    ) -> None:
        """The one reincarnation recipe shared by the elastic paths
        (mid-drain crash revival, rolling restart): thread-runtime ring
        rejoin with the standard skip accounting (process children
        rejoin themselves at boot), incarnation bump, BOOT signal,
        respawn.  `crashed` adds the crash-only steps (on_crash
        cleanup, the restarts counter) that a clean halt skips."""
        ts = self.tiles[name]
        ctx = ts.ctx
        is_proc = self._runtime == "process" and ts.tile.proc_safe
        if not is_proc:
            from .supervisor import rejoin_links

            metrics = self._metrics[name]

            def _account_skip(il, skipped):
                metrics.inc("overrun_frags", skipped)
                il.fseq.diag_add(0, skipped)

            rejoin_links(
                ctx.ins, ctx.outs, replay=replay, on_skip=_account_skip
            )
            if crashed:
                ts.tile.on_crash(ctx)
        ctx.interrupt.clear()
        ctx.booted = False
        ctx.incarnation += 1
        if crashed:
            self._metrics[name].inc("restarts")
        self._cncs[name].signal(R.CNC_BOOT)
        self._spawn_tile(name, replay=replay)

    def _revive_if_dead(self, name: str, replay: int) -> None:
        """Mid-drain crash recovery for a deliberately-retiring member
        (the supervisor stands back during commanded ops): respawn the
        dead incarnation through the ordinary rejoin path so the drain
        completes exactly-once."""
        ts = self.tiles[name]
        sig = self._cncs[name].signal_query()
        died = (
            not ts.proc.is_alive()
            if ts.proc is not None
            else ts.thread is not None and not ts.thread.is_alive()
        )
        if not died and sig != R.CNC_FAIL:
            return
        if ts.proc is not None:
            self._reap(ts, timeout_s=10.0)
        elif ts.thread is not None:
            ts.thread.join(timeout=10.0)
        self._respawn_incarnation(name, replay, crashed=True)

    def rolling_restart(
        self,
        name: str,
        *,
        mutate=None,
        replay: int = 0,
        timeout_s: float = 300.0,
    ) -> None:
        """Deliberately restart one tile under traffic: halt (on_halt
        drains), reap, optionally apply a config mutation to the tile
        object (`mutate(tile)` — the respawn pickles the mutated tile
        into the new child, which is what makes config reload and code
        hot-swap first-class), rejoin the rings, respawn, wait for RUN.
        Exactly-once across the restart rides the same replay +
        surviving-dedup discipline as crash restarts."""
        ts = self.tiles[name]
        assert ts.active, f"tile {name!r} is not active"
        cnc = self._cncs[name]
        cnc.signal(R.CNC_HALT)
        if ts.proc is not None:
            self._reap(ts, timeout_s=30.0)
        elif ts.thread is not None:
            ts.thread.join(timeout=30.0)
            ts.thread = None
        if mutate is not None:
            mutate(ts.tile)
        self._respawn_incarnation(name, replay, crashed=False)
        self._wait_run(name, timeout_s)
        self.export_manifest()

    # ---- hot code upgrade (fdt_upgrade) ---------------------------------

    def handshake(self):
        """The workspace's version-handshake view (disco/handshake.py),
        bound at build()."""
        assert self._handshake is not None, "build() first"
        return self._handshake

    def approve_version(self, digest: int) -> None:
        """Admit a foreign ABI digest into the workspace compat table —
        the operator's out-of-band ring-compatibility proof.  Joining
        incarnations carrying it pass the handshake thereafter."""
        self.handshake().approve(digest)

    def hot_upgrade(
        self,
        name: str,
        *,
        version_root: str | None = None,
        so_path: str | None = None,
        digest: int | None = None,
        mutate=None,
        replay: int = 0,
        timeout_s: float = 300.0,
    ) -> None:
        """Rolling restart into NEW CODE behind the same rings.

        Pre-flight: the candidate version's ring-ABI digest (`digest`
        if given, else probed via handshake.probe_digest — identity
        versions answer in-process) must be proven compatible with the
        workspace handshake word BEFORE the running tile is touched; a
        mismatch raises UpgradeRefused with both digests and zero
        downtime.  Accepted: halt → reap → stamp the version onto the
        tile spec (the next incarnation imports firedancer_tpu from
        `version_root` and loads `so_path`, see _spawn_tile) → mutate →
        respawn → wait RUN.  A new-version boot failure rolls back to
        the OLD recipe (old version fields, pre-mutate tile snapshot
        where picklable), respawns it, and raises UpgradeRolledBack —
        commanded-then-rollback, not a crash streak (the supervisor's
        breaker never sees it when bracketed via
        ElasticController.hot_upgrade).

        `version_root`/`so_path` are process-runtime contracts (one
        interpreter cannot swap module trees): thread tiles hot-upgrade
        via `mutate` swapping the tile object, still digest-gated.
        """
        ts = self.tiles[name]
        assert ts.active, f"tile {name!r} is not active"
        is_proc = self._runtime == "process" and ts.tile.proc_safe
        if (version_root is not None or so_path is not None) and not is_proc:
            raise ValueError(
                f"tile {name!r} runs in-process: version_root/so_path "
                f"need a process-runtime child (use mutate for a "
                f"thread-tile code swap)"
            )
        if digest is None:
            from .handshake import probe_digest

            digest = probe_digest(version_root, so_path)
        hs = self.handshake()
        if not hs.compatible(digest):
            raise UpgradeRefused(hs.digest(), digest, name)
        # snapshot the old recipe for rollback (tile snapshot is
        # best-effort: an unpicklable tile rolls back version fields
        # only, keeping the mutated object)
        import pickle

        old_version = (ts.version_root, ts.so_path)
        try:
            old_tile = pickle.dumps(ts.tile)
        except Exception:  # noqa: BLE001 — thread tiles may hold locks
            old_tile = None
        cnc = self._cncs[name]
        cnc.signal(R.CNC_HALT)
        if ts.proc is not None:
            self._reap(ts, timeout_s=30.0)
        elif ts.thread is not None:
            ts.thread.join(timeout=30.0)
            ts.thread = None
        if version_root is not None or so_path is not None:
            ts.version_root, ts.so_path = version_root, so_path
        try:
            if mutate is not None:
                mutate(ts.tile)
            self._respawn_incarnation(name, replay, crashed=False)
            self._wait_run(name, timeout_s)
        except BaseException as cause:  # noqa: BLE001 — rollback then raise
            if ts.proc is not None:
                self._reap(ts, timeout_s=10.0)
            elif ts.thread is not None:
                ts.thread.join(timeout=10.0)
                ts.thread = None
            ts.version_root, ts.so_path = old_version
            if old_tile is not None:
                ts.tile = pickle.loads(old_tile)
            self._respawn_incarnation(name, replay, crashed=False)
            self._wait_run(name, timeout_s)
            self.export_manifest()
            raise UpgradeRolledBack(name, cause) from cause
        self.export_manifest()

    def halt(self, timeout_s: float = 30.0) -> None:
        """Halt upstream-first so in-flight frags drain before consumers
        stop.  Process children are reaped with bounded SIGTERM→SIGKILL
        escalation (a child that died mid-boot is reaped the same way),
        so repeated bench runs never accumulate zombies."""
        order = self._topo_order()
        for name in order:
            cnc = self._cncs.get(name)
            if cnc is None or not self.tiles[name].active:
                continue
            cnc.signal(R.CNC_HALT)
            ts = self.tiles[name]
            if ts.proc is not None:
                self._reap(ts, timeout_s)
            if ts.thread is not None:
                ts.thread.join(timeout=timeout_s)

    def _topo_order(self) -> list[str]:
        """Tiles ordered producers-before-consumers (cycles broken by
        declaration order)."""
        order: list[str] = []
        seen: set[str] = set()

        def visit(name: str) -> None:
            if name in seen:
                return
            seen.add(name)
            for ln in self.tiles[name].ins:
                prod = self.links[ln[0]].producer
                if prod is not None and prod not in seen:
                    visit(prod)
            order.append(name)

        for name in self.tiles:
            visit(name)
        return order

    def metrics(self, tile_name: str) -> Metrics:
        return self._metrics[tile_name]

    def metrics_registry(self) -> dict[str, Metrics]:
        """Snapshot of every tile's Metrics (the metric tile's source)."""
        return dict(self._metrics)

    def profile_metrics(self) -> dict[str, Metrics]:
        """Per-tile profiler regions (disco/profile.py readers), empty
        when profiling is off."""
        return {name: p.m for name, p in self._profilers.items()}

    def tile_alloc_view(self, tile: str, name: str) -> np.ndarray:
        """Resolve a tile's ctx.alloc region by name from the PARENT
        (tests, benches): the per-tile shm arena in the process
        runtime, the workspace alloc table in the threaded one, the
        ctx-local buffer for anonymous thread topologies."""
        key = f"{tile}_{name}"
        if self._runtime == "process" and self.tiles[tile].tile.proc_safe:
            # join=True: read-only attach — never initialize the header
            # (that is the owning child's job; racing it would corrupt
            # the name table)
            return R.WkspArena(
                self.wksp.view(f"arena_{tile}"), join=True
            ).view(key)
        if self.wksp is not None and key in self.wksp._allocs:
            return self.wksp.view(key)
        return self.tiles[tile].ctx._local_allocs[key]

    def close(self) -> None:
        # reap stragglers first (failed starts, children dead mid-boot):
        # unlinking shm under a live child is POSIX-safe but the zombie
        # and its err sidecar must not outlive the topology
        for ts in self.tiles.values():
            if ts.proc is not None:
                self._reap(ts, timeout_s=1.0)
        if self.wksp is not None:
            self.wksp.unlink()
            self.wksp = None


# ---------------------------------------------------------------------------
# process-runtime child entrypoint
#
# Runs in a FRESH interpreter (spawn) or forked child: re-attach the named
# workspace, rebind every endpoint by boot-manifest name, rebuild the
# MuxCtx, rejoin the rings if this is a re-incarnation, and enter the
# SAME run loop the threaded runtime uses — the ring protocol itself is
# process-safe (fdtmc-verified), so nothing below the ctx changes.


def _tile_process_main(
    wksp_name: str,
    tile_name: str,
    tile: Tile,
    loop_kw: dict,
    incarnation: int,
    replay: int,
    faults_spec: tuple | None,
    rejoin: bool | None = None,
) -> None:
    import sys
    import traceback

    from firedancer_tpu.utils import log

    log.set_tile(tile_name)
    err_path = _err_path(wksp_name, tile_name)
    ctx = None
    cnc = None
    pstat = None
    try:
        ws, extra = R.Workspace.attach(wksp_name)
        boot = extra["boot"]
        links = boot["links"]
        t = boot["tiles"][tile_name]
        pstat = ws.view(t["pstat"])[: 4 * 8].view(np.uint64)
        pstat[PSTAT_PID] = os.getpid()
        # version handshake (disco/handshake.py): prove THIS
        # incarnation's ring-ABI digest against the workspace word
        # BEFORE binding a single ring — a mixed-version join is either
        # digest/compat-proven or refused right here (HandshakeRefused
        # lands in the err sidecar with both digests; exit code 2, a
        # construction failure).  The ring-handshake-rebind lint rule
        # pins that this check precedes the link construction below.
        if boot.get("handshake") is not None:
            from .handshake import check_join

            check_join(
                ws.view(boot["handshake"]), R.abi_digest(), tile=tile_name
            )
        mcaches: dict[str, R.MCache] = {}
        dcaches: dict[str, R.DCache] = {}

        def _mc(ln: str) -> R.MCache:
            if ln not in mcaches:
                mcaches[ln] = R.MCache(
                    ws.view(links[ln]["mcache"]), links[ln]["depth"],
                    join=True,
                )
            return mcaches[ln]

        def _dc(ln: str, producer: bool = False) -> R.DCache | None:
            spec = links[ln]
            if spec["dcache"] is None:
                return None
            if ln not in dcaches:
                dcaches[ln] = R.DCache(
                    ws.view(spec["dcache"]), spec["mtu"], spec["depth"]
                )
            dc = dcaches[ln]
            if producer and spec["dcur"] is not None:
                dc.bind_cursor(ws.view(spec["dcur"]))
            return dc

        cnc = R.CNC(ws.view(t["cnc"]), join=True)
        sch = t["schema"]
        schema = MetricsSchema(
            counters=tuple(sch["counters"]),
            hists=tuple(sch["hists"]),
            wide_hists=tuple(sch.get("wide_hists", ())),
        )
        metrics = Metrics(ws.view(t["metrics"]), schema)
        tracer = None
        if boot.get("trace") is not None and t["trace"] is not None:
            ring = SpanRing(ws.view(t["trace"]), join=True)
            tracer = Tracer(ring, boot["trace"]["sample"], name=tile_name)
        profiler = None
        if t["profile"] is not None:
            from .profile import PROFILE_SCHEMA, TileProfiler

            profiler = TileProfiler(
                Metrics(ws.view(t["profile"]), PROFILE_SCHEMA)
            )
        ins = [
            InLink(
                ln,
                _mc(ln),
                _dc(ln),
                R.FSeq(
                    ws.view(
                        next(
                            c[2]
                            for c in links[ln]["consumers"]
                            if c[0] == tile_name
                        )
                    ),
                    join=True,
                ),
                bool(rel),
                link_id=links[ln]["id"],
                h_qwait=f"qwait_us_{ln}",
                h_svc=f"svc_us_{ln}",
                h_e2e=f"e2e_us_{ln}",
            )
            for ln, rel in t["ins"]
        ]
        outs = [
            OutLink(
                ln,
                _mc(ln),
                _dc(ln, producer=True),
                [
                    R.FSeq(ws.view(c[2]), join=True)
                    for c in links[ln]["consumers"]
                    if c[1]
                ],
                link_id=links[ln]["id"],
                tracer=tracer,
            )
            for ln in t["outs"]
        ]
        ctx = MuxCtx(tile_name, cnc, ins, outs, metrics, wksp=ws)
        ctx.tracer = tracer
        ctx.profiler = profiler
        ctx.arena = R.WkspArena(ws.view(t["arena"]))
        ctx.incarnation = incarnation
        if faults_spec is not None:
            from .faultinj import FaultInjector

            seed, faults = faults_spec
            tf = FaultInjector(seed=seed, faults=faults).view(tile_name)
            # cumulative trigger state lives in shm so a restarted
            # incarnation does not re-fire already-fired faults
            tf.bind_shm(ws.view(t["fstat"]))
            ctx.faults = tf
        if rejoin if rejoin is not None else incarnation > 0:
            # ring rejoin runs IN the child (the dead incarnation's seqs
            # live in the shm fseqs/mcaches, so the repair is derivable
            # here) — same helper, and the same loss accounting, as the
            # thread runtime's supervisor-side rejoin.  An elastic
            # add_shard spawn forces rejoin on a FIRST incarnation: the
            # member's parked fseq resolves to the live producer head.
            from .supervisor import rejoin_links

            def _account_skip(il, skipped):
                metrics.inc("overrun_frags", skipped)
                il.fseq.diag_add(0, skipped)

            rejoin_links(
                ctx.ins, ctx.outs, replay=replay, on_skip=_account_skip
            )
        run_loop(tile, ctx, **loop_kw)
    except BaseException:  # noqa: BLE001 — fail-stop, reported via shm
        try:
            with open(err_path, "w") as f:
                f.write(traceback.format_exc())
        except OSError:
            pass
        booted = bool(ctx is not None and ctx.booted)
        if pstat is not None:
            pstat[PSTAT_BOOTED] = 1 if booted else 0
        # run_loop signals FAIL for its own exceptions; cover crashes
        # before/outside it so the parent's cnc wait always resolves
        if cnc is not None and cnc.signal_query() != R.CNC_FAIL:
            cnc.signal(R.CNC_FAIL)
        log.err("tile process failed: see %s", err_path)
        # exit code mirrors the thread runtime's boot/run classification
        sys.exit(2 if not booted else 1)
    else:
        if pstat is not None:
            pstat[PSTAT_BOOTED] = 1
