#!/usr/bin/env python3
"""fdttrace — drain a live topology's span rings, assemble per-frag
timelines, and export latency attribution.

Usage:
    scripts/fdttrace.py WKSP --summary           # per-hop percentile table
    scripts/fdttrace.py WKSP --out trace.json    # Chrome trace-event JSON
    scripts/fdttrace.py WKSP --follow [-i 2.0]   # live summary loop
    scripts/fdttrace.py WKSP --seconds 2 --out t.json   # longer capture
    scripts/fdttrace.py WKSP --out t.json --xplane FILE.xplane.pb
                                  # + the profiler's trace, on the same clock

WKSP is the topology's workspace name (Topology(name=...) with
enable_trace(); the manifest published at start() carries the span-ring
directory).  `--summary` needs only the always-on per-link latency
histograms; the trace export needs span rings (enable_trace) and emits
Chrome trace-event JSON loadable in Perfetto / chrome://tracing: "X"
(complete) events only, one track per tile facet (frags / device pool /
loop / faults), timestamps unwrapped from the compressed u32 µs domain
and strictly sorted per track.

Frag spans correlate across tiles by the sig field (the dedup tag is
carried hop to hop), which is also the sampling key — the same 1-in-N
frags are traced at every hop, so a sampled frag's whole
quic -> verify -> dedup -> pack timeline is assemblable.  Injected
faults (disco/faultinj.py) and supervisor restarts appear on each
tile's fault track, so a kill -> restart gap is visible in the trace
and assertable from `classify()` (a timeline is whole, or it is lost
with its furthest-reached hop named).

The verify tile's device-pool track draws each device batch's lifecycle
from its five span events (STAGE, ENQUEUE, DISPATCH, LAND, PUBLISHED,
matched by pool seq) as four back-to-back spans: fill, queue, the
device's batch, drain.  `--xplane` lays a jax.profiler trace of the
process that holds the chip under the same time axis: the tile writes a
`fdt.clock` host span once a second whose `mono_ns` argument is
time.monotonic_ns() at its start, which ties the profiler's clock to
the span rings' (`profiler_events`).
"""

from __future__ import annotations

import argparse
import json
import re
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from firedancer_tpu.disco import trace as T  # noqa: E402
from firedancer_tpu.disco.metrics import (  # noqa: E402
    Metrics,
    MetricsSchema,
    hist_percentile,
)
from firedancer_tpu.disco.mux import (  # noqa: E402
    LINK_HIST_KINDS,
    ns_to_ts,
    ts_diff,
)
from firedancer_tpu.tango import rings as R  # noqa: E402

#: the arguments the verify tile gives its `fdt.*` host spans: the batch's
#: pool seq and lanes, on the worker's dispatch / land spans its txns, its
#: kernel tiles (0 where the host path serves it) and the pool domain
#: (`dev=`) that worker serves, and `fdt.clock`'s tie
_SPAN_ARGS = ("seq", "lanes", "txns", "tiles", "dev", "mono_ns")

#: per-tile sub-tracks in the Chrome trace (tid = tile_index * 4 + facet)
_FACET_FRAGS, _FACET_DEVICE, _FACET_LOOP, _FACET_FAULTS = 0, 1, 2, 3


class TraceSession:
    """Attached (or in-process) view of a topology's span rings +
    metrics regions, with incremental drain cursors."""

    def __init__(
        self,
        rings: dict[str, "T.SpanRing"],
        link_names: list[str],
        metrics: dict[str, Metrics] | None = None,
        tile_links: dict[str, dict] | None = None,
    ):
        self.rings = rings
        self.link_names = list(link_names)
        self.metrics = metrics or {}
        #: {tile: {"ins": [...], "outs": [...]}} for the summary table
        self.tile_links = tile_links or {}
        self.cursors = {t: 0 for t in rings}
        self.dropped = {t: 0 for t in rings}
        self.events: dict[str, list[dict]] = {t: [] for t in rings}

    # -- construction -----------------------------------------------------

    @classmethod
    def attach(cls, wksp_name: str) -> "TraceSession":
        """Attach to a live named workspace via its published manifest."""
        wksp, extra = R.Workspace.attach(wksp_name)
        tiles = extra.get("tiles", {})
        metrics = {}
        tile_links = {}
        for name, t in tiles.items():
            schema = MetricsSchema(
                counters=tuple(t["counters"]), hists=tuple(t["hists"]),
                # layout-affecting: the per-link latency hists are wide
                # (ISSUE 15) — dropping this field misreads every hist
                # after the first wide one
                wide_hists=tuple(t.get("wide_hists", ())),
            )
            metrics[name] = Metrics(wksp.view(t["metrics"]), schema)
            tile_links[name] = {
                "ins": t.get("ins", []),
                "outs": t.get("outs", []),
            }
        tr = extra.get("trace")
        rings = {}
        link_names = list(extra.get("links", {}))
        if tr is not None:
            link_names = tr["links"]
            for name, alloc in tr["tiles"].items():
                rings[name] = T.SpanRing(wksp.view(alloc), join=True)
        s = cls(rings, link_names, metrics, tile_links)
        s.wksp = wksp  # keep the mapping alive
        return s

    @classmethod
    def from_topology(cls, topo) -> "TraceSession":
        """In-process session over a (possibly anonymous) Topology with
        tracing enabled — the test-suite entry point."""
        rings = {name: tr.ring for name, tr in topo._tracers.items()}
        tile_links = {
            name: {"ins": [ln for ln, _ in ts.ins], "outs": list(ts.outs)}
            for name, ts in topo.tiles.items()
        }
        return cls(
            rings, list(topo.links), dict(topo._metrics), tile_links
        )

    # -- span drain -------------------------------------------------------

    def drain(self) -> int:
        """Pull new events from every ring; returns how many arrived."""
        got = 0
        for tile, ring in self.rings.items():
            ev, cur, dropped = ring.read(self.cursors[tile])
            self.cursors[tile] = cur
            self.dropped[tile] += dropped
            decoded = T.decode(ev)
            self.events[tile].extend(decoded)
            got += len(decoded)
        return got

    def link_name(self, link_id: int) -> str:
        if 0 <= link_id < len(self.link_names):
            return self.link_names[link_id]
        return f"link{link_id}"


# ---------------------------------------------------------------------------
# timeline assembly + completeness classification


def assemble(session: TraceSession) -> dict[int, list[dict]]:
    """Per-frag timelines: {sig: [frag events across tiles, ts-order]}.
    Only INGEST/PUBLISH events carry a frag identity."""
    timelines: dict[int, list[dict]] = {}
    for tile, evs in session.events.items():
        for e in evs:
            if e["kind"] not in (T.INGEST, T.PUBLISH):
                continue
            timelines.setdefault(e["sig"], []).append(
                {
                    "tile": tile,
                    "kind": T.KIND_NAMES[e["kind"]],
                    "link": session.link_name(e["link"]),
                    "ts": e["ts"],
                    "seq": e["seq"],
                }
            )
    anchor = _anchor(session)
    for evs in timelines.values():
        evs.sort(key=lambda e: ts_diff(e["ts"], anchor))
    return timelines


def classify(
    timelines: dict[int, list[dict]], path: list[str]
) -> tuple[set, dict]:
    """Completeness over an ordered link path (e.g. [quic_verify,
    verify_dedup, dedup_pack]).  A timeline is WHOLE when it was
    published on every path link; otherwise it is LOST at the furthest
    link it did reach (None = touched the path but was never published
    on it).  Sigs whose timeline never touches a path link at all —
    foreign traffic like microblock handles on the bank rings — are
    outside the classification.  Kill -> restart chaos runs assert on
    exactly this: every admitted frag whole, every lost frag explained
    by a declared injection."""
    path_set = set(path)
    whole: set = set()
    lost: dict = {}
    for sig, evs in timelines.items():
        if not any(e["link"] in path_set for e in evs):
            continue
        published = {e["link"] for e in evs if e["kind"] == "publish"}
        progress = None
        ok = True
        for ln in path:
            if ln in published:
                progress = ln
            else:
                ok = False
        if ok:
            whole.add(sig)
        else:
            lost[sig] = progress
    return whole, lost


# ---------------------------------------------------------------------------
# Chrome trace-event export


def _anchor(session: TraceSession) -> int:
    for evs in session.events.values():
        for e in evs:
            return e["ts"]
    return 0


def profiler_events(xplane_path: str) -> tuple[int | None, list[dict]]:
    """A jax.profiler trace (`*.xplane.pb`) -> (offset_ns, events), the
    events being the program's own host spans (`fdt.*`) and every device
    op, each {"track", "name", "start_ns", "dur_ns", "args"} on the
    PROFILER's clock (`args`: a host span's `seq`, `lanes` and, for a pool
    worker's dispatch / land, `txns`, `tiles` and `dev`).  offset_ns = time.monotonic_ns() - profiler ns, taken from
    the `fdt.clock` spans the verify tile writes once a second (their
    `mono_ns` argument is the monotonic clock at their start; the median
    over the trace's ties); None when the trace holds no tie.  With it,
    `start_ns + offset_ns` is a time on the clock of the span rings, the
    metrics' stamps and any harness that reads time.monotonic_ns()."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    ties, events = [], []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and not line.name.startswith("XLA Ops"):
                continue
            for e in line.events:
                name = e.name
                if not device and not name.startswith("fdt."):
                    continue
                # the profiler keeps an annotation's arguments as the
                # event's stats, or (other versions) inside its name as
                # `fdt.verify.dispatch#seq=7,lanes=85,dev=2#`
                args = {} if device else {
                    k: int(v) for k, v in dict(e.stats).items()
                    if k in _SPAN_ARGS
                } or {
                    k: int(v) for k, v in
                    re.findall(r"(\w+)=(\d+)", name.partition("#")[2])
                }
                if name.startswith("fdt.clock") and "mono_ns" in args:
                    ties.append(args["mono_ns"] - int(e.start_ns))
                events.append(
                    {
                        "track": f"{plane.name} {line.name}",
                        "name": name.split("#", 1)[0],
                        "start_ns": int(e.start_ns),
                        "dur_ns": int(e.duration_ns),
                        "args": args,
                    }
                )
    offset = int(statistics.median(ties)) if ties else None
    return offset, events


def chrome_trace(
    session: TraceSession, profiler: tuple[int, list[dict]] | None = None
) -> list[dict]:
    """Span events -> Chrome trace-event JSON (list of "X" events,
    strictly sorted per (pid, tid) track).  `profiler` = what
    profiler_events returned (with a clock tie): its events go under
    pid 2, one track per profiler line, on the rings' time axis."""
    anchor = _anchor(session)
    rel0 = min(
        (
            ts_diff(e["ts"], anchor)
            for evs in session.events.values()
            for e in evs
        ),
        default=0,
    )

    def us(ts: int) -> int:
        return ts_diff(ts, anchor) - rel0

    out: list[dict] = []
    tiles = sorted(session.events)
    for t_idx, tile in enumerate(tiles):
        evs = session.events[tile]
        tid = t_idx * 4
        # frag track: INGEST paired with the tile's next PUBLISH of the
        # same sig = the frag's service span at this tile
        pubs: dict[int, list[int]] = {}
        for e in evs:
            if e["kind"] == T.PUBLISH:
                pubs.setdefault(e["sig"], []).append(e["ts"])
        for sig in pubs:
            pubs[sig].sort(key=us)
        ingest_sigs = set()
        for e in evs:
            k = e["kind"]
            if k == T.INGEST:
                ingest_sigs.add(e["sig"])
                t_in = us(e["ts"])
                dur = 1
                for p in pubs.get(e["sig"], ()):
                    if us(p) >= t_in:
                        dur = max(us(p) - t_in, 1)
                        break
                tsorig = int(e["aux64"]) >> 32
                tspub = int(e["aux64"]) & 0xFFFFFFFF
                out.append(
                    {
                        "name": f"{tile} {session.link_name(e['link'])}",
                        "ph": "X",
                        "pid": 1,
                        "tid": tid + _FACET_FRAGS,
                        "ts": t_in,
                        "dur": dur,
                        "args": {
                            "sig": f"{e['sig']:#018x}",
                            "seq": int(e["seq"]),
                            "qwait_us": max(ts_diff(e["ts"], tspub), 0),
                            "e2e_us": max(ts_diff(e["ts"], tsorig), 0),
                        },
                    }
                )
            elif k == T.PUBLISH and e["sig"] not in ingest_sigs:
                # origin tiles (quic/synth/replay) publish frags they
                # never ingested from a ring
                out.append(
                    {
                        "name": f"{tile} publish "
                        f"{session.link_name(e['link'])}",
                        "ph": "X",
                        "pid": 1,
                        "tid": tid + _FACET_FRAGS,
                        "ts": us(e["ts"]),
                        "dur": 1,
                        "args": {
                            "sig": f"{e['sig']:#018x}",
                            "seq": int(e["seq"]),
                        },
                    }
                )
        # device-pool track: one batch's lifecycle, matched by pool seq —
        # STAGE -> ENQUEUE (fill: waiting for a pool slot / more lanes),
        # ENQUEUE -> DISPATCH (the worker's request queue), DISPATCH ->
        # LAND (the device's batch), LAND -> PUBLISHED (drain).  A ring
        # lap can lose any of the five; a span needs both its ends.
        by_kind = {
            k: {e["seq"]: e for e in evs if e["kind"] == k}
            for k in (T.STAGE, T.ENQUEUE, T.DISPATCH, T.PUBLISHED)
        }
        for e in evs:
            if e["kind"] != T.LAND:
                continue
            seq = e["seq"]
            st, q, d, pub = (
                by_kind[k].get(seq)
                for k in (T.STAGE, T.ENQUEUE, T.DISPATCH, T.PUBLISHED)
            )
            args = {
                "pool_seq": int(seq),
                "lanes": int(e["aux64"]),
                "queue_us": 0 if q is None or d is None
                else max(us(d["ts"]) - us(q["ts"]), 0),
            }
            for name, a, b in (
                (f"{tile} fill", st, q),
                (f"{tile} queue", q, d),
                (f"{tile} dev{e['aux16']} batch", d, e),
                (f"{tile} drain", e, pub),
            ):
                if a is None or b is None:
                    continue
                out.append(
                    {
                        "name": name,
                        "ph": "X",
                        "pid": 1,
                        "tid": tid + _FACET_DEVICE,
                        "ts": us(a["ts"]),
                        "dur": max(us(b["ts"]) - us(a["ts"]), 1),
                        "args": args,
                    }
                )
        # loop track (housekeeping + backpressure streak markers) and
        # fault annotations (injected faults, supervisor restarts)
        for e in evs:
            if e["kind"] == T.HK:
                out.append(
                    {
                        "name": f"{tile} hk",
                        "ph": "X",
                        "pid": 1,
                        "tid": tid + _FACET_LOOP,
                        "ts": us(e["ts"]),
                        "dur": max(int(e["aux64"]) // 1000, 1),
                        "args": {},
                    }
                )
            elif e["kind"] in (T.BP, T.FALLBACK, T.QUARANTINE):
                out.append(
                    {
                        "name": f"{tile} {T.KIND_NAMES[e['kind']]}",
                        "ph": "X",
                        "pid": 1,
                        "tid": tid + _FACET_LOOP,
                        "ts": us(e["ts"]),
                        "dur": 1,
                        "args": {"aux": int(e["aux64"])},
                    }
                )
            elif e["kind"] == T.FAULT:
                code = T.FAULT_NAMES.get(e["aux16"], "?")
                dur = 1
                if code == "stall":
                    dur = max(int(e["aux64"]), 1)  # stall length, µs
                out.append(
                    {
                        "name": f"{tile} fault:{code}",
                        "ph": "X",
                        "pid": 1,
                        "tid": tid + _FACET_FAULTS,
                        "ts": us(e["ts"]),
                        "dur": dur,
                        "args": {"detail": int(e["aux64"])},
                    }
                )
    if profiler is not None:
        offset_ns, spans = profiler
        tracks = {t: i for i, t in enumerate(
            sorted({s["track"] for s in spans}))}
        for s in spans:
            out.append(
                {
                    "name": s["name"],
                    "ph": "X",
                    "pid": 2,
                    "tid": tracks[s["track"]],
                    "ts": us(ns_to_ts(s["start_ns"] + offset_ns)),
                    "dur": max(s["dur_ns"] // 1000, 1),
                    "args": {"track": s["track"], **s["args"]},
                }
            )
    # strict per-track time order (Perfetto requires monotone begins)
    out.sort(key=lambda e: (e["pid"], e["tid"], e["ts"], -e["dur"]))
    return out


# ---------------------------------------------------------------------------
# summary: per-hop percentile table from the always-on latency hists


def summary_rows(session: TraceSession) -> list[dict]:
    """One row per (tile, in-link) hop: p50/p99/p99.9 for queue-wait /
    service / end-to-end, plus the tile's %backpressure."""
    rows = []
    for tile in sorted(session.metrics):
        m = session.metrics[tile]
        c = {k: m.counter(k) for k in ("backpressure_iters", "loop_iters")}
        bp_pct = 100.0 * c["backpressure_iters"] / max(c["loop_iters"], 1)
        for ln in session.tile_links.get(tile, {}).get("ins", []):
            row = {"tile": tile, "link": ln, "bp_pct": round(bp_pct, 2)}
            have = False
            for kind in LINK_HIST_KINDS:
                name = f"{kind}_{ln}"
                if name not in m.schema.hists:
                    continue
                h = m.hist(name)
                have = True
                row[kind] = {
                    "count": h["count"],
                    "p50": round(hist_percentile(h, 50), 1),
                    "p99": round(hist_percentile(h, 99), 1),
                    "p99.9": round(hist_percentile(h, 99.9), 1),
                }
            if have:
                rows.append(row)
    return rows


def render_summary(rows: list[dict]) -> str:
    lines = [
        f"{'hop (tile < link)':<34} {'n':>9} "
        f"{'qwait p50/p99':>17} {'svc p50/p99':>17} "
        f"{'e2e p50/p99/p99.9':>26} {'bp%':>6}"
    ]
    for r in rows:
        q, s, e = r.get("qwait_us"), r.get("svc_us"), r.get("e2e_us")

        def pair(d):
            if d is None or not d["count"]:
                return "-"
            return f"{d['p50']:,.0f}/{d['p99']:,.0f}"

        e2e = "-"
        if e is not None and e["count"]:
            e2e = f"{e['p50']:,.0f}/{e['p99']:,.0f}/{e['p99.9']:,.0f}"
        lines.append(
            f"{r['tile'] + ' < ' + r['link']:<34} "
            f"{(q or {'count': 0})['count']:>9,} "
            f"{pair(q):>17} {pair(s):>17} {e2e:>26} {r['bp_pct']:>5.1f}%"
        )
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# CLI


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="fdttrace", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("wksp", help="topology workspace name (Topology(name=...))")
    ap.add_argument("--summary", action="store_true",
                    help="print the per-hop percentile table and exit")
    ap.add_argument("--follow", action="store_true",
                    help="re-print the summary every --interval seconds")
    ap.add_argument("--interval", "-i", type=float, default=2.0)
    ap.add_argument("--iterations", type=int, default=None,
                    help="stop --follow after N prints (default: forever)")
    ap.add_argument("--seconds", type=float, default=1.0,
                    help="span capture window for the trace export")
    ap.add_argument("--out", default=None, metavar="FILE",
                    help="write Chrome trace-event JSON here (default stdout)")
    ap.add_argument("--xplane", default=None, metavar="FILE",
                    help="a jax.profiler *.xplane.pb of the verify tile's "
                    "process: its fdt.* host spans (with their seq=, lanes= "
                    "and, on a worker's dispatch / land, dev= arguments) and "
                    "device ops are added to the export, tied to the rings' "
                    "clock by fdt.clock")
    args = ap.parse_args(argv)

    try:
        session = TraceSession.attach(args.wksp)
    except FileNotFoundError:
        print(
            f"fdttrace: no workspace {args.wksp!r} (is the topology "
            "running with a name, and was start() reached?)",
            file=sys.stderr,
        )
        return 2

    if args.follow:
        i = 0
        while args.iterations is None or i < args.iterations:
            print(render_summary(summary_rows(session)))
            print()
            i += 1
            if args.iterations is None or i < args.iterations:
                time.sleep(args.interval)
        return 0
    if args.summary:
        print(render_summary(summary_rows(session)))
        return 0

    if not session.rings:
        print(
            "fdttrace: workspace has no span rings — run the topology "
            "with enable_trace() (sampling > 0) for trace export",
            file=sys.stderr,
        )
        return 2
    session.drain()
    end = time.monotonic() + args.seconds
    while time.monotonic() < end:
        time.sleep(min(0.05, args.seconds))
        session.drain()
    profiler = None
    if args.xplane:
        profiler = profiler_events(args.xplane)
        if profiler[0] is None:
            print(
                f"fdttrace: {args.xplane} holds no fdt.clock span: its "
                "clock cannot be tied to the rings' (was the verify tile "
                "serving, with JAX, while it was traced?)",
                file=sys.stderr,
            )
            return 2
    events = chrome_trace(session, profiler)
    doc = json.dumps(events)
    if args.out:
        Path(args.out).write_text(doc)
        n_drop = sum(session.dropped.values())
        print(
            f"fdttrace: wrote {len(events)} events to {args.out}"
            + (f" ({n_drop} spans lost to ring laps)" if n_drop else "")
        )
    else:
        print(doc)
    return 0


if __name__ == "__main__":
    sys.exit(main())
