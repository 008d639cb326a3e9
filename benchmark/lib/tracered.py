"""Reduction of a profiler trace to device metrics.

Input is plain data, so the arithmetic is testable without a profiler:
    trace = [(plane_name, line_name, [(event_name, start_ns, dur_ns), ...])]
`load` makes that from an `.xplane.pb` with jax.profiler.ProfileData.

Busy is the UNION of the intervals in which an operation ran on the
device; idle share is 1 - busy / traced window.  Kernel time is the
summed device duration of the events whose name matches.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

#: planes that are devices, and the line on them that holds single ops
DEVICE_PLANE = r"^/device:(TPU|GPU):\d+"
OPS_LINE = r"^XLA Ops"


def load(trace_dir: str):
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return []
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    return [
        (pl.name, ln.name,
         [(op_name(e.name), int(e.start_ns), int(e.duration_ns))
          for e in ln.events])
        for pl in pd.planes for ln in pl.lines
    ]


def op_name(name: str) -> str:
    """A device op's event carries its whole HLO line
    (`%verify_core.1 = s32[1,4096]{...} custom-call(...)`): keep the
    op's own name."""
    return name.split(" = ", 1)[0].lstrip("%") if " = " in name else name


def span_s(trace) -> float:
    """The traced window as the trace itself knows it: first start to
    last end over every event of every plane (the runtime's host threads
    write events all through it, whatever the device does)."""
    lo = min((s for _, _, ev in trace for _, s, _ in ev), default=0)
    hi = max((s + d for _, _, ev in trace for _, s, d in ev), default=0)
    return (hi - lo) / 1e9


def device_ops(trace, plane=DEVICE_PLANE, line=OPS_LINE) -> dict:
    """-> {plane_name: [events]} of the op lines of each device plane."""
    out: dict = {}
    for pname, lname, events in trace:
        if re.search(plane, pname) and re.search(line, lname):
            out.setdefault(pname, []).extend(events)
    return out


def union_ns(events) -> int:
    """Total length of the union of [start, start + dur) intervals."""
    total, end = 0, None
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if d <= 0:
            continue
        if end is None or s > end:
            total += d
            end = s + d
        elif s + d > end:
            total += s + d - end
            end = s + d
    return total


def busy_s(trace, **sel) -> float | None:
    """Seconds an op ran on the device, averaged over the devices that
    ran any; None when the trace holds no device op."""
    per = [union_ns(ev) for ev in device_ops(trace, **sel).values()]
    per = [b for b in per if b > 0]
    return sum(per) / len(per) / 1e9 if per else None


def kernel(trace, pattern: str, **sel):
    """-> (summed seconds, calls) of device events matching `pattern`."""
    dur = calls = 0
    for events in device_ops(trace, **sel).values():
        for name, _, d in events:
            if re.search(pattern, name):
                dur += d
                calls += 1
    return dur / 1e9, calls


def top_ops(trace, k: int = 10, **sel) -> list:
    tot: dict = {}
    for events in device_ops(trace, **sel).values():
        for name, _, d in events:
            tot[name] = tot.get(name, 0) + d
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, d / 1e9] for n, d in top]


def idle_gaps(trace, k: int = 10, **sel) -> list:
    """The k longest gaps between device ops, each named by the host
    event (any non-device line) that overlaps it most — or
    `unattributed`: the program writes no host spans of its own yet."""
    gaps = []
    for events in device_ops(trace, **sel).values():
        end = None
        for _, s, d in sorted(events, key=lambda e: e[1]):
            if end is not None and s > end:
                gaps.append((end, s))
            end = max(end or 0, s + d)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    host = [e for pname, _, events in trace
            if not re.search(sel.get("plane", DEVICE_PLANE), pname)
            for e in events if e[2] > 0]
    start = np.array([e[1] for e in host], np.int64)
    end = start + np.array([e[2] for e in host], np.int64)
    out = []
    for a, b in gaps:
        name = "unattributed"
        if len(host):
            overlap = np.minimum(end, b) - np.maximum(start, a)
            i = int(overlap.argmax())  # the first of equals, as listed
            if overlap[i] > 0:
                name = host[i][0]
        out.append([name, (b - a) / 1e9])
    return out
