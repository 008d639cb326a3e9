"""Completion lag from a counter: the arithmetic of `lag_p50_ms` and
`lag_p95_ms`.

The k-th valid first-seen txn of the schedule is due at t_due[k].  The
deployment's terminal counter (the leader's sum of executed_txns — what
RPC getTransactionCount answers) is sampled on the same clock; t_done[k]
is the first sample at which it has grown by k + 1.  lag = t_done -
t_due: timed from when the txn was DUE, so a stall charges every txn
queued behind it, and it is what a client polling the count sees.
"""

from __future__ import annotations

import time

import numpy as np


#: a gap between two polls, or a callback, longer than this is kept
GAP_NS = 1_000_000


def sample_until(read, done, *, period_s: float = 0.0002,
                 every=None, every_ns: int = 100_000_000):
    """Poll read() until done(now_ns, count) says so.  Returns the change
    points (ts ns, counts) — strictly increasing counts —, the longest
    gap between two polls in ns, and what kept the loop from polling:
    `{"poll": (n, 2), "every": (m, 2)}`, rows of (when ns, how long ns)
    for every gap between two polls and every callback over GAP_NS (the
    sampler off its core, or at other work: a txn that lands meanwhile is
    stamped at the next poll).  `every(now_ns)` runs about every every_ns
    (failure polls, window-edge snapshots)."""
    ts, cs = [], []
    held = {"poll": [], "every": []}
    last, gap, prev, nxt = None, 0, time.monotonic_ns(), 0
    while True:
        now = time.monotonic_ns()
        c = read()
        gap = max(gap, now - prev)
        if now - prev > GAP_NS:
            held["poll"].append((now, now - prev))
        prev = now
        if c != last:
            ts.append(now)
            cs.append(c)
            last = c
        if every is not None and now >= nxt:
            every(now)
            nxt = now + every_ns
            prev = time.monotonic_ns()  # the callback is not a sampling gap
            if prev - now > GAP_NS:
                held["every"].append((now, prev - now))
        if done(now, c):
            return (np.array(ts, np.int64), np.array(cs, np.int64), gap,
                    {k: np.array(v, np.int64).reshape(-1, 2)
                     for k, v in held.items()})
        time.sleep(period_s)


def held_in(rows, t0_ns: int, t1_ns: int) -> tuple:
    """(count, summed ns, longest ns) of sample_until's rows whose `when`
    lies in [t0_ns, t1_ns)."""
    d = rows[(rows[:, 0] >= t0_ns) & (rows[:, 0] < t1_ns), 1]
    return len(d), int(d.sum()), int(d.max()) if len(d) else 0


def count_at(ts, cs, t_ns: int, base: int) -> int:
    """The counter as last sampled at or before t_ns."""
    i = int(np.searchsorted(ts, t_ns, side="right")) - 1
    return int(cs[i]) if i >= 0 else base


def completion_lags(t_due, ts, cs, base: int, t_give_up: int):
    """-> (lag ns per request, landed mask).  A request the counter never
    reached by the end of the drain has the lag t_give_up - t_due and
    counts as failed."""
    need = base + 1 + np.arange(len(t_due))
    idx = np.searchsorted(cs, need, side="left")
    landed = idx < len(cs)
    if not len(cs):
        return t_give_up - np.asarray(t_due), landed
    t_done = np.where(landed, ts[np.minimum(idx, len(ts) - 1)], t_give_up)
    return t_done - np.asarray(t_due), landed


def after_send(lag, sent_at, t_due):
    """The lag counted from when the txn really LEFT the benchmark's
    sender where that was after its due time: t_done - max(t_due,
    sent_at), the lag a remote sender's txn would have seen.  The lag
    from due holds every ms the generator ran late; the distance between
    the two is the generator's share.  Sent early is no credit."""
    late = np.maximum(np.asarray(sent_at, np.int64)
                      - np.asarray(t_due, np.int64), 0)
    return np.asarray(lag, np.int64) - late


def percentile(values, q: float) -> float:
    """The q-th percentile over ALL values (never over chunks)."""
    return float(np.percentile(np.asarray(values, np.float64), q))
