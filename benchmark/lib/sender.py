"""The benchmark's senders: one general generator, two loops.

open    a schedule fixed before the run: bursts of `burst` datagrams at
        constant spacing, sent by a process of its own whatever the
        system does with them; it reports when each datagram really left.
closed  flow-controlled: never more than `window` txns between the
        sender and the terminal counter, nor more unread datagrams than
        the receiving socket holds.  Runs in the caller's thread (it
        needs the deployment's counters).

Both use CLOCK_MONOTONIC (time.monotonic_ns), which all processes of a
host share.
"""

from __future__ import annotations

import socket
import time
from multiprocessing import shared_memory

import numpy as np


def burst_due_ns(n_rows: int, burst: int, interval_ns: int) -> np.ndarray:
    """Due time of every row, relative to the schedule's start."""
    return (np.arange(n_rows) // burst) * interval_ns


def open_loop_main(shm_name: str, n_rows: int, row_sz: int, addr,
                   bind_port: int, t_start_ns: int, burst: int,
                   interval_ns: int, conn) -> None:
    """Body of the open-loop sender process.  Sends row i at
    t_start_ns + (i // burst) * interval_ns; answers on `conn` with
    `sent_at`, the (n_rows,) int64 times at which each send returned, and
    `woke_at`, a burst: when the wait for its due time ended."""
    shm = shared_memory.SharedMemory(name=shm_name)
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        rows = np.ndarray((n_rows, row_sz), np.uint8, buffer=shm.buf)
        sock.bind(("127.0.0.1", bind_port))  # the address a stake names
        sock.connect(tuple(addr))
        sent_at = np.zeros(n_rows, np.int64)
        woke_at = np.zeros(-(-n_rows // burst), np.int64)
        now = time.monotonic_ns
        for first in range(0, n_rows, burst):
            due = t_start_ns + (first // burst) * interval_ns
            while True:
                left = due - now()
                if left <= 0:
                    break
                if left > 1_500_000:  # sleep to within a ms, then spin
                    time.sleep((left - 1_000_000) / 1e9)
            woke_at[first // burst] = now()
            for i in range(first, min(first + burst, n_rows)):
                sock.send(rows[i])
                sent_at[i] = now()
        conn.send(dict(sent_at=sent_at, woke_at=woke_at))
        del rows
    finally:
        sock.close()
        shm.close()
        conn.close()


def account(said: dict, due, burst: int, t0_ns: int, t1_ns: int) -> dict:
    """The `host` line's fields from the open-loop sender's own stamps,
    over the bursts due in [t0_ns, t1_ns): what open_loop_main answered
    against the schedule (`due`: every row's due time, ns)."""
    first = np.arange(0, len(due), burst)       # each burst's first row
    last = np.minimum(first + burst, len(due)) - 1
    win = np.flatnonzero((due[first] >= t0_ns) & (due[first] < t1_ns))
    if not len(win):
        return {}
    sent_at, woke = said["sent_at"], said["woke_at"][win]
    late = (sent_at[first] - due[first])[win]   # the burst's first send
    over = late > 1_000_000

    def p50_p99(ns):
        return [round(float(np.percentile(ns, q)) / 1e3, 1) for q in (50, 99)]

    return dict(
        sender_late_over_1ms=int(over.sum()),
        sender_late_sum_ms=round(int(late[over].sum()) / 1e6, 3),
        # late because woken late, or because the sends before it were slow
        sender_wake_late_us=p50_p99(woke - due[first][win]),
        # a burst's sends, first to last
        sender_burst_send_us=p50_p99(sent_at[last][win] - woke))


def closed_loop(sock, addr, rows, *, in_flight, unread, window: int,
                unread_max: int, t_stop_ns: int, tick, chunk: int = 512) -> int:
    """Send `rows` to `addr` until t_stop_ns, keeping in_flight(sent) <=
    window and unread(sent) <= unread_max (UDP has no backpressure: the
    socket buffer drops what overflows it).  `tick(now_ns, sent)` is
    called every turn (window edges, failure polls).  Returns the count
    sent; running out of rows before t_stop_ns is the caller's to judge."""
    sent, n = 0, len(rows)
    while sent < n:
        t = time.monotonic_ns()
        tick(t, sent)
        if t >= t_stop_ns:
            break
        room = min(window - in_flight(sent), unread_max - unread(sent),
                   n - sent, chunk)
        if room <= 0:
            time.sleep(0.0005)
            continue
        for i in range(sent, sent + room):
            sock.sendto(rows[i], addr)  # a row is one contiguous buffer
        sent += room
    return sent
