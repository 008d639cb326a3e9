"""The benchmark's senders: one general generator, two loops.  Each
sends every row of the corpus at its own length, one datagram a row.

open    a schedule fixed before the run: bursts of `burst` datagrams at
        constant spacing, sent by a process of its own whatever the
        system does with them; it reports when each datagram really left.
closed  flow-controlled: never more than `window` txns between the
        sender and the terminal counter, nor more unread datagrams than
        the receiving socket holds, each charged its own size; a turn's
        rows go in one sendmmsg(2).  Runs in the caller's thread (it
        needs the deployment's counters).

Both use CLOCK_MONOTONIC (time.monotonic_ns), which all processes of a
host share.
"""

from __future__ import annotations

import ctypes
import os
import socket
import struct
import time
from multiprocessing import shared_memory

import numpy as np


def burst_due_ns(n_rows: int, burst: int, interval_ns: int) -> np.ndarray:
    """Due time of every row, relative to the schedule's start."""
    return (np.arange(n_rows) // burst) * interval_ns


def open_loop_main(shm_name: str, off, addr, bind_port: int,
                   t_start_ns: int, burst: int, interval_ns: int,
                   conn) -> None:
    """Body of the open-loop sender process.  Sends row i, the bytes
    [off[i], off[i + 1]) of the shared memory, at
    t_start_ns + (i // burst) * interval_ns; answers on `conn` with
    `sent_at`, the (n_rows,) int64 times at which each send returned, and
    `woke_at`, a burst: when the wait for its due time ended."""
    shm = shared_memory.SharedMemory(name=shm_name)
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        rows, off = shm.buf, off.tolist()
        n_rows = len(off) - 1
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
                sock.send(rows[off[i]:off[i + 1]])
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


#: struct iovec and struct mmsghdr (a struct msghdr, then msg_len) as
#: glibc lays them out on a 64-bit Linux
_IOVEC = np.dtype([("base", np.uint64), ("len", np.uint64)])
_MMSGHDR = np.dtype({
    "names": ["name", "namelen", "iov", "iovlen", "control", "controllen",
              "flags", "len"],
    "formats": [np.uint64, np.uint32, np.uint64, np.uint64, np.uint64,
                np.uint64, np.int32, np.uint32],
    "offsets": [0, 8, 16, 24, 32, 40, 48, 56], "itemsize": 64})


class MultiSend:
    """sendmmsg(2): rows of one flat buffer sent with one system call,
    each row its own datagram to `addr`.  With one `sendto` a row the
    closed loop's own thread bound the flood on a TPU v5e host (about
    27 us a call there, 95% of the window in sending: PERF.md section
    6), so the sender, not the system, was measured."""

    def __init__(self, sock, addr, buf, off, most: int):
        self.fd = sock.fileno()
        self.off = np.asarray(off, np.int64)
        self.buf = np.ascontiguousarray(buf, np.uint8)  # kept alive
        host, port = addr
        self.name = np.frombuffer(
            struct.pack("=H", socket.AF_INET) + struct.pack("!H", port)
            + socket.inet_aton(host) + bytes(8), np.uint8).copy()
        self.iov = np.zeros(most, _IOVEC)
        self.hdr = np.zeros(most, _MMSGHDR)
        self.hdr["name"] = self.name.ctypes.data
        self.hdr["namelen"] = len(self.name)
        self.hdr["iov"] = self.iov.ctypes.data + _IOVEC.itemsize * np.arange(
            most, dtype=np.uint64)
        self.hdr["iovlen"] = 1
        libc = ctypes.CDLL(None, use_errno=True)
        self.sendmmsg = libc.sendmmsg
        self.sendmmsg.argtypes = [ctypes.c_int, ctypes.c_void_p,
                                  ctypes.c_uint, ctypes.c_int]
        self.sendmmsg.restype = ctypes.c_int

    def send(self, first: int, stop: int) -> None:
        """Rows [first, stop), at most `most` of them, all sent."""
        k = stop - first
        at = self.off[first:stop + 1]
        self.iov["base"][:k] = self.buf.ctypes.data + at[:-1]
        self.iov["len"][:k] = np.diff(at)
        done = 0
        while done < k:
            n = self.sendmmsg(self.fd, self.hdr.ctypes.data
                              + _MMSGHDR.itemsize * done, k - done, 0)
            if n < 0:
                err = ctypes.get_errno()
                raise OSError(err, os.strerror(err))
            done += n


def closed_loop(sock, addr, buf, off, *, in_flight, received, window: int,
                unread_bytes: int, charge, t_stop_ns: int, tick,
                chunk: int = 512, account: dict | None = None) -> int:
    """Send the rows (buf, off: row i is buf[off[i]:off[i + 1]]) to `addr`
    until t_stop_ns, keeping in_flight(sent) <= window, and the datagrams
    sent but not yet received() within `unread_bytes` of the
    receiving socket's buffer (UDP has no backpressure: the socket drops
    what overflows it).  `charge` (n_rows,): what each row's datagram
    costs that buffer.  `tick(now_ns, sent)` is called every turn
    (window edges, failure polls).  Returns the count sent; running out
    of rows before t_stop_ns is the caller's to judge.

    `account`, where given, is kept as the loop runs (totals, which the
    caller reads at its window's edges): `turns`; `full`, the turns that
    found no room (the receiver behind); `empty`, the turns that found
    every datagram sent already received (the receiving socket empty at
    that moment: with `full` rare, the sender behind); `send_ns`, the
    time spent in sending."""
    sent, n = 0, len(off) - 1
    cost = np.zeros(n + 1, np.int64)  # cost[i]: the charge of rows < i
    np.cumsum(charge, out=cost[1:])
    out = MultiSend(sock, addr, buf, off, chunk)
    acc = account if account is not None else {}
    for k in ("turns", "full", "empty", "send_ns"):
        acc.setdefault(k, 0)
    while sent < n:
        t = time.monotonic_ns()
        tick(t, sent)
        if t >= t_stop_ns:
            break
        got = received()
        fits = int(np.searchsorted(
            cost, cost[got] + unread_bytes, "right")) - 1
        room = min(window - in_flight(sent), fits - sent, n - sent, chunk)
        acc["turns"] += 1
        acc["empty"] += got >= sent
        if room <= 0:
            acc["full"] += 1
            time.sleep(0.0005)
            continue
        t = time.monotonic_ns()
        out.send(sent, sent + room)
        acc["send_ns"] += time.monotonic_ns() - t
        sent += room
    return sent
