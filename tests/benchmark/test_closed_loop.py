"""The closed loop's sender: one sendmmsg(2) a turn sends every row as its
own datagram, byte for byte and in order, and the loop's account says
whether the sender or the receiver was behind (turns that found the
socket full, turns that found it empty, the time spent in sending),
which the `sender.send_busy_share.ingress` reader turns into a share."""

import os
import socket
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import run as RUN  # noqa: E402
from benchmark.lib import deploy, sender  # noqa: E402

ROOT = os.path.join(REPO, "benchmark")


def _rows(n: int, seed: int):
    """n rows of 1 to 1,232 random bytes, laid flat."""
    rng = np.random.default_rng(seed)
    off = np.zeros(n + 1, np.int64)
    np.cumsum(rng.integers(1, 1233, n), out=off[1:])
    return rng.integers(0, 256, int(off[-1]), np.uint8), off


def _pair():
    rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 21)
    rx.bind(("127.0.0.1", 0))
    return rx, socket.socket(socket.AF_INET, socket.SOCK_DGRAM)


def test_one_call_sends_every_row_as_its_own_datagram_in_order():
    buf, off = _rows(300, 1)
    rx, tx = _pair()
    try:
        out = sender.MultiSend(tx, rx.getsockname(), buf, off, 64)
        for first in range(0, 300, 64):  # the last call sends 44
            out.send(first, min(first + 64, 300))
        rx.settimeout(5.0)
        got = [rx.recv(2048) for _ in range(300)]
    finally:
        rx.close()
        tx.close()
    assert got == [buf[a:b].tobytes() for a, b in zip(off[:-1], off[1:])]


def test_the_account_says_who_was_behind():
    """Nobody reads: after the first turns every turn finds the socket
    full.  A reader that drains at once: the sender finds it empty."""
    buf, off = _rows(2000, 2)
    rx, tx = _pair()
    acct: dict = {}
    try:
        sent = sender.closed_loop(
            tx, rx.getsockname(), buf, off, in_flight=lambda s: 0,
            received=lambda: 0, window=1 << 20, unread_bytes=64 * 2304,
            charge=np.full(2000, 2304),
            t_stop_ns=time.monotonic_ns() + 200_000_000,
            tick=lambda now, s: None, account=acct)
    finally:
        rx.close()
        tx.close()
    assert sent == 64 and acct["send_ns"] > 0
    assert acct["full"] == acct["turns"] - 1 and acct["empty"] == 1
    # the receiver keeps up: every turn finds what was sent already read
    buf, off = _rows(400, 3)
    rx, tx = _pair()
    got, stop, acct = [], threading.Event(), {}

    def serve():
        rx.settimeout(0.05)
        while not stop.is_set():
            try:
                got.append(rx.recv(2048))
            except socket.timeout:
                pass

    t = threading.Thread(target=serve)
    t.start()
    try:
        sent = sender.closed_loop(
            tx, rx.getsockname(), buf, off, in_flight=lambda s: 0,
            received=lambda: len(got), window=1 << 20,
            unread_bytes=deploy.socket_window(),
            charge=np.full(400, 2304), t_stop_ns=1 << 62,
            tick=lambda now, s: None, chunk=16, account=acct)
        deadline = time.monotonic() + 10
        while len(got) < sent and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        stop.set()
        t.join(10)
        rx.close()
        tx.close()
    assert not t.is_alive() and sent == 400 and len(got) == 400
    assert acct["turns"] >= 400 // 16 and acct["empty"] >= 1
    busy = RUN.load_reader(ROOT, "sender_busy")
    assert busy({"send_busy_share": 0.25}) == 25.0
    assert busy({"send_busy_share": None}) is None
