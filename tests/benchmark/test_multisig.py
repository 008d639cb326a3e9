"""Multi-signature traffic, which a configuration states as data
(`txn_shape`): the generator's N-signer transfers held against the wire
format and OpenSSL's Ed25519 (not the program's), the reference's fee of
5,000 lamports a signature, the control that checks signature 0 alone,
the senders at each row's own length, the closed loop's unread bound at
the kernel's charge for a 1,175-byte datagram, and whole runs of an
N-signer cell laid as files in a COPY of benchmark/: correct with the
strict verifier, not correct with the tile's per-txn join cut to lane 0.
"""

import json
import os
import shutil
import socket
import subprocess
import sys
import threading
import time
from multiprocessing import shared_memory

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import run as RUN  # noqa: E402
from benchmark.lib import corpus as C  # noqa: E402
from benchmark.lib import deploy, ledger, reference, sender  # noqa: E402

ROOT = os.path.join(REPO, "benchmark")
SEED = (1 << 31) + 38
UNIFORM = {"signers": {str(n): 1 for n in range(1, C.MAX_SIGNERS + 1)}}


def _weights(shape):
    return C.signer_weights({"txn_shape": shape})


@pytest.fixture(scope="module")
def corp():
    return C.make_corpus(1500, 64, 16, 4, seed=SEED, workers=1,
                         weights=_weights(UNIFORM))


def _rows(corp):
    buf, off = corp["buf"], corp["off"]
    return [buf[a:b].tobytes() for a, b in zip(off, off[1:])]


def _failing_slots(raw: bytes) -> list:
    """The wire format read by hand; each signature checked by OpenSSL."""
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey,
    )

    n = raw[0]
    msg = raw[1 + 64 * n:]
    fails = []
    for j in range(n):
        key = Ed25519PublicKey.from_public_bytes(msg[4 + 32 * j:36 + 32 * j])
        try:
            key.verify(raw[1 + 64 * j:65 + 64 * j], msg)
        except InvalidSignature:
            fails.append(j)
    return fails


def test_without_the_key_the_shape_is_one_signer():
    assert C.signer_weights({}) is None
    bh = bytes(range(32))
    assert (C.template(bh, 1) == C.template(bh)).all()
    assert len(C.template(bh)) == C.TXN_SZ == C.txn_size(1)
    with pytest.raises(ValueError, match="rehearse"):
        C.signer_weights({"txn_shape": UNIFORM,
                          "rehearse": {"txn_shape": {"signers": {"1": 1}}}})
    for bad in ({"0": 1}, {"12": 1}, {"3": -1}, {"2": 0}):
        with pytest.raises(ValueError):
            _weights({"signers": bad})


def test_every_row_is_an_n_signer_transfer_of_119_plus_96_n_bytes(corp):
    rows, src, kind = _rows(corp), corp["src"], corp["kind"]
    assert corp["off"][-1] == sum(map(len, rows)) == len(corp["buf"])
    for i, raw in enumerate(rows):
        n = raw[0]
        assert n == corp["nsig"][src[i]] and len(raw) == C.txn_size(n)
        msg = raw[1 + 64 * n:]
        assert tuple(msg[:4]) == (n, n - 1, 1, n + 2)
        keys = [msg[4 + 32 * k:36 + 32 * k] for k in range(n + 2)]
        assert len(set(keys)) == n + 2 and keys[-1] == C.SYSTEM_PROGRAM
        assert keys[0] == corp["pubs"][corp["payer"][src[i]]].tobytes()
        assert keys[n] == corp["pubs"][corp["dest"][src[i]]].tobytes()
        ix = msg[4 + 32 * (n + 2) + 32:]
        assert tuple(ix[:6]) == (1, n + 1, 2, 0, n, 12)
        assert int.from_bytes(ix[6:10], "little") == 2  # Transfer
        assert int.from_bytes(ix[10:], "little") == corp["amount"][src[i]]
    # re-sends are byte-for-byte copies after their originals
    first = {}
    for i, s in enumerate(src):
        if kind[i] == C.KIND_DUP:
            assert first[s] < i and rows[first[s]] == rows[i]
        first.setdefault(int(s), i)


def test_every_signature_verifies_but_one_of_each_bad_row(corp):
    slots = []
    for raw, k, j in zip(_rows(corp), corp["kind"], corp["bad_sig"]):
        fails = _failing_slots(raw)
        if k == C.KIND_BAD:
            assert fails == [j]
            slots.append((raw[0], int(j)))
        else:
            assert fails == [] and j == -1
    # the failing slot is uniform over the txn's N: every slot of the
    # widest txns is hit, and not only slot 0 of the others
    assert {j for _, j in slots} == set(range(C.MAX_SIGNERS))
    assert all(any(j for m, j in slots if m == n) for n in range(2, 12))
    # each bad txn is one of its own: its tag is no other row's
    tags = [raw[1:9] for raw in _rows(corp)]
    bad = np.flatnonzero(corp["kind"] == C.KIND_BAD)
    assert all(tags.count(tags[i]) == 1 for i in bad)


@pytest.mark.parametrize("shape", [UNIFORM, {"signers": {"1": 3, "11": 1}},
                                   {"signers": {"2": 1, "5": 2, "9": 4}}])
def test_signer_counts_follow_the_weights_on_every_seed(shape):
    w = _weights(shape)
    got = [C.make_corpus(300, 32, 16, 8, seed=s, workers=1, weights=w)
           for s in (1, SEED)]
    for corp in got:
        want = np.bincount(np.concatenate([C.apportion(w, 300),
                                           C.apportion(w, 300 // 8)]),
                           minlength=C.MAX_SIGNERS + 1)
        assert (np.bincount(corp["nsig"], minlength=len(want)) == want).all()
        share = want[1:] / want.sum()
        assert np.abs(share - w[1:] / w.sum()).max() < 0.01
        assert (np.diff(corp["off"]) == C.txn_size(
            corp["nsig"][corp["src"]])).all()
    # the seed deals the same set of sizes in another order
    a, b = (np.diff(c["off"]) for c in got)
    assert sorted(a) == sorted(b) and (a != b).any()
    again = C.make_corpus(300, 32, 16, 8, seed=SEED, workers=1, weights=w)
    assert (again["buf"] == got[1]["buf"]).all()


def test_the_pool_signs_what_one_process_signs():
    w = _weights(UNIFORM)
    one = C.make_corpus(700, 32, 16, 8, seed=SEED, workers=1, weights=w)
    pool = C.make_corpus(700, 32, 16, 8, seed=SEED, workers=3, weights=w)
    assert (one["buf"] == pool["buf"]).all()


def test_the_reference_charges_5000_lamports_a_signature(corp):
    n = len(corp["kind"])
    out = reference.outcome(corp, n)
    bal = {i: C.START_LAMPORTS for i in range(len(corp["pubs"]))}
    for s in corp["src"][corp["kind"] == C.KIND_UNIQUE]:
        amt = int(corp["amount"][s])
        bal[int(corp["payer"][s])] -= amt + 5000 * int(corp["nsig"][s])
        bal[int(corp["dest"][s])] += amt
    assert list(out["balances"]) == [bal[i] for i in range(len(bal))]
    assert out["landed"] == (corp["kind"] == C.KIND_UNIQUE).sum()
    tags = sorted(int.from_bytes(r[1:9], "little") for r, k in zip(
        _rows(corp), corp["kind"]) if k == C.KIND_UNIQUE)
    assert list(out["tags"]) == tags


def test_each_control_fails_and_lane0_admits_the_bad_rows_past_slot_0(corp):
    n = len(corp["kind"])
    exp = reference.outcome(corp, n)
    past0 = int(((corp["kind"] == C.KIND_BAD) & (corp["bad_sig"] > 0)).sum())
    assert 0 < past0 < (corp["kind"] == C.KIND_BAD).sum()
    for how, extra in (({"verify": False}, (corp["kind"] == 2).sum()),
                       ({"dedup": False}, (corp["kind"] == 1).sum()),
                       ({"verify": "lane0"}, past0)):
        out = reference.outcome(corp, n, **how)
        checks = {k: v for k, v, _ in ledger.compare(
            ledger.sound_observation(out, n), exp)}
        assert not ledger.correct(ledger.compare(
            ledger.sound_observation(out, n), exp))
        assert checks["landed_off"] == checks["tags_differ"] == extra
        assert checks["balances_differ"] > 0


def _cell_files(root, bad_every=8):
    """The N-signer deployment and its flood, as files beside the ones
    that are there: the ingress configuration with a `txn_shape`."""
    conf = json.load(open(root / "configs" / "ingress.json"))
    conf["txn_shape"] = UNIFORM
    (root / "configs" / "ingress_multisig.json").write_text(json.dumps(conf))
    cell = json.load(open(root / "workloads" / "ingress.flood.json"))
    cell.update(config="ingress_multisig", bad_every=bad_every, dup_every=8,
                metrics=["verified_tps"])
    (root / "workloads" / "ingress_multisig.flood.json").write_text(
        json.dumps(cell))


@pytest.fixture
def copy(tmp_path):
    root = tmp_path / "benchmark"
    shutil.copytree(ROOT, root, ignore=shutil.ignore_patterns("__pycache__"))
    _cell_files(root)
    return root


def test_control_py_fails_each_of_three_controls(copy):
    cell = json.load(open(copy / "workloads" / "ingress_multisig.flood.json"))
    cell["corpus_tps"] = 300  # CPU test size: 300 txns a second
    (copy / "workloads" / "ingress_multisig.flood.json").write_text(
        json.dumps(cell))
    r = subprocess.run(
        [sys.executable, str(copy / "control.py"), "--workload",
         "ingress_multisig.flood", "--seconds", "1", "--seeds", "3",
         str(SEED)], capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("control")]
    assert len(lines) == 6 and all("correct=False" in ln for ln in lines)
    assert sum("verify=lane0" in ln for ln in lines) == 2


def _serve(rx, got, stop):
    rx.settimeout(0.05)
    while not stop.is_set():
        try:
            got.append(rx.recv(2048))
        except socket.timeout:
            pass


def test_the_senders_send_each_row_at_its_own_length():
    corp = C.make_corpus(120, 32, 8, 8, seed=SEED, workers=1,
                         weights=_weights(UNIFORM))
    buf, off, want = corp["buf"], corp["off"], _rows(corp)
    # the closed loop
    rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 21)
    rx.bind(("127.0.0.1", 0))
    got, stop = [], threading.Event()
    t = threading.Thread(target=_serve, args=(rx, got, stop))
    t.start()
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        sent = sender.closed_loop(
            tx, rx.getsockname(), buf, off, in_flight=lambda s: 0,
            received=lambda: len(got), window=1 << 20,
            unread_bytes=deploy.socket_window(),
            charge=np.full(len(want), 2304), t_stop_ns=1 << 62,
            tick=lambda now, s: None, chunk=7)
        deadline = time.monotonic() + 10
        while len(got) < sent and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        stop.set()
        t.join(10)
        tx.close()
    assert not t.is_alive() and sent == len(want) and got == want
    # the open loop, from shared memory, as its process runs it
    shm = shared_memory.SharedMemory(create=True, size=buf.nbytes)
    got.clear()
    stop.clear()
    t = threading.Thread(target=_serve, args=(rx, got, stop))
    t.start()
    try:
        np.ndarray(buf.shape, np.uint8, buffer=shm.buf)[:] = buf

        class Conn:
            def send(self, said):
                self.said = said

            def close(self):
                pass

        conn = Conn()
        sender.open_loop_main(shm.name, off, rx.getsockname(),
                              deploy.free_udp_port(), time.monotonic_ns(),
                              16, 1_000_000, conn)
        deadline = time.monotonic() + 10
        while len(got) < len(want) and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        stop.set()
        t.join(10)
        rx.close()
        shm.close()
        shm.unlink()
    assert got == want and (conn.said["sent_at"] > 0).all()


def test_the_unread_bound_holds_1175_byte_rows_with_no_drop():
    """Nobody reads the socket: the closed loop stops at its bound, and
    the kernel's own count of the unread bytes stays within it."""
    n = 4000
    off = np.arange(n + 1, dtype=np.int64) * C.txn_size(C.MAX_SIGNERS)
    buf = np.zeros(off[-1], np.uint8)
    size = deploy.udp_truesize([C.txn_size(C.MAX_SIGNERS)])
    assert size[C.txn_size(C.MAX_SIGNERS)] >= deploy.MIN_TRUESIZE
    for charge, fits in ((size[C.txn_size(C.MAX_SIGNERS)], True),
                         # what the bound charged every datagram before
                         (deploy.MIN_TRUESIZE, None)):
        rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        # the receiving tile's socket asks for what waltz/udpsock.py does
        rx.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 21)
        rx.bind(("127.0.0.1", 0))
        tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        port = rx.getsockname()[1]
        try:
            sent = sender.closed_loop(
                tx, ("127.0.0.1", port), buf, off, in_flight=lambda s: 0,
                received=lambda: 0, window=n,
                unread_bytes=deploy.socket_window(),
                charge=np.full(n, charge),
                t_stop_ns=time.monotonic_ns() + 300_000_000,
                tick=lambda now, s: None)
            time.sleep(0.05)
            queued = deploy._rx_queue(port)
            drops = deploy.udp_kernel_drops(port)
        finally:
            rx.close()
            tx.close()
        assert 0 < sent < n
        if fits:
            assert drops == 0 and queued <= deploy.socket_window()
            assert sent == deploy.socket_window() // charge
        else:  # 1,280 a datagram: the half of the buffer to spare is spent
            assert queued > deploy.socket_window() or drops > 0


def _rig(monkeypatch, root, lane0: bool):
    """test_benchmark.py's rig (thread runtime, the strict host verifier
    standing in for the device), and with `lane0` the verify tile's
    per-txn join cut to the verdict of each txn's first lane."""
    from benchmark.lib.deploy import Deployment
    from firedancer_tpu.tiles.verify import VerifyTile

    def host_verifier(digests, sigs, pubs):
        from firedancer_tpu.ops.ed25519 import hostpath

        return hostpath.verify_batch_digest_host(digests, sigs, pubs)

    monkeypatch.setattr(VerifyTile, "_make_device_fns",
                        lambda self: [host_verifier] * self.n_devices)
    monkeypatch.setattr(Deployment, "parent_backend_initialized",
                        lambda self: False)
    if lane0:
        land = VerifyTile._land_batch

        def land_by_lane0(self, ctx, meta, ok):
            cnt = meta["sig_cnt"]
            first = np.concatenate(([0], np.cumsum(cnt)[:-1]))
            return land(self, ctx, meta, np.repeat(ok[first], cnt))

        monkeypatch.setattr(VerifyTile, "_land_batch", land_by_lane0)
    return RUN.run_cell(
        str(root), "ingress_multisig.flood", seed=SEED, seconds=1.0,
        trace=False, rehearse=True, require_chip=False,
        overrides={"topo": {"runtime": "thread", "stem": "python"}})


@pytest.mark.parametrize("lane0,failing", [
    (False, set()),
    (True, {"landed_off", "rejected_off", "tags_differ"}),
])
def test_a_whole_run_of_n_signer_traffic_laid_as_files(monkeypatch, capsys,
                                                       copy, lane0, failing):
    res = _rig(monkeypatch, copy, lane0)
    bad = {k for k, (v, lim) in res["checks"].items() if v > lim}
    assert res["correct"] == (not failing) and failing <= bad, res["checks"]
    assert res["attempted"] > 0
    if not failing:
        assert not bad and res["failed"] == 0
        assert set(res["metrics"]) == {"verified_tps", "setup_s"}
    # the corpus line says the shape; the result line does not
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines()
                if ln.startswith("benchmark corpus:"))
    kv = dict(w.split("=", 1) for w in line.split()[2:])
    assert 600 < float(kv["txn_bytes_mean"]) < 800
    assert int(kv["txn_bytes_max"]) == C.txn_size(C.MAX_SIGNERS)
    assert int(kv["lanes"]) > 5 * int(kv["rows"])
    assert float(kv["sign_s"]) > 0
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
