"""Wire ingress end to end: txns arrive over REAL UDP sockets — legacy
datagrams and a loopback QUIC connection (handshake included) — then flow
through quic tile → verify → dedup → sink.

This is the gap the round-1 review named: "the pipeline starts at a
synthetic tile, not the wire".  Reference shape: net → quic (fd_quic.c, incl. the legacy
UDP path) → verify → dedup (src/app/fdctl/config.c topology)."""

import time

import numpy as np

from firedancer_tpu.ballet import txn as T
from firedancer_tpu.disco import Topology
from firedancer_tpu.ops.ed25519 import golden
from firedancer_tpu.tiles import wire
from firedancer_tpu.tiles.dedup import DedupTile
from firedancer_tpu.tiles.quic import QuicIngressTile
from firedancer_tpu.tiles.sink import SinkTile
from firedancer_tpu.tiles.verify import VerifyTile
from firedancer_tpu.waltz import quic as Q
from firedancer_tpu.waltz.udpsock import UdpSock
import pytest

pytestmark = pytest.mark.slow


def _signed_txn(rng, sk, pk, blockhash, corrupt=False) -> bytes:
    extra = [rng.integers(0, 256, 32, np.uint8).tobytes() for _ in range(2)]
    data = rng.integers(0, 256, 24, np.uint8).tobytes()
    body = T.build([bytes(64)], [pk] + extra, blockhash, [(2, [0, 1], data)])
    desc = T.parse(body)
    sig = golden.sign(sk, desc.message(body))
    payload = body[:1] + sig + body[1 + 64 :]
    if corrupt:
        b = bytearray(payload)
        b[5] ^= 0xFF
        payload = bytes(b)
    return payload


def test_wire_ingress_quic_and_udp():
    rng = np.random.default_rng(31)
    identity = rng.integers(0, 256, 32, np.uint8).tobytes()
    sk = rng.integers(0, 256, 32, np.uint8).tobytes()
    pk = golden.public_from_secret(sk)
    blockhash = rng.integers(0, 256, 32, np.uint8).tobytes()

    udp_txns = [_signed_txn(rng, sk, pk, blockhash) for _ in range(4)]
    quic_txns = [_signed_txn(rng, sk, pk, blockhash) for _ in range(5)]
    bad_txn = _signed_txn(rng, sk, pk, blockhash, corrupt=True)

    qt = QuicIngressTile(identity)
    verify = VerifyTile(msg_width=256, max_lanes=32, pad_full=True,
                        pre_dedup=False)
    dedup = DedupTile(depth=1 << 10)
    sink = SinkTile(record=True)

    topo = Topology()
    topo.link("quic_verify", depth=256, mtu=wire.LINK_MTU)
    topo.link("verify_dedup", depth=256, mtu=wire.LINK_MTU)
    topo.link("dedup_sink", depth=256, mtu=wire.LINK_MTU)
    topo.tile(qt, outs=["quic_verify"])
    topo.tile(verify, ins=[("quic_verify", True)], outs=["verify_dedup"])
    topo.tile(dedup, ins=[("verify_dedup", True)], outs=["dedup_sink"])
    topo.tile(sink, ins=[("dedup_sink", True)])
    topo.build()
    topo.start(batch_max=64)
    try:
        # ---- legacy UDP path: one datagram per txn (+ one corrupted)
        tx = UdpSock()
        for t in udp_txns + [bad_txn]:
            tx.sock.sendto(t, qt.udp_addr)

        # ---- QUIC path: handshake over the real socket, then streams
        client = Q.QuicClient()
        csock = UdpSock()
        csock.sock.settimeout(5.0)

        def pump(deadline_s=10.0):
            end = time.monotonic() + deadline_s
            while time.monotonic() < end:
                sent = False
                for d in client.conn.datagrams_out():
                    csock.sock.sendto(d, qt.quic_addr)
                    sent = True
                try:
                    csock.sock.settimeout(0.2)
                    data, _ = csock.sock.recvfrom(2048)
                    client.conn.on_datagram(data)
                    continue
                except OSError:
                    pass
                if not sent and client.conn.established:
                    return
                topo.poll_failure()
            raise TimeoutError("QUIC handshake did not complete")

        pump()
        assert client.conn.established
        assert client.conn.tls.peer_identity == golden.public_from_secret(
            identity
        )
        for t in quic_txns:
            client.conn.send_txn(t)
        for d in client.conn.datagrams_out():
            csock.sock.sendto(d, qt.quic_addr)

        n_good = len(udp_txns) + len(quic_txns)
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            if topo.metrics("sink").counter("sunk_frags") >= n_good:
                break
            time.sleep(0.02)
        topo.halt()

        mq = topo.metrics("quic")
        mv = topo.metrics("verify")
        ms = topo.metrics("sink")
        assert mq.counter("rx_txns_udp") == len(udp_txns) + 1
        assert mq.counter("rx_txns_quic") == len(quic_txns)
        assert mq.counter("conns_opened") == 1
        assert mv.counter("verify_fail_txns") == 1  # the corrupted one
        assert ms.counter("sunk_frags") == n_good

        # end-to-end identity: the sink's dedup tags are exactly the first
        # 8 signature bytes of every good wire txn, and each recorded row
        # starts with the original txn bytes
        def tag(t: bytes) -> int:
            d = T.parse(t)
            return int.from_bytes(
                t[d.signature_off : d.signature_off + 8], "little"
            )

        want = set(udp_txns + quic_txns)
        assert set(sink.all_sigs().tolist()) == {tag(t) for t in want}
        with sink.lock:
            recorded = [row.tobytes() for rows in sink.payloads for row in rows]
        for t in want:
            assert any(r.startswith(t) for r in recorded)
        tx.close()
        csock.close()
    finally:
        topo.halt()
        topo.close()
