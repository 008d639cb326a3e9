"""Shred egress pipeline over real rings: poh -> shred (-> keyguard sign
-> ) -> store, then receiver-side FEC reconstruction of the stored block.

Reference analog: the fd_shred.c -> fd_store.c tile chain
(src/app/fdctl/run/tiles/), driven here by the PoH clock and the keyguard
sign tile exactly as in the production topology.
"""

import time

import numpy as np
import pytest

from firedancer_tpu.ballet import shred as SH
from firedancer_tpu.disco import Topology
from firedancer_tpu.disco.fec_resolver import FecResolver
from firedancer_tpu.ops.ed25519 import golden
from firedancer_tpu.tiles.poh import ENTRY_SZ, PohTile
from firedancer_tpu.tiles.shred import ShredTile
from firedancer_tpu.tiles.sign import ROLE_SHRED, SignTile
from firedancer_tpu.tiles.store import StoreTile


@pytest.mark.slow
def test_shred_store_pipeline(tmp_path):
    rng = np.random.default_rng(11)
    identity = rng.integers(0, 256, 32, np.uint8).tobytes()
    leader_pub = golden.public_from_secret(identity)

    poh = PohTile(tick_batch=8, ticks_per_slot=128)
    shred = ShredTile(shred_version=7)
    sign = SignTile(identity, roles=[ROLE_SHRED])
    store = StoreTile(
        str(tmp_path / "blockstore"),
        verify_sig=lambda sig, root, slot: golden.verify(
            root, sig, leader_pub
        ) == 0,
    )

    topo = Topology()
    topo.link("poh_shred", depth=4096, mtu=ENTRY_SZ)
    topo.link("shred_store", depth=4096, mtu=SH.MAX_SZ)
    topo.link("shred_sign", depth=256, mtu=32)
    topo.link("sign_shred", depth=256, mtu=64)
    topo.tile(poh, outs=["poh_shred"])
    topo.tile(
        shred,
        ins=[("poh_shred", True), ("sign_shred", True)],
        outs=["shred_store", "shred_sign"],
    )
    topo.tile(sign, ins=[("shred_sign", True)], outs=["sign_shred"])
    topo.tile(store, ins=[("shred_store", True)])
    topo.build()
    topo.start(batch_max=512)
    try:
        deadline = time.monotonic() + 120.0
        ms = topo.metrics("store")
        while time.monotonic() < deadline:
            topo.poll_failure()
            if ms.counter("completed_slots") >= 2:
                break
            time.sleep(0.02)
        topo.halt()
        assert ms.counter("completed_slots") >= 2
        assert topo.metrics("shred").counter("sign_requests") > 0
        # published requests == responses + in flight at the keyguard
        # (pending_cnt also counts queued-but-unsent requests in _signq)
        assert topo.metrics("shred").counter("sign_requests") == topo.metrics(
            "shred"
        ).counter("sign_responses") + shred.pending_cnt - shred.signq_len
        assert topo.metrics("sign").counter("refused") == 0
        bs = store.store

        done = [s for s in bs.slots() if bs.block(s) is not None]
        assert done
        slot = done[0]
        block = bs.block(slot)
        shreds = bs.shreds(slot)
        data = [s for s in map(SH.parse, shreds) if s is not None and s.is_data]
        parity = [
            s for s in map(SH.parse, shreds) if s is not None and not s.is_data
        ]
        assert data and parity

        # block is a whole number of poh entries forming a hash chain
        assert len(block) % ENTRY_SZ == 0 and len(block) > 0
        entries = [
            block[i : i + ENTRY_SZ] for i in range(0, len(block), ENTRY_SZ)
        ]
        for prev, nxt in zip(entries, entries[1:]):
            assert nxt[0:32] == prev[72:104]  # prev_state chains to state

        # every stored shred carries the leader's signature over its
        # set's merkle root (checked again by the receiver below)
        sig0 = shreds[0][0:0x40]
        assert sig0 != b"\0" * 0x40

        # ---- receiver path: drop a data shred per set (recover from
        # parity) and feed the rest to a fresh resolver with signature
        # verification on; reconstruction must be bit-exact ----
        drop = {min(s.idx for s in data)}  # first data shred of set 0
        resolver = FecResolver(
            verify_sig=lambda sig, root, s: golden.verify(root, sig, leader_pub)
            == 0
        )
        recovered = {}
        for raw in shreds:
            s = SH.parse(raw)
            if s is not None and s.is_data and s.idx in drop:
                continue
            res = resolver.add_shred(raw)
            if res is not None:
                recovered[res.fec_set_idx] = res
        assert resolver.rejected == 0
        payload = b"".join(
            recovered[i].payload for i in sorted(recovered)
        )
        assert payload == block
        assert any(r.recovered_cnt for r in recovered.values())
    finally:
        topo.close()


def test_blockstore_roundtrip(tmp_path):
    from firedancer_tpu.tiles.store import Blockstore

    bs = Blockstore(str(tmp_path / "bs"))
    bs.append_shred(3, b"abc")
    bs.append_shred(3, b"defg")
    bs.append_shred(5, b"x" * 1228)
    bs.write_block(3, b"payload")
    bs.flush()
    assert bs.shreds(3) == [b"abc", b"defg"]
    assert len(bs.shreds(5)) == 1
    assert bs.block(3) == b"payload"
    assert bs.block(5) is None
    assert bs.slots() == [3, 5]
    bs.close()


@pytest.mark.parametrize("sz", [
    31_840,          # the most one 32:32 set carries
    31_841,          # one byte more: a normal set and a 1-shred tail
    36_192,          # a slot that caught up after a stall: 348 entries
    62_399, 62_400,  # either side of twice the normal payload
    93_041,
])
def test_entry_batch_of_any_size_shreds_and_reassembles(sz):
    """A slot's entry batch whose tail is more than one set carries used
    to index past the 32-shred parity table (the shred tile died on the
    first slot with more than 306 entries — a burst after a host stall,
    once verify stopped spacing it out: PERF.md, PR 26)."""
    from firedancer_tpu.disco import shredder as SD

    batch = np.random.default_rng(sz).integers(0, 256, sz, np.uint8).tobytes()
    sd = SD.Shredder(1)
    sd.start_slot(9)
    sets = sd.shred_batch(batch, SD.EntryBatchMeta(block_complete=True))
    res, payload = FecResolver(), b""
    for fs in sets:
        assert 1 <= len(fs.data_shreds) <= 32 >= len(fs.parity_shreds) >= 1
        out = res.add_shred(fs.parity_shreds[0])  # sizes the set
        for raw in fs.data_shreds:
            out = res.add_shred(raw) or out
        payload += out.payload
    assert payload == batch
    assert all(len(fs.data_shreds) == 32 for fs in sets[:-1])
    last = SH.parse(sets[-1].data_shreds[-1])
    assert last.flags & SH.FLAG_SLOT_COMPLETE
