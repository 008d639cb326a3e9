"""Leader-side pipeline: dedup → pack → banks → poh over real rings.

Covers the reference's pack/bank/poh tile interplay (microblock
scheduling, bank-busy completion handshake, PoH mixin of executed
microblocks) in the multi-tile-in-one-process harness."""

import time

import numpy as np

from firedancer_tpu.disco import Topology
from firedancer_tpu.tiles import wire
from firedancer_tpu.tiles.bank import BankTile
from firedancer_tpu.tiles.dedup import DedupTile
from firedancer_tpu.tiles.pack import PackTile, mb_decode, mb_encode
from firedancer_tpu.tiles.poh import PohTile
from firedancer_tpu.tiles.sink import SinkTile
from firedancer_tpu.tiles.synth import SynthTile, make_txn_pool
import pytest

pytestmark = pytest.mark.slow

MB_MTU = 40_000


def test_microblock_wire_roundtrip():
    rows, szs, _ = make_txn_pool(5, seed=31)
    buf = mb_encode(7, 3, rows, szs)
    handle, bank, txns = mb_decode(buf)
    assert handle == 7 and bank == 3 and len(txns) == 5
    for i, t in enumerate(txns):
        assert (t == rows[i, : szs[i]]).all()


def test_leader_pipeline_end_to_end():
    n_banks = 2
    pool_n = 48
    rows, szs, _ = make_txn_pool(pool_n, seed=29)
    synth = SynthTile(rows, szs, total=pool_n)
    dedup = DedupTile(depth=1 << 12)
    # device_select ON: the conflict prefilter (ops/pack_select) runs in
    # the live topology, not just the multichip dryrun
    pack = PackTile(n_banks, microblock_ns=1_000, use_device_select=True)
    banks = [BankTile(i) for i in range(n_banks)]
    poh = PohTile(tick_batch=16)
    sink = SinkTile(record=True)

    topo = Topology()
    topo.link("synth_dedup", depth=256, mtu=wire.LINK_MTU)
    topo.link("dedup_pack", depth=256, mtu=wire.LINK_MTU)
    for i in range(n_banks):
        topo.link(f"pack_bank{i}", depth=64, mtu=MB_MTU)
        topo.link(f"bank{i}_pack", depth=64)  # completions: metadata only
        topo.link(f"bank{i}_poh", depth=64, mtu=MB_MTU)
    topo.link("poh_entries", depth=1024, mtu=256)

    topo.tile(synth, outs=["synth_dedup"])
    topo.tile(dedup, ins=[("synth_dedup", True)], outs=["dedup_pack"])
    topo.tile(
        pack,
        ins=[("dedup_pack", True)]
        + [(f"bank{i}_pack", True) for i in range(n_banks)],
        outs=[f"pack_bank{i}" for i in range(n_banks)],
    )
    for i in range(n_banks):
        topo.tile(
            banks[i],
            ins=[(f"pack_bank{i}", True)],
            outs=[f"bank{i}_pack", f"bank{i}_poh"],
        )
    topo.tile(
        poh,
        ins=[(f"bank{i}_poh", True) for i in range(n_banks)],
        outs=["poh_entries"],
    )
    # poh floods tick entries; sink reads unreliably so poh never stalls
    topo.tile(sink, ins=[("poh_entries", False)])
    topo.build()
    topo.start(batch_max=64)
    try:
        deadline = time.monotonic() + 60.0
        want_txns = pool_n
        while time.monotonic() < deadline:
            topo.poll_failure()
            done = sum(
                topo.metrics(f"bank{i}").counter("executed_txns")
                for i in range(n_banks)
            )
            if done >= want_txns:
                break
            time.sleep(0.02)
        topo.halt()

        mp = topo.metrics("pack")
        assert mp.counter("inserted_txns") == pool_n
        total_exec = sum(
            topo.metrics(f"bank{i}").counter("executed_txns")
            for i in range(n_banks)
        )
        assert total_exec == pool_n
        n_mbs = mp.counter("microblocks")
        assert n_mbs >= 1
        assert mp.counter("completions") == n_mbs
        # pack engine fully drained and unlocked
        assert pack.engine.inflight_cnt == 0
        assert (pack.engine.bit_ref_rw == 0).all()
        # poh mixed in every executed microblock
        mpoh = topo.metrics("poh")
        assert mpoh.counter("mixins") == n_mbs
        assert mpoh.counter("hashcnt") >= mpoh.counter("mixins")
        # every microblock produced a mixin entry in the sink stream
        with sink.lock:
            n_entries = sum(len(s) for s in sink.sigs)
        assert n_entries > 0
    finally:
        topo.close()


def test_leader_pipeline_executes_balances():
    """Funk-backed banks: post-block balances reflect every transfer
    (round-1 review, item 4: 'leader pipeline test asserts post-block
    balances')."""
    from firedancer_tpu.ballet import txn as T
    from firedancer_tpu.flamenco.accounts import (
        Account, AccountMgr, SYSTEM_PROGRAM_ID,
    )
    from firedancer_tpu.flamenco.runtime import FEE_PER_SIGNATURE
    from firedancer_tpu.funk.funk import Funk
    from firedancer_tpu.ops.ed25519 import golden

    rng = np.random.default_rng(41)
    n_txns, n_banks = 12, 2
    funk = Funk()
    mgr = AccountMgr(funk)
    bh = rng.integers(0, 256, 32, np.uint8).tobytes()

    payers, dsts, amounts = [], [], []
    rows = np.zeros((n_txns, wire.LINK_MTU), np.uint8)
    szs = np.zeros(n_txns, np.uint16)
    for i in range(n_txns):
        sk = rng.integers(0, 256, 32, np.uint8).tobytes()
        pk = golden.public_from_secret(sk)
        dst = rng.integers(0, 256, 32, np.uint8).tobytes()
        amt = int(rng.integers(1_000, 50_000))
        mgr.store(pk, Account(1_000_000))
        data = (2).to_bytes(4, "little") + amt.to_bytes(8, "little")
        body = T.build(
            [bytes(64)], [pk, dst, SYSTEM_PROGRAM_ID], bh,
            [(2, [0, 1], data)], readonly_unsigned_cnt=1,
        )
        desc = T.parse(body)
        sig = golden.sign(sk, desc.message(body))
        payload = body[:1] + sig + body[1 + 64 :]
        full = wire.append_trailer(payload, desc)
        rows[i, : len(full)] = np.frombuffer(full, np.uint8)
        szs[i] = len(full)
        payers.append(pk)
        dsts.append(dst)
        amounts.append(amt)

    synth = SynthTile(rows, szs, total=n_txns)
    dedup = DedupTile(depth=1 << 10)
    pack = PackTile(n_banks, microblock_ns=1_000)
    banks = [BankTile(i, funk=funk) for i in range(n_banks)]
    poh = PohTile(tick_batch=16)
    sink = SinkTile()

    topo = Topology()
    topo.link("synth_dedup", depth=256, mtu=wire.LINK_MTU)
    topo.link("dedup_pack", depth=256, mtu=wire.LINK_MTU)
    for i in range(n_banks):
        topo.link(f"pack_bank{i}", depth=64, mtu=MB_MTU)
        topo.link(f"bank{i}_pack", depth=64)
        topo.link(f"bank{i}_poh", depth=64, mtu=MB_MTU)
    topo.link("poh_entries", depth=1024, mtu=256)
    topo.tile(synth, outs=["synth_dedup"])
    topo.tile(dedup, ins=[("synth_dedup", True)], outs=["dedup_pack"])
    topo.tile(
        pack,
        ins=[("dedup_pack", True)]
        + [(f"bank{i}_pack", True) for i in range(n_banks)],
        outs=[f"pack_bank{i}" for i in range(n_banks)],
    )
    for i in range(n_banks):
        topo.tile(
            banks[i],
            ins=[(f"pack_bank{i}", True)],
            outs=[f"bank{i}_pack", f"bank{i}_poh"],
        )
    topo.tile(
        poh,
        ins=[(f"bank{i}_poh", True) for i in range(n_banks)],
        outs=["poh_entries"],
    )
    topo.tile(sink, ins=[("poh_entries", False)])
    topo.build()
    topo.start(batch_max=64)
    try:
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            done = sum(
                topo.metrics(f"bank{i}").counter("executed_txns")
                for i in range(n_banks)
            )
            if done >= n_txns:
                break
            time.sleep(0.02)
        topo.halt()

        failed = sum(
            topo.metrics(f"bank{i}").counter("failed_txns")
            for i in range(n_banks)
        )
        assert failed == 0
        # post-block balances: every transfer landed exactly once
        for pk, dst, amt in zip(payers, dsts, amounts):
            assert mgr.lamports(pk) == 1_000_000 - FEE_PER_SIGNATURE - amt
            assert mgr.lamports(dst) == amt
        fees = sum(
            topo.metrics(f"bank{i}").counter("fees_lamports")
            for i in range(n_banks)
        )
        assert fees == n_txns * FEE_PER_SIGNATURE
    finally:
        topo.close()
