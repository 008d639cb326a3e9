"""Pack vote lane: votes-first scheduling, vote CU budgets, and a
randomized property test of the dense engine against a straightforward
oracle (round-1 review, item 5)."""

import numpy as np
import pytest

from firedancer_tpu.ballet import pack as P
from firedancer_tpu.ballet import txn as T


def _mk_txn(rng, *, vote: bool, writable_key: bytes | None = None,
            signer: bytes | None = None,
            program: bytes | None = None) -> bytes:
    """A minimal txn; vote txns have one instr on the Vote program.
    Non-vote default is an unknown (BPF-costed) program; pass program=
    bytes(32) for a cheap builtin-costed txn."""
    signer = signer or rng.integers(0, 256, 32, np.uint8).tobytes()
    acct = writable_key or rng.integers(0, 256, 32, np.uint8).tobytes()
    if program is None:
        program = P.VOTE_PROGRAM_ID if vote else bytes(31) + b"\x01"
    blockhash = rng.integers(0, 256, 32, np.uint8).tobytes()
    data = rng.integers(0, 256, 16, np.uint8).tobytes()
    body = T.build(
        [rng.integers(0, 256, 64, np.uint8).tobytes()],
        [signer, acct, program],
        blockhash,
        [(2, [0, 1], data)],
        readonly_unsigned_cnt=1,
    )
    return body


def test_is_simple_vote():
    rng = np.random.default_rng(0)
    v = _mk_txn(rng, vote=True)
    n = _mk_txn(rng, vote=False)
    assert P.is_simple_vote(v, T.parse(v))
    assert not P.is_simple_vote(n, T.parse(n))


def test_votes_scheduled_first_and_budgeted():
    rng = np.random.default_rng(1)
    pk = P.Pack(256)
    for _ in range(20):
        assert pk.insert(_mk_txn(rng, vote=True)) == "ok"
    for _ in range(20):
        # builtin-costed non-votes (system program): cheap enough to
        # share a microblock whose budget is sized in vote costs
        assert pk.insert(_mk_txn(rng, vote=False, program=bytes(32))) == "ok"
    vote_cost = int(pk.cost[pk.is_vote & (pk.state == 1)][0])

    # a budget that fits exactly 3 votes at 25% of the CU limit
    cu_limit = vote_cost * 3 * 4
    mb = pk.schedule_microblock(0, cu_limit=cu_limit, txn_limit=31)
    assert mb is not None
    picked_votes = int(pk.is_vote[mb.txn_idx].sum())
    assert picked_votes == 3  # vote_fraction * cu_limit / vote_cost
    assert picked_votes < len(mb.txn_idx)  # non-votes filled the rest
    # votes come first in the microblock
    assert pk.is_vote[mb.txn_idx[:picked_votes]].all()
    assert pk.cumulative_vote_cost == picked_votes * vote_cost


def test_vote_block_cap_enforced():
    rng = np.random.default_rng(2)
    pk = P.Pack(64)
    for _ in range(8):
        assert pk.insert(_mk_txn(rng, vote=True)) == "ok"
    vote_cost = int(pk.cost[pk.state == 1][0])
    # shrink the per-block vote cap to 2 votes' worth
    pk.vote_cost_limit = 2 * vote_cost
    mb = pk.schedule_microblock(0, cu_limit=10_000_000, txn_limit=31,
                                vote_fraction=1.0)
    assert mb is not None and len(mb.txn_idx) == 2
    pk.microblock_complete(0, mb.handle)
    # cap reached: no more votes this block
    assert pk.schedule_microblock(
        0, cu_limit=10_000_000, txn_limit=31, vote_fraction=1.0
    ) is None
    # next block resets the vote budget
    pk.end_block()
    mb2 = pk.schedule_microblock(0, cu_limit=10_000_000, txn_limit=31,
                                 vote_fraction=1.0)
    assert mb2 is not None and len(mb2.txn_idx) == 2


def _oracle_schedule(txns, in_use, cu_limit, vote_budget, txn_limit,
                     vote_fraction):
    """Straightforward model: priority order, votes first (with CU and
    txn-slot vote budgets), conflict via exact account sets, greedy skip."""
    chosen = []
    used = set(in_use)
    cu = 0
    vcu = 0
    any_nonvote = any(not t["vote"] and t["pending"] for t in txns)
    vote_slots = (
        max(1, int(txn_limit * vote_fraction)) if any_nonvote else txn_limit
    )
    n_votes = 0
    for lane in (True, False):
        cands = [t for t in txns if t["vote"] == lane and t["pending"]]
        cands.sort(key=lambda t: (-t["prio"], t["order"]))
        for t in cands:
            if len(chosen) >= txn_limit:
                break
            if lane and n_votes >= vote_slots:
                break
            if cu + t["cost"] > cu_limit:
                continue
            if lane and vcu + t["cost"] > vote_budget:
                continue
            if used & t["accts"]:
                continue
            chosen.append(t["id"])
            used |= t["accts"]
            cu += t["cost"]
            if lane:
                vcu += t["cost"]
                n_votes += 1
    return chosen


def test_randomized_vs_oracle():
    """With collision-free account hashing (few accounts, big bitset), the
    dense engine must match the oracle exactly."""
    rng = np.random.default_rng(3)
    nbits = 4096

    seen = {
        P._hash_acct(P.VOTE_PROGRAM_ID) % nbits,
        P._hash_acct(bytes(31) + b"\x01") % nbits,
    }

    def fresh_keys(n):
        """Distinct keys whose hashed bits are collision-free against
        everything issued so far, so bitset conflicts == exact conflicts."""
        out = []
        while len(out) < n:
            k = rng.integers(0, 256, 32, np.uint8).tobytes()
            h = P._hash_acct(k) % nbits
            if h not in seen:
                seen.add(h)
                out.append(k)
        return out

    keys = fresh_keys(12)

    for trial in range(8):
        pk = P.Pack(128, nbits=nbits)
        model = []
        n = int(rng.integers(6, 24))
        signers = fresh_keys(n)
        for i in range(n):
            vote = bool(rng.integers(0, 2))
            wk = keys[rng.integers(0, len(keys))]
            body = _mk_txn(rng, vote=vote, writable_key=wk, signer=signers[i])
            assert pk.insert(body) == "ok"
            desc = T.parse(body)
            accts = {
                bytes(desc.acct_addr(body, j)) for j in desc.writable_idxs()
            }
            slot = i  # inserts fill slots in order in an empty pool
            model.append(
                {
                    "id": slot,
                    "vote": vote,
                    "cost": int(pk.cost[slot]),
                    "prio": float(pk.rewards[slot]) / max(int(pk.cost[slot]), 1),
                    "accts": accts,
                    "order": i,
                    "pending": True,
                }
            )
        cu_limit = int(rng.integers(1, 8)) * int(pk.cost[0])
        vf = float(rng.choice([0.0, 0.25, 1.0]))
        mb = pk.schedule_microblock(
            0, cu_limit=cu_limit, txn_limit=8, vote_fraction=vf
        )
        want = _oracle_schedule(
            model, set(), cu_limit, int(cu_limit * vf), 8, vf
        )
        got = [] if mb is None else [int(s) for s in mb.txn_idx]
        assert got == want, f"trial {trial}: {got} != {want}"
