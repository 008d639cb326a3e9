"""Standalone pack insert/schedule benchmark at rate.

Pack is unexercised above the landed-TPS rate (round-4 review, #5): measure
insert throughput and schedule/commit latency at 100K-1M inserts/s with
payer contention, device prefilter on vs off, BEFORE the full pipeline
gets there.  Reference bar: fd_pack survives ~1M inserts/s
(src/ballet/pack/fd_pack.c:742-953 insert path).

Run: python scripts/bench_pack.py [n_txns_log2=17] [n_payers=1024]
Prints one summary line per phase + a JSON tail.
"""

from __future__ import annotations

import json
import sys
import time

sys.path.insert(0, ".")

import numpy as np

from firedancer_tpu.ballet import pack as P
from firedancer_tpu.tiles.bench import make_transfer_pool


def main() -> None:
    nlog = int(sys.argv[1]) if len(sys.argv) > 1 else 17
    n_payers = int(sys.argv[2]) if len(sys.argv) > 2 else 1024
    n = 1 << nlog
    t0 = time.perf_counter()
    rows, _payers = make_transfer_pool(n, n_signers=n_payers, seed=5)
    print(f"pool: {n} txns, {n_payers} payers, "
          f"{time.perf_counter()-t0:.1f}s to build", flush=True)
    szs = np.full(n, rows.shape[1], np.uint32)

    out = {}

    # ---- batch insert throughput (the verify->dedup->pack path's cost)
    eng = P.Pack(1 << nlog, max_banks=4)
    batch = 4096
    t0 = time.perf_counter()
    inserted = 0
    for off in range(0, n, batch):
        scan = P.txn_scan(
            rows[off : off + batch], szs[off : off + batch],
            nbits=eng.nbits, with_bitsets=True,
        )
        inserted += eng.insert_batch(
            rows[off : off + batch], szs[off : off + batch], scan=scan
        )
    dt = time.perf_counter() - t0
    out["insert_per_s"] = round(inserted / dt, 1)
    print(f"insert: {inserted}/{n} ok, {inserted/dt:,.0f}/s", flush=True)

    # ---- schedule/commit loop: drain everything through 4 banks
    scheduled = 0
    lat = []
    t0 = time.perf_counter()
    while True:
        progress = False
        for bank in range(4):
            s0 = time.perf_counter()
            mb = eng.schedule_microblock(
                bank, cu_limit=1_500_000, txn_limit=256, byte_limit=60_000
            )
            lat.append(time.perf_counter() - s0)
            if mb is None:
                continue
            progress = True
            scheduled += len(mb.txn_idx)
            eng.microblock_complete(bank, mb.handle)
        if not progress:
            if eng.pending_cnt == 0:
                break
            # block budget exhausted with txns remaining: roll the block
            eng.end_block()
    dt = time.perf_counter() - t0
    lat_us = np.array(lat) * 1e6
    out["schedule_per_s"] = round(scheduled / dt, 1) if dt else 0.0
    out["schedule_p50_us"] = round(float(np.percentile(lat_us, 50)), 1)
    out["schedule_p99_us"] = round(float(np.percentile(lat_us, 99)), 1)
    print(
        f"schedule: {scheduled} txns in {dt:.2f}s "
        f"({scheduled/max(dt,1e-9):,.0f}/s), "
        f"latency p50={out['schedule_p50_us']}us "
        f"p99={out['schedule_p99_us']}us",
        flush=True,
    )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
