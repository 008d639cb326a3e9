"""`builder = "validator"`: the full leader topology, as `fdtctl run
--full` builds it, with the corpus's accounts funded in funk."""


def build(cfg, identity, workdir, pubs, conf, siglog_cap):
    import os

    from firedancer_tpu.app import config as C
    from firedancer_tpu.flamenco.accounts import Account, AccountMgr
    from firedancer_tpu.funk.funk import Funk

    from benchmark.lib.corpus import START_LAMPORTS

    if len(pubs) * 2 > cfg.bank_table_slots:
        raise ValueError("the accounts do not fit the bank table")
    funk = Funk()
    mgr = AccountMgr(funk)
    for p in pubs:
        mgr.store(p.tobytes(), Account(START_LAMPORTS))
    topo, _ = C.build_validator_topology(
        cfg, identity, os.path.join(workdir, "blockstore"), funk=funk)
    return topo
