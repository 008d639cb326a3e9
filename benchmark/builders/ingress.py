"""`builder = "ingress"`: quic -> verify -> dedup -> sink, as `fdtctl
run` builds it."""


def build(cfg, identity, workdir, pubs, conf, siglog_cap):
    from firedancer_tpu.app import config as C

    topo, _ = C.build_ingress_topology(cfg, identity)
    # the sink's own recording surface (tiles/sink.py `shm_log`): the
    # dedup tag of every sunk txn, in the workspace, so the comparison
    # can tell WHICH txns came through, not only how many
    topo.tiles[conf["siglog_tile"]].tile.shm_log = siglog_cap
    return topo
