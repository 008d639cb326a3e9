"""fdtctl — run / monitor CLI.

Reference model: the fdctl binary (src/app/fdctl/main.c): `run` boots the
topology from a config file, `monitor` attaches to a running one and
prints live rates.  Usage:

    python -m firedancer_tpu.app.fdtctl run --config cfg.toml [--keyfile k]
    python -m firedancer_tpu.app.fdtctl monitor --name fdt
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import time


def cmd_run(args) -> int:
    from firedancer_tpu.app import config as C
    from firedancer_tpu.app.monitor import Monitor

    from firedancer_tpu.utils import log
    from firedancer_tpu.utils.hostdev import enable_compilation_cache

    text = open(args.config).read() if args.config else ""
    cfg = C.parse(text)
    log.init(path=args.log_path, stderr_level="NOTICE")
    # config only, no backend: under the process runtime this parent
    # must leave the chip to the verify tile child
    enable_compilation_cache()
    if args.keyfile:
        identity = open(args.keyfile, "rb").read()[:32]
    else:
        identity = os.urandom(32)
    if args.full:
        topo, handles = C.build_validator_topology(
            cfg, identity, args.blockstore or f"/tmp/fdt_{cfg.name}_store"
        )
        qt = handles["net"]
        topo.build()
        topo.start()
        quic_addr, udp_addr = _wire_addrs(topo, qt, cfg)
        log.notice(
            "workspace %r: quic %s udp %s metrics %s rpc %s",
            cfg.name, quic_addr, udp_addr,
            handles["metric"].addr, handles["rpc"].addr,
        )
    else:
        topo, qt = C.build_ingress_topology(cfg, identity)
        topo.build()
        topo.start()
        quic_addr, udp_addr = _wire_addrs(topo, qt, cfg)
        log.notice(
            "workspace %r: quic %s udp %s", cfg.name, quic_addr, udp_addr
        )

    stop = []
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    mon = Monitor(cfg.name)
    prev = None
    try:
        while not stop:
            topo.poll_failure()
            cur = mon.snapshot()
            print(mon.render(prev, cur, 1.0), flush=True)
            prev = cur
            if args.iterations:
                args.iterations -= 1
                if args.iterations <= 0:
                    break
            time.sleep(1.0)
    finally:
        topo.halt()
        topo.close()
    return 0


def _wire_addrs(topo, tile, cfg):
    """(quic, udp) listen addresses for the boot log.  Under the process
    runtime the sockets are bound in the tile's CHILD — the parent's
    copy of the tile never boots — so the configured ports are all this
    process knows (an ephemeral 0 stays 0: give real ports there)."""
    if topo._resolve_runtime() == "process":
        return ("0.0.0.0", cfg.quic_port), ("0.0.0.0", cfg.udp_port)
    return tile.quic_addr, tile.udp_addr


def cmd_configure(args) -> int:
    from firedancer_tpu.app import configure as CF

    stages = tuple(args.stages.split(",")) if args.stages else CF.STAGES
    results = CF.run(args.mode, stages, keyfile=args.keyfile)
    bad = 0
    for r in results:
        print(f"[{'ok' if r.ok else '!!'}] {r.name:8s} {r.detail}")
        bad += not r.ok
    return 1 if bad else 0


def cmd_monitor(args) -> int:
    from firedancer_tpu.app.monitor import Monitor

    Monitor(args.name).run(
        interval_s=args.interval, iterations=args.iterations
    )
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="fdtctl")
    sub = p.add_subparsers(dest="cmd", required=True)
    pr = sub.add_parser("run", help="boot the ingress topology from config")
    pr.add_argument("--config", default=None)
    pr.add_argument("--keyfile", default=None)
    pr.add_argument("--full", action="store_true",
                    help="full validator topology (net..store+metric+rpc)")
    pr.add_argument("--blockstore", default=None)
    pr.add_argument("--log-path", default=None)
    pr.add_argument("--iterations", type=int, default=0,
                    help="exit after N monitor prints (0 = run forever)")
    pm = sub.add_parser("monitor", help="attach to a running topology")
    pm.add_argument("--name", default="fdt")
    pm.add_argument("--interval", type=float, default=1.0)
    pm.add_argument("--iterations", type=int, default=None)
    pc = sub.add_parser("configure", help="system setup stages (check/init)")
    pc.add_argument("mode", nargs="?", default="check",
                    choices=("check", "init"))
    pc.add_argument("--stages", default=None,
                    help="comma-separated subset (default: all)")
    pc.add_argument("--keyfile", default=None)
    args = p.parse_args(argv)
    return {
        "run": cmd_run, "monitor": cmd_monitor, "configure": cmd_configure,
    }[args.cmd](args)


if __name__ == "__main__":
    raise SystemExit(main())
