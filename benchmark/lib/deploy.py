"""The one place the benchmark touches the program: boot a deployment
from its configuration files through the entry points a user calls
(config file -> app.config.parse -> build_*_topology -> build -> start)
and read its shared counters.

This process — the topology parent — never initialises a JAX backend:
under `[topo] runtime = "process"` the verify tile's child owns the chip.
"""

from __future__ import annotations

import importlib.util
import json
import os
import select
import socket
import tomllib


def load_config(root: str, name: str) -> dict:
    with open(os.path.join(root, "configs", f"{name}.json")) as f:
        conf = json.load(f)
    with open(os.path.join(root, "configs", conf["toml"])) as f:
        conf["toml_text"] = f.read()
    conf["root"] = root
    return conf


def load_builder(root: str, name: str):
    """`builders/<name>.py`, found by the name a configuration gives:
    build(cfg, identity, workdir, pubs, conf, siglog_cap) -> topology."""
    path = os.path.join(root, "builders", f"{name}.py")
    if not os.path.exists(path):
        raise ValueError(f"unknown builder {name!r}: no {path}")
    spec = importlib.util.spec_from_file_location(f"_builder_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.build


def merge(into: dict, over: dict) -> dict:
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(into.get(k), dict):
            merge(into[k], v)
        else:
            into[k] = v
    return into


def dump_toml(doc: dict, prefix: str = "") -> str:
    """Tables of scalars, as the program's config files are."""
    def key(k: str) -> str:  # "127.0.0.1:9000" is no bare key
        return k if k.replace("_", "").replace("-", "").isalnum() else (
            json.dumps(k))

    scalars = [(k, v) for k, v in doc.items() if not isinstance(v, dict)]
    lines = [f"[{prefix}]"] if prefix and scalars else []
    lines += [f"{key(k)} = {json.dumps(v)}" for k, v in scalars]
    for k, v in doc.items():
        if isinstance(v, dict):
            lines.append(dump_toml(
                v, f"{prefix}.{key(k)}" if prefix else key(k)))
    return "\n".join(lines) + "\n"


def free_udp_port() -> int:
    """Under the process runtime a tile's child binds the socket, so the
    port must be known to this parent beforehand.  (Probe->bind leaves a
    small window; a stolen port fails the child's bind loudly.)"""
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _udp_rows(port: int) -> list:
    """The rows of /proc/net/udp of the sockets bound to `port`, split."""
    with open("/proc/net/udp") as f:
        next(f)
        rows = [line.split() for line in f]
    return [c for c in rows if int(c[1].rsplit(":", 1)[1], 16) == port]


def udp_kernel_drops(port: int) -> int:
    """Datagrams the kernel dropped at the socket bound to `port` (the
    last column of /proc/net/udp): the one loss no tile can count."""
    return sum(int(c[-1]) for c in _udp_rows(port))


def _rx_queue(port: int) -> int:
    """Bytes queued unread at the UDP socket bound to `port` (the
    rx_queue column of /proc/net/udp: its skbs' truesize)."""
    return sum(int(c[4].split(":")[1], 16) for c in _udp_rows(port))


#: the least a datagram is charged of a receiving socket's buffer: what
#: loopback UDP charges one of a few hundred bytes (its skb's truesize)
MIN_TRUESIZE = 1280


def socket_window() -> int:
    """Bytes of the receiving tile's socket buffer that unread datagrams
    may take, with half the buffer to spare.  waltz/udpsock.py asks for
    2 MiB; the kernel grants min(that, rmem_max), doubled."""
    with open("/proc/sys/net/core/rmem_max") as f:
        granted = 2 * min(1 << 21, int(f.read()))
    return max(granted // 2, 64 * MIN_TRUESIZE)


def udp_truesize(lengths) -> dict:
    """{length: bytes} that one loopback datagram of each length takes of
    the receiving socket's buffer, at least MIN_TRUESIZE: the kernel's
    own charge, read off a socket of this process with one such datagram
    queued.  A host whose /proc/net/udp reports no queue (it reads 0 with
    the datagram there) gets MIN_TRUESIZE for every length."""
    rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        rx.bind(("127.0.0.1", 0))
        rx.settimeout(1.0)
        port = rx.getsockname()[1]
        got = {}
        for n in sorted({int(n) for n in lengths}):
            tx.sendto(bytes(n), ("127.0.0.1", port))
            # readable once queued, and charged before it is queued
            if not select.select([rx], [], [], 1.0)[0]:
                raise OSError(f"a {n}-byte loopback datagram did not "
                              f"arrive within 1 s")
            got[n] = max(_rx_queue(port), MIN_TRUESIZE)
            rx.recv(n)
        return got
    finally:
        rx.close()
        tx.close()


class Deployment:
    """A booted topology and named reads of its shared metrics (valid
    from the parent under either runtime: the regions live in the
    workspace)."""

    def __init__(self, conf: dict, workdir: str, seed: int, pubs=None,
                 overrides: dict | None = None, siglog_cap: int = 0,
                 sender_stake: int = 0):
        """`overrides` is merged over the configuration's TOML (a
        rehearsal's tiny sizes, a test's runtime); with any, the stated
        sizes are not asserted — such a run is never a measurement."""
        import numpy as np

        from firedancer_tpu.app import config as C

        self.conf, self.port = conf, free_udp_port()
        doc = tomllib.loads(conf["toml_text"])
        merge(doc, overrides or {})
        # the workspace name is per process, the ports per run: the only
        # keys the harness itself sets (a cell whose sender is staked
        # gets its address into [stakes] with the cell's weight)
        merge(doc, {"name": f"bm{os.getpid()}",
                    "tiles": {"quic": {"udp_port": self.port}}})
        self.sender_port = free_udp_port()
        if sender_stake:
            merge(doc, {"stakes": {
                f"127.0.0.1:{self.sender_port}": sender_stake}})
        # the config goes through a FILE, as for `fdtctl run --config`
        path = os.path.join(workdir, conf["toml"])
        with open(path, "w") as f:
            f.write(dump_toml(doc))
        with open(path) as f:
            self.cfg = cfg = C.parse(f.read())
        for key, want in ({} if overrides else conf["sizes"]).items():
            got = getattr(cfg, key)
            if got != want:
                raise ValueError(
                    f"{conf['toml']}: {key} = {got!r}, the configuration "
                    f"states {want!r}")
        identity = np.random.default_rng(seed).integers(
            0, 256, 32, np.uint8).tobytes()
        self.topo = load_builder(conf["root"], conf["builder"])(
            cfg, identity, workdir, pubs, conf, siglog_cap)
        self.topo.build()

    def start(self) -> None:
        self.topo.start()

    # ---- counters --------------------------------------------------------

    def get(self, tile: str, name: str) -> int:
        return int(self.topo.metrics(tile).counter(name))

    def total(self, terms) -> int:
        return sum(self.get(t, n) for t, n in terms)

    def reader(self, terms):
        """A fast closure reading sum(terms): the sampler's inner loop."""
        cells = []
        for t, n in terms:
            m = self.topo.metrics(t)
            cells.append((m.words, m._slot[n]))
        return lambda: sum(int(w[s]) for w, s in cells)

    def settled(self) -> int:
        """Txns that reached their end: landed, or dropped under a
        counter that says why (a loss still ends a txn's flight)."""
        c = self.conf
        return (self.total(c["terminal"]) + self.total(c["rejected"])
                + self.total(c["dups"])
                + sum(self.total(t) for t in c["losses"].values()))

    def snapshot(self) -> dict:
        """Every tile's counters and hists (a per-layer reader's input)."""
        return {t: m.read() for t, m in self.topo.metrics_registry().items()}

    def poll_failure(self) -> None:
        self.topo.poll_failure()

    def failed_tiles(self) -> list[str]:
        from firedancer_tpu.tango import rings as R

        return [n for n, cnc in self.topo._cncs.items()
                if cnc.signal_query() == R.CNC_FAIL]

    def tile_pids(self) -> dict:
        """Each tile's child pid (process runtime; a tile that runs as a
        thread of this process has none and is left out)."""
        pids = {n: self.topo.tile_pid(n) for n in self.topo.tiles}
        return {n: p for n, p in pids.items() if p}

    def runtime(self) -> tuple[str, str]:
        return self.topo._runtime, self.topo._loop_kw["stem"]

    def balances(self, pubs):
        """Final lamports out of the banks' shared table (authoritative
        for resident accounts under both runtimes; funk lags a commit,
        and under the process runtime it is a copy in each bank child)."""
        import numpy as np

        from firedancer_tpu.flamenco.runtime import BankTable

        tab = BankTable(self.topo.wksp.view("shared_banktab"),
                        self.cfg.bank_table_slots)
        got = np.zeros(len(pubs), np.uint64)
        for i, p in enumerate(pubs):
            st, lam = tab.get(p.tobytes())
            # an account that left the table reads as a mismatch
            got[i] = lam if st == BankTable.ST_TRIVIAL else 0
        return got

    def sunk_tags(self):
        """The dedup tags (first 8 signature bytes, LE u64) the sink
        recorded, in arrival order."""
        import numpy as np

        mem = self.topo.tile_alloc_view(self.conf["siglog_tile"], "siglog")
        words = mem[: (len(mem) // 8) * 8].view(np.uint64)
        return words[1: 1 + min(int(words[0]), len(words) - 1)].copy()

    def parent_backend_initialized(self) -> bool:
        from firedancer_tpu.utils import hostdev

        return hostdev.backend_initialized()

    def halt(self) -> None:
        self.topo.halt()

    def close(self) -> None:
        self.topo.close()
