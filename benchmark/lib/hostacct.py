"""What the host did to a run: the `host` line's readings.

Every read is guarded: a file that is absent or unreadable gives None
(printed `null`), and never fails the run.  Nothing here is a metric;
the line says who was kept off a core, so that a far-off lag can be laid
at the generator's, the sampler's or the program's door.

The readings are taken at the window's two edges by a thread of their
OWN (`Watch`): every counter read here is cumulative and kept by the
kernel, so whoever reads it reads the same, and neither the thread that
samples the terminal counter nor one that sends pays for a read (an open
on the chip's host costs about a ms with every core spinning: 250 of
them on the sampling thread put a quarter-second hole into the sampling,
and a dozen still 10-12 ms — PR 33's first calls).  `Host()` finds out
ONCE, during set-up, which readings this host gives at all (the chip's
host is a sandbox whose /proc has no schedstat, no context switches, no
pressure, and a /proc/stat of zeros), and an edge reads only those.

task      one thread: ns it waited runnable for a CPU and ns it ran
          (/proc/<pid>/task/<tid>/schedstat), voluntary / involuntary
          context switches (.../status)
cpu_ms    one process, all its threads: user + system time
          (/proc/<pid>/stat).  A tile spins, so the window less its CPU
          time is the time it was kept off a core
machine   /proc/stat's cpu line (steal and not-idle jiffies), the
          cgroup's (v2) cpu.stat (nr_throttled, throttled_usec), and
          `some avg10` of /proc/pressure/cpu
"""

from __future__ import annotations

import os
import threading
import time

#: where the readings come from (module words, so that a test can point
#: them at a directory that holds nothing)
PROC = "/proc"
CGROUP = "/sys/fs/cgroup"


def _text(path: str) -> str | None:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return None


def _hz() -> int:
    try:
        return os.sysconf("SC_CLK_TCK")
    except (ValueError, OSError):
        return 100


def task(pid, tid=None) -> dict | None:
    """One thread's schedstat and context switches (tid None: the
    process's first thread); None where the host gives neither."""
    base = f"{PROC}/{pid}" + (f"/task/{tid}" if tid is not None else "")
    out = dict(run_ns=None, wait_ns=None, vol=None, invol=None)
    try:
        run, wait = _text(f"{base}/schedstat").split()[:2]
        out.update(run_ns=int(run), wait_ns=int(wait))
    except (AttributeError, ValueError):
        pass
    for line in (_text(f"{base}/status") or "").splitlines():
        key, _, val = line.partition(":")
        if key in ("voluntary_ctxt_switches", "nonvoluntary_ctxt_switches"):
            try:
                out["vol" if key[0] == "v" else "invol"] = int(val)
            except ValueError:
                pass
    return out if any(v is not None for v in out.values()) else None


def cpu_ms(pid) -> float | None:
    """User + system time of a whole process, ms."""
    try:
        # "pid (comm) state ppid ... utime stime": comm may hold spaces
        f = _text(f"{PROC}/{pid}/stat").rpartition(")")[2].split()
        return (int(f[11]) + int(f[12])) * 1e3 / _hz()
    except (AttributeError, IndexError, ValueError):
        return None


def _cpu_stat_path() -> str | None:
    """The cpu.stat of this process's cgroup (v2: the `0::<path>` line of
    /proc/self/cgroup), else the root's; None where there is neither."""
    tried = [os.path.join(CGROUP, line[3:].strip().lstrip("/"))
             for line in (_text(f"{PROC}/self/cgroup") or "").splitlines()
             if line.startswith("0::")]
    for d in tried + [CGROUP]:
        if os.path.exists(os.path.join(d, "cpu.stat")):
            return os.path.join(d, "cpu.stat")
    return None


def machine(cpu_stat: str | None) -> dict:
    out = dict(steal_ticks=None, busy_ticks=None, nr_throttled=None,
               throttled_usec=None, psi_some_avg10=None)
    try:
        # cpu user nice system idle iowait irq softirq steal guest guest_nice
        f = [int(x) for x in _text(f"{PROC}/stat").splitlines()[0].split()[1:]]
        if sum(f):  # all zeros: the host keeps no such account
            out.update(steal_ticks=f[7], busy_ticks=sum(f[:8]) - f[3] - f[4])
    except (AttributeError, IndexError, ValueError):
        pass
    for line in ((_text(cpu_stat) if cpu_stat else None) or "").splitlines():
        key, _, val = line.partition(" ")
        try:
            if key in ("nr_throttled", "throttled_usec"):
                out[key] = int(val)
        except ValueError:
            pass
    for line in (_text(f"{PROC}/pressure/cpu") or "").splitlines():
        if line.startswith("some"):
            try:
                out["psi_some_avg10"] = float(
                    line.split("avg10=")[1].split()[0])
            except (IndexError, ValueError):
                pass
    return out


class Host:
    """Found out once, in set-up: which readings this host gives.  `read`
    then takes those and no others (what it does not take reads None)."""

    def __init__(self):
        me = os.getpid()
        self.cpu_stat = _cpu_stat_path()
        self.gives = dict(
            machine=any(v is not None
                        for v in machine(self.cpu_stat).values()),
            task=task(me) is not None, cpu_ms=cpu_ms(me) is not None)

    def read(self, procs: dict, tid: int) -> dict:
        """One edge's readings: the machine, the harness's loop thread
        (`tid`: the one that samples the counter or, in the closed loop,
        sends), and of each process of `procs` (name -> pid: the tiles'
        children, the open loop's sender) its first thread and the CPU
        time of the whole process."""
        t0 = time.monotonic_ns()
        g = self.gives
        none = dict.fromkeys(procs)
        out = dict(
            t=t0,
            machine=machine(self.cpu_stat) if g["machine"] else None,
            harness=task(os.getpid(), tid) if g["task"] else None,
            procs={n: task(p) for n, p in procs.items()}
            if g["task"] else none,
            procs_cpu_ms={n: cpu_ms(p) for n, p in procs.items()}
            if g["cpu_ms"] else none)
        out["read_ns"] = time.monotonic_ns() - t0
        return out


class Watch(threading.Thread):
    """Takes `Host.read` as each of `marks_ns` (the window's edges,
    CLOCK_MONOTONIC ns) passes, on a thread of its own that sleeps in
    between: the thread that started it (`tid`) samples or sends and
    reads nothing."""

    def __init__(self, host: Host, marks_ns, procs: dict):
        super().__init__(name="fdt-benchmark-host", daemon=True)
        self.host, self.marks, self.procs = host, list(marks_ns), procs
        self.tid = threading.get_native_id()
        self.reads: list = []
        self._halt = threading.Event()
        self.start()

    def run(self) -> None:
        for mark in self.marks:
            while (left := mark - time.monotonic_ns()) > 0:
                if self._halt.wait(left / 1e9):
                    return
            self.reads.append(self.host.read(self.procs, self.tid))

    def done(self) -> list:
        """The readings taken (every mark's, once the last has passed);
        stops a watch whose marks a failed run never reached."""
        self._halt.set()
        self.join()
        return self.reads


def delta(after: dict | None, before: dict | None) -> dict:
    """after - before, key by key; None where either side has no reading
    (a gauge such as `psi_some_avg10` is the caller's to take from one
    side); {} where a whole side is missing."""
    if after is None or before is None:
        return {}
    return {k: (after[k] - before[k]
                if after.get(k) is not None and before.get(k) is not None
                else None) for k in after}


def cpus() -> tuple:
    """(CPUs this process may run on, CPUs online)."""
    try:
        allowed = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        allowed = None
    return allowed, os.cpu_count()


def window(reads: list, tiles, loop_cpu_ns=None) -> dict:
    """The `host` line's fields that come from the two edges' readings
    (a `Watch`'s; fewer than two: the run never reached its end, and only
    what needs no reading is said).  `tiles`: the names in the readings'
    `procs` that are spinning tiles; every other name there (the open
    loop's `sender`) gets fields of its own.  `loop_cpu_ns`: what the
    harness's loop thread read on its own CPU clock between the edges."""
    allowed, online = cpus()
    out = dict(cpus_allowed=allowed, cpus_online=online,
               tile_procs=len(tiles))
    if len(reads) < 2:
        return out
    b, a = reads[0], reads[-1]
    span_ms = (a["t"] - b["t"]) / 1e6
    mach = delta(a["machine"], b["machine"])

    def scaled(key, by):
        return None if mach.get(key) is None else round(mach[key] * by, 2)

    out.update(
        steal_ms=scaled("steal_ticks", 1e3 / _hz()),
        busy_cpus=scaled("busy_ticks", 1e3 / _hz() / span_ms),
        throttled_n=mach.get("nr_throttled"),
        throttled_ms=scaled("throttled_usec", 1e-3),
        psi_some_avg10=(a["machine"] or {}).get("psi_some_avg10"))
    procs = {n: delta(a["procs"].get(n), b["procs"].get(n))
             for n in a["procs"]}
    cpu = delta(a["procs_cpu_ms"], b["procs_cpu_ms"])
    for who, d, cpu_ms_ in [("harness", delta(a["harness"], b["harness"]),
                             ms(loop_cpu_ns))] + [
            (n, d, cpu.get(n)) for n, d in procs.items() if n not in tiles]:
        out.update({f"{who}_wait_ms": ms(d.get("wait_ns")),
                    f"{who}_run_ms": ms(d.get("run_ns")),
                    f"{who}_vol": d.get("vol"), f"{who}_invol": d.get("invol"),
                    f"{who}_cpu_ms": cpu_ms_})
    out["tiles_wait_ms"] = {n: ms(procs[n].get("wait_ns")) for n in tiles}
    out["tiles_invol"] = {n: procs[n].get("invol") for n in tiles}
    # a tile spins: the window less its CPU time is its time off a core
    # (all threads of the process count, so the chip's process, with its
    # worker and runtime threads, can read below 0)
    out["tiles_off_cpu_ms"] = {
        n: None if cpu.get(n) is None else round(span_ms - cpu[n], 1)
        for n in tiles}
    out["edge_read_ms"] = [ms(b["read_ns"]), ms(a["read_ns"])]
    return out


def ms(ns) -> float | None:
    return None if ns is None else round(ns / 1e6, 3)


def say(v) -> str:
    """A reading as the line prints it: `null` where there was none."""
    if isinstance(v, dict):
        return "{" + ",".join(f"{k}:{say(x)}" for k, x in v.items()) + "}"
    if isinstance(v, (list, tuple)):
        return "[" + ",".join(say(x) for x in v) + "]"
    return "null" if v is None else str(v)
