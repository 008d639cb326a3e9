#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It finds the cell (`workloads/<cell>.json`), its configuration
(`configs/<config>.json` + `.toml`) and every metric the cell reports
(`metrics/*.json` -> `readers/<reader>.py`) BY NAME: a later PR adds a
deployment, a traffic mix, a metric, or a cell under the metrics that
are there (the cell's file names them) by adding files only.

A run: traffic from --seed -> boot the deployment through the program's
normal entry points -> warm-up (set-up ends at the window's first edge)
-> the measured window -> drain -> the comparison that decides `correct`
-> one JSON object as the LAST line of stdout.  With no TPU it exits
non-zero and prints no result.  `--rehearse` is the CPU rehearsal: tiny
sizes from the files' `rehearse` groups, exit code 3, never a result a
driver could take for a chip run.

This process never initialises a JAX backend: the verify tile's child
holds the chip, and `inject/sitecustomize.py` is the benchmark's hook
inside it (device kind, peak memory, and in a traced run the profiler).
"""

from __future__ import annotations

import time

_T_PROCESS_START_NS = time.monotonic_ns()

import argparse  # noqa: E402
import concurrent.futures  # noqa: E402
import errno  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import socket  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from multiprocessing import shared_memory  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np  # noqa: E402

from benchmark.lib import corpus as C  # noqa: E402
from benchmark.lib import (hostacct, ledger, reference, sampler,  # noqa: E402
                           sender, tracered)

#: exit codes: 0 a chip run that printed its result; 1 no TPU, or a
#: malformed run; 2 a crash; 3 a rehearsal that ran through (not a chip run)
EXIT_NO_RESULT, EXIT_REHEARSED = 1, 3
#: seconds the drain may take before what is still missing counts as failed
DRAIN_LIMIT_S = 60.0
#: seconds of the window that a traced run traces, in its middle
TRACE_SLICE_S = 4.0


class Malformed(Exception):
    """The run cannot give a result (no TPU, corpus ran out, ...)."""


def say(what: str, **kv) -> None:
    print(f"benchmark {what}: "
          + " ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, name: str, rehearse: bool):
    path = os.path.join(root, "workloads", f"{name}.json")
    if not os.path.exists(path):
        have = sorted(os.path.basename(p)[:-5] for p in
                      glob.glob(os.path.join(root, "workloads", "*.json")))
        raise Malformed(f"no workload {name!r}; there are {have}")
    cell = load_json(path)
    if rehearse:
        cell.update(cell.get("rehearse", {}))
    return cell


def load_metrics(root: str, cell_name: str, end_to_end: bool) -> dict:
    """Every metric file of the kind asked for (`"end_to_end": true`, or
    per-layer) that lists this cell, or lists none, or that the cell's
    own file names under `"metrics"`.  A name with no file is Malformed."""
    files = {os.path.basename(p)[:-5]: load_json(p) for p in
             sorted(glob.glob(os.path.join(root, "metrics", "*.json")))}
    path = os.path.join(root, "workloads", f"{cell_name}.json")
    named = load_json(path).get("metrics", []) if os.path.exists(path) else []
    if missing := [n for n in named if n not in files]:
        raise Malformed(f"workload {cell_name!r} names metrics that have "
                        f"no file under metrics/: {missing}")
    return {n: m for n, m in files.items()
            if bool(m.get("end_to_end")) == end_to_end
            and (cell_name in m.get("workloads", [cell_name]) or n in named)}


def load_reader(root: str, name: str):
    path = os.path.join(root, "readers", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Hook:
    """The parent's end of inject/sitecustomize.py: a directory shared
    with the process that holds the chip.  Words go down the `cmd` pipe
    (the other end blocks on it: nothing polls while the window runs),
    answers come back as files."""

    def __init__(self, root: str, workdir: str):
        self.dir = os.path.join(workdir, "hook")
        os.makedirs(self.dir)
        os.mkfifo(os.path.join(self.dir, "cmd"))
        self.pipe = None
        os.environ["FDT_BENCHMARK_HOOK_DIR"] = self.dir
        inject = os.path.join(root, "inject")
        os.environ["PYTHONPATH"] = os.pathsep.join(
            [inject] + [p for p in os.environ.get("PYTHONPATH", "").split(
                os.pathsep) if p and p != inject])

    def connect(self, wait_s: float) -> None:
        """Open the pipe for writing once the other end reads it."""
        deadline = time.monotonic() + wait_s
        while True:
            try:
                self.pipe = os.open(os.path.join(self.dir, "cmd"),
                                    os.O_WRONLY | os.O_NONBLOCK)
                return
            except OSError as e:  # ENXIO: no reader yet
                if e.errno != errno.ENXIO or time.monotonic() >= deadline:
                    raise Malformed(f"the hook does not listen: {e}")
                time.sleep(0.05)

    def tell(self, word: str) -> None:
        os.write(self.pipe, word.encode() + b"\n")

    def close(self) -> None:
        if self.pipe is not None:
            os.close(self.pipe)
            self.pipe = None

    def answers(self, prefix: str, wait_s: float) -> list:
        deadline = time.monotonic() + wait_s
        while True:
            found = sorted(glob.glob(os.path.join(self.dir, f"{prefix}.*.json")))
            if found or time.monotonic() >= deadline:
                return [load_json(p) for p in found]
            time.sleep(0.05)


def run_cell(root: str, name: str, seed: int, seconds: float, trace: bool,
             *, rehearse: bool = False, require_chip: bool = True,
             overrides: dict | None = None,
             cell_overrides: dict | None = None) -> dict:
    """One run of one cell -> the result object.  `require_chip=False`
    and `overrides` (over the configuration's TOML) are for the tests,
    which drive this with the timed path stubbed or broken underneath;
    `cell_overrides` (over the workload file) is sweep.py's.  The one
    command passes none of them: its runs are the files' alone."""
    from benchmark.lib.deploy import (Deployment, load_config,
                                      socket_window, udp_kernel_drops,
                                      udp_truesize)

    cell = {**load_cell(root, name, rehearse), **(cell_overrides or {})}
    conf = load_config(root, cell["config"])
    weights = C.signer_weights(conf)
    if rehearse:
        overrides = {**conf.get("rehearse", {}), **(overrides or {})}
    open_loop = cell["loop"] == "open"
    copies = 1 + 1 / cell["dup_every"] + 1 / cell["bad_every"]
    warmup_s = cell["warmup_s"]
    if open_loop:
        span_s = warmup_s + seconds + cell["margin_s"]
        n_unique = math.ceil(cell["rate_tps"] * span_s)
    else:
        n_unique = math.ceil(
            cell["corpus_tps"] * (warmup_s + seconds))
    n_accounts = conf["accounts"] if not rehearse else conf.get(
        "rehearse_accounts", conf["accounts"])

    keys = C.make_keys(n_accounts, seed)
    pubs = keys[3]
    workdir = tempfile.mkdtemp(prefix="fdt_benchmark_")
    dep = shm = proc = hook = watch = None
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    n_rows = n_unique + n_unique // cell["dup_every"] + (
        n_unique // cell["bad_every"])
    signer = concurrent.futures.ThreadPoolExecutor(1)
    try:
        t0 = time.perf_counter()
        dep = Deployment(conf, workdir, seed, pubs, overrides,
                         siglog_cap=n_rows,
                         sender_stake=cell.get("sender_stake", 0))
        # the hook lives in the tile child that holds the chip: under
        # the thread runtime (tests) there is no such child
        if dep.cfg.runtime == "process":
            hook = Hook(root, workdir)
        if not open_loop:  # the open loop's sender process binds it
            sock.bind(("127.0.0.1", dep.sender_port))
        # the corpus is signed (a pool of processes) BESIDE the boot, which
        # is one process tracing the verify program for most of a minute
        making = signer.submit(C.make_corpus, n_unique, n_accounts,
                               cell["dup_every"], cell["bad_every"], seed,
                               keys=keys, weights=weights)
        dep.start()
        say("boot", runtime=dep.runtime(), seconds=round(
            time.perf_counter() - t0, 2))
        corp = making.result()
        buf, off, kind = corp["buf"], corp["off"], corp["kind"]
        assert len(kind) == n_rows
        row_bytes = np.diff(off)
        say("corpus", rows=n_rows, unique=n_unique,
            dup=int((kind == C.KIND_DUP).sum()),
            bad=int((kind == C.KIND_BAD).sum()), accounts=n_accounts,
            txn_bytes_mean=round(float(row_bytes.mean()), 1),
            txn_bytes_max=int(row_bytes.max()),
            lanes=int(corp["nsig"][corp["src"]].sum()),
            sign_s=round(corp["sign_s"], 2), boot_and_corpus_s=round(
                time.perf_counter() - t0, 2))
        device = dict(platform="none", kind="none", count=0)
        if hook:
            found = hook.answers("device", 10.0)
            if len(found) != 1:
                raise Malformed(
                    f"{len(found)} processes of the topology hold a JAX "
                    f"backend, expected the verify tile's child alone")
            device = {k: found[0][k] for k in ("platform", "kind", "count")}
            hook.connect(10.0)
        say("device", **device)
        if require_chip and (device["platform"] != "tpu"
                             or device["count"] < cell["chips"]):
            raise Malformed(f"no TPU with {cell['chips']} chip(s): JAX "
                            f"found {device}")

        read_terminal = dep.reader(conf["terminal"])
        dev_tile = conf["device_tile"]
        programs0 = dep.get(dev_tile, "device_programs")
        base = read_terminal()
        addr = ("127.0.0.1", dep.port)
        edge: dict = {}  # what was read at each window edge
        state = dict(next_poll=0, trace="off", next_sec=0)
        # (t, terminal count, txns between sender and their end) about
        # once a second: how steady the window was, and where it queued
        by_second: list = []

        # the `host` line's readings (lib/hostacct.py) are taken at the
        # window's edges by a thread of their own, `watch`: this thread,
        # which samples or sends, only stamps its own CPU clock
        tile_pids = dep.tile_pids()
        host_reads = hostacct.Host()  # set-up: what this host gives

        sent_acct: dict = {}  # the closed loop's own totals

        def at_edge(which: str, now: int, sent: int) -> None:
            edge[which] = dict(t=now, count=read_terminal(), sent=sent,
                               cpu_ns=time.thread_time_ns(),
                               sender=dict(sent_acct))
            if trace:
                edge[which]["snap"] = dep.snapshot()
                edge[which]["kdrops"] = udp_kernel_drops(dep.port)

        def housekeeping(now: int, sent_fn) -> None:
            """Window edges, the traced slice, failure polls."""
            if "T0" not in edge and now >= T0:
                at_edge("T0", now, sent_fn(now))
            if "T1" not in edge and now >= T1:
                at_edge("T1", now, sent_fn(now))
            if trace and hook:
                if state["trace"] == "off" and now >= t_trace:
                    hook.tell("start")
                    state["trace"] = "on"
                elif state["trace"] == "on" and now >= t_trace + slice_ns:
                    hook.tell("stop")
                    state["trace"] = "done"
            if now >= state["next_poll"]:
                dep.poll_failure()
                state["next_poll"] = now + 100_000_000
            if now >= max(state["next_sec"], T0) and now < T1 + 10**9:
                by_second.append((now, read_terminal(),
                                  sent_fn(now) - (dep.settled() - base)))
                state["next_sec"] = now + 1_000_000_000

        slice_ns = int(min(TRACE_SLICE_S, seconds / 2) * 1e9)
        sent_at = sender_said = held = send_busy = None
        if open_loop:
            dgram_rate = cell["rate_tps"] * copies
            interval_ns = round(cell["burst"] / dgram_rate * 1e9)
            due_rel = sender.burst_due_ns(n_rows, cell["burst"], interval_ns)
            shm = shared_memory.SharedMemory(create=True, size=buf.nbytes)
            np.ndarray(buf.shape, np.uint8, buffer=shm.buf)[:] = buf
            ctx = multiprocessing.get_context("spawn")
            rx, tx = ctx.Pipe(duplex=False)
            t_start = time.monotonic_ns() + 2_000_000_000
            proc = ctx.Process(
                target=sender.open_loop_main, name="fdt-benchmark-sender",
                args=(shm.name, off, addr, dep.sender_port, t_start,
                      cell["burst"], interval_ns, tx))
            proc.start()
            tx.close()
            T0 = t_start + int(warmup_s * 1e9)
            T1 = T0 + int(seconds * 1e9)
            watch = hostacct.Watch(host_reads, (T0, T1),
                                   {**tile_pids, "sender": proc.pid})
            t_trace = T0 + (T1 - T0 - slice_ns) // 2
            t_sched_end = t_start + int(due_rel[-1])
            due = t_start + due_rel
            rows_due_by = lambda now: int(  # noqa: E731
                np.searchsorted(due, now, side="right"))
            t_limit = t_sched_end + int(DRAIN_LIMIT_S * 1e9)

            def every(now):
                housekeeping(now, rows_due_by)
                if now > t_sched_end and (
                        dep.settled() - base >= n_rows or now > t_limit):
                    state["drained"] = True

            ts, cs, gap, held = sampler.sample_until(
                read_terminal, lambda now, c: state.get("drained", False),
                every=every, every_ns=10_000_000)
            t_end = time.monotonic_ns()
            sender_said = rx.recv() if rx.poll(30.0) else None
            proc.join(30.0)
            if sender_said is None or proc.exitcode != 0:
                raise Malformed(f"the sender process failed (exit "
                                f"{proc.exitcode})")
            sent_at = sender_said["sent_at"]
            n_sent = n_rows
            say("sampler", change_points=len(ts),
                longest_gap_ms=round(gap / 1e6, 3))
        else:
            rx_terms = [conf["rx"]]
            # what each row's datagram takes of the receiving socket
            size_of = udp_truesize(np.unique(row_bytes))
            charge = np.zeros(max(size_of) + 1, np.int64)
            charge[list(size_of)] = list(size_of.values())
            say("socket", unread_bytes=socket_window(),
                truesize=json.dumps(size_of, separators=(",", ":")))
            t_start = time.monotonic_ns()
            T0 = t_start + int(warmup_s * 1e9)
            T1 = T0 + int(seconds * 1e9)
            t_trace = T0 + (T1 - T0 - slice_ns) // 2
            watch = hostacct.Watch(host_reads, (T0, T1), tile_pids)
            n_sent = sender.closed_loop(
                sock, addr, buf, off,
                in_flight=lambda sent: sent - (dep.settled() - base),
                received=lambda: dep.total(rx_terms),
                window=cell["window_txns"], unread_bytes=socket_window(),
                charge=charge[row_bytes], t_stop_ns=T1,
                tick=lambda now, sent: housekeeping(now, lambda _: sent),
                account=sent_acct)
            if "T1" not in edge:
                raise Malformed(
                    f"the corpus ({n_rows} rows) ran out before the "
                    f"window's end: raise corpus_tps in the workload file")
            # whether the sender kept up: a window spent sending, with
            # the socket seldom found full, measures the sender
            a, b = edge["T0"]["sender"], edge["T1"]["sender"]
            turns = max(b["turns"] - a["turns"], 1)
            send_busy = (b["send_ns"] - a["send_ns"]) / (
                edge["T1"]["t"] - edge["T0"]["t"])
            say("sender", turns=b["turns"] - a["turns"],
                full_share=round((b["full"] - a["full"]) / turns, 4),
                empty_share=round((b["empty"] - a["empty"]) / turns, 4),
                send_busy_share=round(send_busy, 4))
            deadline = time.monotonic() + DRAIN_LIMIT_S
            while (dep.settled() - base < n_sent
                   and time.monotonic() < deadline):
                dep.poll_failure()
                time.sleep(0.01)
            t_end = time.monotonic_ns()
        if trace and hook and state["trace"] == "on":
            hook.tell("stop")
        setup_s = (T0 - _T_PROCESS_START_NS) / 1e9

        # ---- after the window: device facts, then the ledger -------------
        mem_peak, traced = 0, None
        if hook:
            if trace:  # stop_trace writes the trace out: wait for it
                traced = hook.answers("stopped", 90.0)
            hook.tell("stats")
            stats = hook.answers("stats", 10.0)
            mem_peak = max((int(d.get("peak_bytes_in_use", 0))
                            for s in stats for d in s), default=0)
        losses = {k: dep.total(terms)
                  for k, terms in conf["losses"].items()}
        losses["udp_kernel_drops"] = udp_kernel_drops(dep.port)
        observed = dict(
            sent=n_sent, received=dep.total([conf["rx"]]),
            landed=dep.total(conf["terminal"]) - base,
            rejected=dep.total(conf["rejected"]), dups=dep.total(conf["dups"]),
            losses=losses,
            fallback_batches=dep.get(dev_tile, "fallback_batches"),
            device_errors=dep.get(dev_tile, "device_errors"),
            device_batches=dep.get(dev_tile, "device_batches"),
            compiles_in_window=dep.get(dev_tile, "device_programs")
            - programs0,
            failed_tiles=len(dep.failed_tiles()),
            parent_backend=int(dep.parent_backend_initialized()),
        )
        counters = {k: v for k, v in observed.items() if k != "losses"}
        dep.halt()
        if conf.get("balances"):
            observed["balances"] = dep.balances(pubs)
        if conf.get("siglog_tile"):
            observed["tags"] = dep.sunk_tags()
    finally:
        signer.shutdown(wait=True)
        sock.close()
        host_read = watch.done() if watch is not None else []
        if proc is not None and proc.is_alive():
            proc.kill()
            proc.join()
        if hook is not None:
            hook.close()
        if dep is not None:
            dep.close()
        if shm is not None:
            shm.close()
            shm.unlink()
        trace_events = (tracered.load(os.path.join(hook.dir, "trace"))
                        if trace and hook else [])
        shutil.rmtree(workdir, ignore_errors=True)

    # ---- the comparison that decides `correct` ---------------------------
    expected = reference.outcome(corp, n_sent, balances=bool(conf.get("balances")))
    checks = ledger.compare(observed, expected)
    say("ledger", **counters, **{f"loss_{k}": v for k, v in losses.items()})
    say("reference", **{k: v for k, v in expected.items()
                        if k not in ("balances", "tags")})

    window_s = (edge["T1"]["t"] - edge["T0"]["t"]) / 1e9
    # closed loop: the counter's growth between the two edge reads, over
    # the time between those reads (each a turn of the sender's loop late)
    rate = (edge["T1"]["count"] - edge["T0"]["count"]) / window_s
    lag_in_win = lag_sent = late = sent_in_win = due_in_win = None
    host = {}
    if open_loop:
        uniq = np.flatnonzero(kind == C.KIND_UNIQUE)
        t_due = due[uniq]
        lag, landed = sampler.completion_lags(t_due, ts, cs, base, t_end)
        in_win = (t_due >= T0) & (t_due < T1)
        attempted = int(in_win.sum())
        failed = int((~landed[in_win]).sum())
        rate = (sampler.count_at(ts, cs, T1, base)
                - sampler.count_at(ts, cs, T0, base)) / seconds
        lag_in_win = lag[in_win]
        row_in_win = (due >= T0) & (due < T1)
        late = (sent_at - due)[row_in_win]
        sent_in_win, due_in_win = sent_at[uniq][in_win], t_due[in_win]
        # the generator's share beside the program's: the lag a txn would
        # have seen from a sender that was never late
        lag_sent = sampler.after_send(lag_in_win, sent_in_win, due_in_win)
        # a backlog that grows shows as a lag that rises through the window
        half = t_due < (T0 + T1) // 2
        say("lag", first_half_p50_ms=round(sampler.percentile(
            lag[in_win & half], 50) / 1e6, 3),
            second_half_p50_ms=round(sampler.percentile(
                lag[in_win & ~half], 50) / 1e6, 3),
            **{f"after_send_p{q}_ms": round(
                sampler.percentile(lag_sent, q) / 1e6, 4) for q in (50, 95)},
            **{f"sender_late_p{q}_us": round(
                float(np.percentile(late, q)) / 1e3, 1) for q in (50, 95, 99)})
        host.update(sender.account(sender_said, due, cell["burst"], T0, T1))
        for what, rows in held.items():
            n, total, longest = sampler.held_in(rows, T0, T1)
            host.update({f"sampler_{what}_over_1ms": n,
                         f"sampler_{what}_sum_ms": hostacct.ms(total),
                         f"sampler_{what}_max_ms": hostacct.ms(longest)})
    else:
        attempted = edge["T1"]["sent"] - edge["T0"]["sent"]
        failed = max(n_sent - (observed["landed"] + observed["rejected"]
                               + observed["dups"]), 0)
    say("steadiness", t0_ns=edge["T0"]["t"], per_second_rate=[
        round((c1 - c0) / ((t1 - t0) / 1e9)) for (t0, c0, _), (t1, c1, _)
        in zip(by_second, by_second[1:])],
        in_flight=[f for _, _, f in by_second])
    # what the host did to the run between the edges (no metric: a far
    # run is laid at the generator's, the sampler's or the program's door)
    host = {**hostacct.window(host_read, list(tile_pids),
                              edge["T1"]["cpu_ns"] - edge["T0"]["cpu_ns"]),
            **host}
    say("host", **{k: hostacct.say(v) for k, v in host.items()})
    say("window", edge_to_edge_s=round(window_s, 4), attempted=attempted,
        failed=failed, setup_s=round(setup_s, 4), rate_tps=round(rate, 4))

    # ---- the metrics: each a file under metrics/ and a reader ------------
    # (--trace 0: the cell's end-to-end metrics; --trace 1: its per-layer)
    ctx = dict(rate_tps=rate, lag_ns=lag_in_win, setup_s=setup_s,
               by_second=by_second, t0_ns=T0, t1_ns=T1, seconds=seconds,
               sender_late_ns=late, sent_at_ns=sent_in_win,
               due_ns=due_in_win, lag_after_send_ns=lag_sent,
               send_busy_share=send_busy, config=conf, cell=cell)
    device_out = dict(device, memory_peak_bytes=mem_peak)
    result = dict(correct=ledger.correct(checks), attempted=attempted,
                  failed=failed)
    if trace:
        tr = None
        if traced:
            tr = dict(events=trace_events,
                      busy_s=tracered.busy_s(trace_events),
                      window_s=tracered.span_s(trace_events))
            if tr["busy_s"] is not None:
                device_out.update(busy_s=tr["busy_s"],
                                  window_s=tr["window_s"])
                result["breakdown"] = dict(
                    device_ops=tracered.top_ops(trace_events),
                    idle_gaps=tracered.idle_gaps(trace_events))
            say("trace", lines=[(p, l, len(e)) for p, l, e in trace_events
                                if len(e)][:40])
        # where frags waited: each in-link's queue wait and service time
        # (the program's log2 hists, window delta, medians in us)
        hist = load_reader(root, "hist_percentile")
        snaps = dict(before=edge["T0"]["snap"], after=edge["T1"]["snap"])
        say("links", **{f"{t}.{h}": round(v) for t, snap in sorted(
            snaps["after"].items()) for h in sorted(snap)
            if h.startswith(("qwait_us_", "svc_us_"))
            and (v := hist(snaps, [[t, h]], 50)) is not None})
        ctx.update(before=edge["T0"]["snap"], after=edge["T1"]["snap"],
                   sent_before=edge["T0"]["sent"],
                   sent_after=edge["T1"]["sent"],
                   kdrops_before=edge["T0"]["kdrops"],
                   kdrops_after=edge["T1"]["kdrops"], trace=tr)
    metrics = {}
    for mname, m in load_metrics(root, name, end_to_end=not trace).items():
        v = load_reader(root, m["reader"])(ctx, **m.get("args", {}))
        if v is not None:
            metrics[mname] = {"value": v, "unit": m["unit"]}
        elif not trace and mname in cell.get("metrics", []):
            # a cell's file does not promise what its run cannot give
            raise Malformed(
                f"workload {name!r} names the end-to-end metric {mname!r}, "
                f"and its reader found nothing to read in this run")
    result["metrics"] = metrics
    result["device"] = device_out
    result["checks"] = {n: [v, lim] for n, v, lim in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the files' tiny sizes; exits 3, "
                    "never a chip run")
    args = ap.parse_args(argv)
    pin = os.environ.get("JAX_PLATFORMS", "")
    try:
        if not args.rehearse and pin and "tpu" not in pin.lower():
            raise Malformed(f"no TPU: JAX is pinned to {pin!r} (the CPU "
                            f"rehearsal is --rehearse)")
        result = run_cell(HERE, args.workload, args.seed, args.seconds,
                          bool(args.trace), rehearse=args.rehearse,
                          require_chip=not args.rehearse)
    except Malformed as e:
        print(f"benchmark: NO RESULT: {e}", file=sys.stderr, flush=True)
        return EXIT_NO_RESULT
    for n, (v, lim) in result["checks"].items():
        print(f"check {n} {v} limit {lim}", file=sys.stderr)
    sys.stderr.flush()
    if args.rehearse:
        print("benchmark: REHEARSAL, not a chip run: "
              + json.dumps(result), flush=True)
        return EXIT_REHEARSED
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
