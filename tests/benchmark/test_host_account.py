"""Whose noise a paced run carries (ISSUE 33): the lag after send beside
the lag from due on a hand-made run, the sender's lateness at any
percentile, the `host` line's readers with every file of the host
absent, the sampler's account of what kept it from polling, and whole
runs of both loops that print the line with `null`s where the host
gives no reading.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from append_only import appended_only, rows_at_least  # noqa: E402  (this directory)

from benchmark import run as RUN  # noqa: E402
from benchmark.lib import hostacct, sampler, sender  # noqa: E402

ROOT = os.path.join(REPO, "benchmark")
MS = 1_000_000
ROWS = {  # this PR's four rows: (reader, q, the lag they stand beside)
    "lag.after_send_p50_ms.leader": ("lag_after_send", 50, "lag_p50_ms"),
    "lag.after_send_p95_ms.leader": ("lag_after_send", 95, "lag_p95_ms"),
    "sender.late_p50_us.leader": ("sender_lateness", 50, "lag_p50_ms"),
    "sender.late_p95_us.leader": ("sender_lateness", 95, "lag_p95_ms"),
}


def _run(late_burst_ms: float) -> dict:
    """100 bursts of 10 txns, 10 ms apart; the program lands every txn
    2 ms after it is sent; the sender runs burst 40 `late_burst_ms` late
    (and no other): what run.py puts into `ctx` of such a run."""
    due = np.repeat(np.arange(100) * 10 * MS, 10)
    sent = due + 50_000  # a send returns 50 us after its due time
    sent[400:410] += int(late_burst_ms * MS)
    lag = sent + 2 * MS - due
    return dict(lag_ns=lag, sent_at_ns=sent, due_ns=due,
                lag_after_send_ns=sampler.after_send(lag, sent, due),
                sender_late_ns=sent - due)


def _read(name: str, ctx: dict):
    m = RUN.load_metrics(ROOT, "leader.paced", end_to_end=False)[name]
    assert (m["reader"], m["args"]["q"]) == ROWS[name][:2]
    return RUN.load_reader(ROOT, m["reader"])(ctx, **m["args"])


def test_a_late_sender_moves_the_lag_from_due_and_not_the_lag_after_send():
    lag = RUN.load_reader(ROOT, "lag_percentile")
    on_time, late = _run(0.0), _run(5.0)
    # a sender 5 ms late on one burst of a hundred: the lag from due sees it
    assert lag(late, q=99.5) == pytest.approx(lag(on_time, q=99.5) + 5.0)
    for name in ("lag.after_send_p50_ms.leader", "lag.after_send_p95_ms.leader"):
        assert _read(name, late) == _read(name, on_time) == pytest.approx(2.0)
    after = RUN.load_reader(ROOT, "lag_after_send")
    assert after(late, q=100) == pytest.approx(2.0)
    # sent EARLY is no credit: a txn is never timed from before it was due
    early = sampler.after_send(on_time["lag_ns"], on_time["due_ns"] - MS,
                               on_time["due_ns"])
    assert (early == on_time["lag_ns"]).all()
    # a closed loop has no due times: nothing, never a zero
    assert after(dict(lag_after_send_ns=None), q=50) is None
    assert after({}, q=50) is None


@pytest.mark.parametrize("name,q", [
    ("sender.late_p50_us.leader", 50), ("sender.late_p95_us.leader", 95),
    ("sender.late_p99_us.leader", 99)])  # the last was there before
def test_sender_lateness_at_a_percentile(name, q):
    """One reader, the percentile a file's argument: files only."""
    m = RUN.load_metrics(ROOT, "leader.paced", end_to_end=False)[name]
    assert (m["reader"], m["args"]) == ("sender_lateness", {"q": q})
    read = RUN.load_reader(ROOT, m["reader"])
    ctx = dict(sender_late_ns=np.arange(1001) * 1000)  # 0 .. 1,000 us
    assert read(ctx, **m["args"]) == pytest.approx(10.0 * q)
    # one burst of a hundred 5 ms late: a tail's matter, not the body's
    late = _run(5.0)
    assert read(late, q=50) == pytest.approx(50.0)
    assert read(late, q=99.5) == pytest.approx(5050.0)
    assert read({}, **m["args"]) is None
    assert read(dict(sender_late_ns=None), **m["args"]) is None


def test_the_four_rows_are_files_in_both_paced_cells_and_move_their_lag():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for cell in ("leader.paced", "leader4.paced"):
        found = RUN.load_metrics(ROOT, cell, end_to_end=False)
        assert rows_at_least(found, ROWS)
        for name, (_, _, moves) in ROWS.items():
            f, m = found[name], listed[name]
            assert (f["layer"], f["moves"], f["better"]) == (
                "benchmark sender", moves, "lower") == (
                m["layer"], m["moves"], m["better"])
            assert appended_only(m["workloads"], f["workloads"])
    assert not set(ROWS) & set(RUN.load_metrics(ROOT, "ingress.flood", False))


@pytest.fixture
def no_host(monkeypatch, tmp_path):
    """Every file the `host` line reads, absent."""
    monkeypatch.setattr(hostacct, "PROC", str(tmp_path / "proc"))
    monkeypatch.setattr(hostacct, "CGROUP", str(tmp_path / "cgroup"))


def _watched(host, procs, work=lambda: None) -> list:
    """Two readings as a run takes them: by a Watch, at two marks."""
    now = time.monotonic_ns()
    watch = hostacct.Watch(host, (now, now + 100 * MS), procs)
    work()
    while len(watch.reads) < 2:
        time.sleep(0.01)
    assert watch.tid == threading.get_native_id()  # whom it reads, not itself
    return watch.done()


def test_the_readers_of_the_host_give_nothing_where_the_files_are_absent(no_host):
    assert hostacct.task(os.getpid()) is None
    assert hostacct.cpu_ms(os.getpid()) is None
    host = hostacct.Host()  # set-up finds that this host gives nothing,
    assert host.gives == dict(machine=False, task=False, cpu_ms=False)
    assert set(hostacct.machine(host.cpu_stat).values()) == {None}
    reads = _watched(host, {"quic": 7, "sender": 8})
    for read in reads:  # and an edge then opens no file at all
        assert read["machine"] is None and read["harness"] is None
        assert read["procs"] == read["procs_cpu_ms"] == {
            "quic": None, "sender": None}
    line = hostacct.window(reads, ["quic"], 3 * MS)
    assert line["steal_ms"] is None and line["harness_wait_ms"] is None
    assert line["busy_cpus"] is None and line["psi_some_avg10"] is None
    assert line["harness_cpu_ms"] == 3.0  # the loop thread's own clock
    assert line["sender_wait_ms"] is None and line["sender_cpu_ms"] is None
    assert line["tiles_wait_ms"] == line["tiles_off_cpu_ms"] == {"quic": None}
    assert hostacct.say(line["tiles_wait_ms"]) == "{quic:null}"
    assert hostacct.say(None) == "null" and hostacct.say([1, None]) == "[1,null]"
    # a run that never reached its window: what needs no reading, no failure
    early = hostacct.Watch(host, (2**62,), {})
    assert early.done() == []
    assert set(hostacct.window([], [])) == {
        "cpus_allowed", "cpus_online", "tile_procs"}
    # the sender's own stamps against the schedule: bursts 0-3 run 0, 1, 2,
    # 3 ms late (two of them by over a ms), woken 0.1 ms before their first
    # send returned, and every burst took 0.1 ms to leave
    due = np.repeat(np.arange(4) * 10 * MS, 10)
    said = dict(sent_at=due + np.repeat(np.arange(4), 10) * MS,
                woke_at=due[::10] + np.arange(4) * MS - 100_000)
    acct = sender.account(said, due, 10, 0, 40 * MS)
    assert (acct["sender_late_over_1ms"], acct["sender_late_sum_ms"]) == (2, 5.0)
    assert acct["sender_burst_send_us"] == [100.0, 100.0]
    assert acct["sender_wake_late_us"][0] == 1400.0
    assert sender.account(said, due, 10, 50 * MS, 60 * MS) == {}


def test_the_readers_of_the_host_read_this_host(tmp_path, monkeypatch):
    host = hostacct.Host()
    if not host.gives["task"]:
        pytest.skip("no /proc with schedstat or context switches here")
    me = os.getpid()
    def work(t0=time.process_time()):  # 50 ms of this process on a core
        while time.process_time() - t0 < 0.05:
            pass

    # between the two readings, which another thread takes
    reads = _watched(host, {"me": me, "sender": me}, work)
    line = hostacct.window(reads, ["me"], None)
    assert line["harness_run_ms"] is None or line["harness_run_ms"] > 0
    assert line["harness_cpu_ms"] is None
    assert line["sender_cpu_ms"] is None or line["sender_cpu_ms"] >= 0
    assert line["tiles_off_cpu_ms"]["me"] is None or (
        line["tiles_off_cpu_ms"]["me"] < 100.0)
    assert set(line["tiles_wait_ms"]) == {"me"} and len(line["edge_read_ms"]) == 2
    d = hostacct.delta(dict(a=5, b=None), dict(a=2, b=1))
    assert d == dict(a=3, b=None) and hostacct.delta(None, d) == {}
    # a host like the chip's: /proc/stat is there and all zeros, no
    # schedstat, a status with no context switches: no reading, no zero
    monkeypatch.setattr(hostacct, "PROC", str(tmp_path))
    monkeypatch.setattr(hostacct, "CGROUP", str(tmp_path / "cgroup"))
    (tmp_path / "stat").write_text("cpu  0 0 0 0 0 0 0 0 0 0\n")
    (tmp_path / str(me)).mkdir()
    (tmp_path / str(me) / "status").write_text("Name:\tpython3\nPid:\t1\n")
    (tmp_path / str(me) / "stat").write_text(
        f"{me} (tile: quic) R 1 1 1 0 -1 0 0 0 0 0 250 50 0 0 20 0 3 0\n")
    assert hostacct.Host().gives == dict(machine=False, task=False, cpu_ms=True)
    assert hostacct.cpu_ms(me) == 300 * 1e3 / hostacct._hz()
    # the cgroup's cpu.stat, found through /proc/self/cgroup
    (tmp_path / "self").mkdir()
    (tmp_path / "self" / "cgroup").write_text("0::/jobs\n")
    (tmp_path / "cgroup" / "jobs").mkdir(parents=True)
    (tmp_path / "cgroup" / "jobs" / "cpu.stat").write_text(
        "usage_usec 9\nnr_throttled 4\nthrottled_usec 7000\n")
    got = hostacct.machine(hostacct.Host().cpu_stat)
    assert (got["nr_throttled"], got["throttled_usec"]) == (4, 7000)


def test_the_sampler_says_what_kept_it_from_polling():
    calls = []

    def every(now):
        calls.append(now)
        if len(calls) == 2:
            time.sleep(0.005)  # a callback at other work for 5 ms

    n = iter(range(10**6))
    t_end = time.monotonic_ns() + 60 * MS
    ts, cs, gap, held = sampler.sample_until(
        lambda: next(n) // 8, lambda now, c: now >= t_end,
        every=every, every_ns=10 * MS)
    assert len(ts) == len(cs) and (np.diff(cs) > 0).all()
    assert held["every"].shape[1] == 2 and len(held["every"]) >= 1
    assert held["every"][:, 1].max() >= 5 * MS
    assert held["poll"].shape[1] == 2  # (when, how long), maybe none
    # the longest gap between two polls, whether or not any passed 1 ms
    assert 0 < gap and gap >= max(held["poll"][:, 1], default=0)
    count, total, longest = sampler.held_in(held["every"], 0, 2**62)
    assert count == len(held["every"]) and total >= longest >= 5 * MS
    assert sampler.held_in(held["every"], 0, 1) == (0, 0, 0)


def _host_verifier(digests, sigs, pubs):
    from firedancer_tpu.ops.ed25519 import hostpath

    return hostpath.verify_batch_digest_host(digests, sigs, pubs)


@pytest.mark.parametrize("cell", ["leader.paced", "ingress.flood"])
def test_a_whole_run_prints_the_host_line_with_the_hosts_files_absent(
        monkeypatch, capsys, no_host, cell):
    """test_benchmark.py's rig (thread runtime, the strict host verifier
    for the device): a missing /proc or cgroup file fails no run, and the
    line is there in both loops, `null` where there is no reading."""
    from benchmark.lib.deploy import Deployment
    from firedancer_tpu.tiles.verify import VerifyTile

    monkeypatch.setattr(VerifyTile, "_make_device_fns",
                        lambda self: [_host_verifier] * self.n_devices)
    monkeypatch.setattr(Deployment, "parent_backend_initialized",
                        lambda self: False)
    res = RUN.run_cell(
        ROOT, cell, seed=(1 << 31) + 33, seconds=1.0, trace=False,
        rehearse=True, require_chip=False,
        overrides={"topo": {"runtime": "thread", "stem": "python"}})
    bad = {k for k, (v, lim) in res["checks"].items() if v > lim}
    assert res["correct"] and not bad, res["checks"]
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("benchmark host: ")]
    assert len(line) == 1
    said = dict(kv.split("=", 1) for kv in line[0].split(": ", 1)[1].split())
    for key in ("steal_ms", "busy_cpus", "throttled_n", "throttled_ms",
                "psi_some_avg10", "harness_wait_ms", "harness_invol"):
        assert said[key] == "null", (key, said[key])
    assert said["tile_procs"] == "0" and int(said["cpus_online"]) >= 1
    assert float(said["harness_cpu_ms"]) > 0  # the thread's own clock
    if cell == "leader.paced":  # the open loop: the sender's and sampler's
        assert "sender_late_over_1ms" in said and said["sender_cpu_ms"] == "null"
        assert int(said["sampler_poll_over_1ms"]) >= 0
