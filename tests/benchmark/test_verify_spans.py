"""The verify tile's spans as the benchmark reads them (ISSUE 25): the
three readers on recorded toy snapshots and traces, every new metric
file against the manifest, and the CPU rehearsal of both cells, traced,
which has to print every one of them with a value.
"""

import glob
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from append_only import appended_only, in_order  # noqa: E402  (this directory)

from benchmark import run as RUN  # noqa: E402

ROOT = os.path.join(REPO, "benchmark")

NEW = {
    "leader.paced": [
        "verify.fill_ms_per_batch.leader", "verify.queue_ms_per_batch.leader",
        "verify.inflight_ms_per_batch.leader",
        "verify.drain_ms_per_batch.leader",
        "verify.origin_to_dedup_ms.leader",
        "verify.dispatch_ms_per_batch.leader"],
    "ingress.flood": [
        "verify.expand_us_per_txn.ingress", "verify.submit_us_per_txn.ingress",
        "verify.results_us_per_txn.ingress",
        "verify.publish_us_per_txn.ingress", "verify.mux_busy_share.ingress",
        "verify.pool_full_share.ingress", "verify.frags_per_burst.ingress"],
}


def _hist(sum_, count):
    return {"buckets": [0] * 24, "sum": sum_, "count": count}


def _snaps(before, after):
    return {"before": {"verify0": before}, "after": {"verify0": after}}


@pytest.mark.parametrize("before,after,want", [
    ((1_000, 10), (61_000, 12), 30.0),   # moved: 60,000 us over 2 batches
    ((1_000, 10), (1_000, 10), None),    # not moved: nothing to read
    ((9_000, 10), (1_000, 12), None),    # torn: the sum went backwards
    ((1_000, 10), (9_000, 8), None),     # a restart: the count did
])
def test_hist_mean_reads_the_sum_and_count_words(before, after, want):
    read = RUN.load_reader(ROOT, "hist_mean")
    ctx = _snaps({"batch_queue_us": _hist(*before)},
                 {"batch_queue_us": _hist(*after)})
    got = read(ctx, hists=[["verify0", "batch_queue_us"]], scale=0.001)
    assert got == want
    # a program from before the hist (the parent commit) has nothing
    assert read(ctx, hists=[["verify0", "batch_fill_us"]]) is None
    assert read(ctx, hists=[["dedup", "batch_queue_us"]]) is None


def test_hist_mean_merges_pairs_and_is_exact_where_the_percentile_is_not():
    read = RUN.load_reader(ROOT, "hist_mean")
    ctx = {"before": {"a": {"h": _hist(0, 0)}, "b": {"h": _hist(50, 1)}},
           "after": {"a": {"h": _hist(300, 3)}, "b": {"h": _hist(150, 2)}}}
    assert read(ctx, hists=[["a", "h"], ["b", "h"]]) == 100.0


@pytest.mark.parametrize("before,after,want", [
    ((0, 0), (2 * 10**9, 3 * 10**9), 50.0),     # 5 s busy of a 10 s window
    ((7, 7), (7, 7), 0.0),                      # counters there, idle: a 0
    ((5, 5), (4, 9), None),                     # torn / restarted
])
def test_counters_per_second_is_a_share_of_the_window(before, after, want):
    read = RUN.load_reader(ROOT, "counters_per_second")
    ctx = _snaps({"expand_ns": before[0], "submit_ns": before[1]},
                 {"expand_ns": after[0], "submit_ns": after[1]})
    ctx.update(t0_ns=10**9, t1_ns=11 * 10**9)
    args = dict(counters=[["verify0", "expand_ns"], ["verify0", "submit_ns"]],
                scale=1e-7)
    assert read(ctx, **args) == want
    # the parent has no such counter: nothing, not a zero
    assert read(ctx, counters=[["verify0", "pool_full_ns"]]) is None
    assert read(dict(ctx, t1_ns=ctx["t0_ns"]), **args) is None


def test_trace_host_span_finds_the_programs_spans_by_search():
    read = RUN.load_reader(ROOT, "trace_host_span")
    trace = [
        ("/device:TPU:0", "XLA Ops", [
            ("verify_core.1", 0, 9_300_000),
            ("fdt.verify.dispatch", 0, 5)]),          # a device line: not ours
        ("/host:CPU", "verify0-dev0", [
            ("fdt.verify.dispatch#seq=7,lanes=85#", 100, 400_000),
            ("fdt.verify.dispatch", 900, 600_000),    # arguments as stats
            ("fdt.verify.land", 1_000, 9_000_000)]),
        ("/host:CPU", "verify0", [("fdt.verify.submit", 50, 30_000)]),
    ]
    ctx = {"trace": {"events": trace}}
    assert read(ctx, pattern=r"^fdt\.verify\.dispatch") == 0.5
    assert read(ctx, pattern=r"^fdt\.verify\.land") == 9.0
    assert read(ctx, pattern=r"^fdt\.verify\.nothing") is None
    assert read({"trace": None}, pattern="x") is None
    assert read({}, pattern="x") is None


def test_every_new_metric_is_a_file_and_the_manifest_says_the_same():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    readers = {os.path.basename(p)[:-3] for p in
               glob.glob(os.path.join(ROOT, "readers", "*.py"))}
    assert {"hist_mean", "counters_per_second", "trace_host_span"} <= readers
    for cell, names in NEW.items():
        found = RUN.load_metrics(ROOT, cell, end_to_end=False)
        for name in names:
            f, m = found[name], listed[name]
            assert f["workloads"] == [cell] and f["reader"] in readers
            for k in ("unit", "better", "source", "layer", "moves"):
                assert f[k] == m[k], (name, k)
            # the cells that name the row are appended behind the file's
            assert appended_only(m["workloads"], f["workloads"]), name
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
            assert f["layer"] == "verify tile (host)"
    # in the issue's order among themselves, wherever later PRs' rows stand
    ours = NEW["leader.paced"] + NEW["ingress.flood"]
    assert in_order(listed, ours)


@pytest.mark.parametrize("cell", sorted(NEW))
def test_cpu_rehearsal_prints_every_new_metric(cell):
    """The one command, end to end on the CPU (process runtime, the hook
    and the profiler included): the CPU profiler records the program's
    TraceAnnotations too, so the trace reader is exercised."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "run.py"), "--workload", cell,
         "--seed", str((1 << 31) + 25), "--seconds", "4", "--trace", "1",
         "--rehearse"],
        cwd=REPO, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == RUN.EXIT_REHEARSED, r.stderr[-3000:]
    tag = "benchmark: REHEARSAL, not a chip run: "
    line = [ln for ln in r.stdout.splitlines() if ln.startswith(tag)][-1]
    res = json.loads(line[len(tag):])
    # (`correct` is the whole-run tests' subject, and on a loaded CPU a
    # rehearsal can lose a datagram; this one asks what a sound run prints)
    assert res["checks"]["device_batches_missing"][0] == 0
    for name in NEW[cell]:
        assert name in res["metrics"], (name, sorted(res["metrics"]))
        # (a pool that never refused a staged lane reads a true 0 there)
        floor = -1 if name == "verify.pool_full_share.ingress" else 0
        assert res["metrics"][name]["value"] > floor, name
