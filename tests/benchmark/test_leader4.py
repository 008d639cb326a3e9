"""The deployment `leader4` and its cell `leader4.paced` (ISSUE 29): the
files against `leader`'s, the metrics the cell reports, the two new
readers on hand-made inputs, and the cell run whole on the CPU: in this
process with the strict host verifier as each of the four devices, and
(slow tier) the one command on four virtual devices, traced.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from append_only import (appended_only, in_order,  # noqa: E402
                         rows_at_least)  # (this directory)

from benchmark import run as RUN  # noqa: E402
from benchmark.lib.deploy import load_config  # noqa: E402

ROOT = os.path.join(REPO, "benchmark")
CELL = "leader4.paced"
#: what the cell's own file may say differently from leader.paced's
OWN = {"config", "chips", "why", "who", "rate_why", "metrics"}
E2E = {"landed_tps", "lag_p50_ms", "lag_p95_ms", "setup_s"}
NEW_IN_ORDER = [
    "verify.reorder_ms_per_batch.leader4",
    "verify.reordered_batch_share.leader4",
    "verify.device_share_min.leader4",
    "verify.dispatch_overlap_share.leader4",
]
NEW = set(NEW_IN_ORDER)
#: rows that need the profiler's trace of the process that holds the chip
TRACE_ROWS = {"device.idle_share.leader", "verify_core.ms_per_batch",
              "verify.dispatch_ms_per_batch.leader",
              "verify.dispatch_overlap_share.leader4"}


def _load(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


def test_the_configuration_is_leader_with_the_pool_at_width_four():
    import tomllib

    from firedancer_tpu.app import config as C

    one, four = load_config(ROOT, "leader"), load_config(ROOT, "leader4")
    cfg = C.parse(four["toml_text"])
    assert cfg.verify_devices == 4 and cfg.verify_count == 1
    doc1, doc4 = (tomllib.loads(c["toml_text"]) for c in (one, four))
    assert doc4.pop("tiles") == {"verify": {"devices": 4}} and doc4 == doc1
    for key, want in four["sizes"].items():
        assert getattr(cfg, key) == want, key
    assert {k for k in four["sizes"] if four["sizes"][k] != one["sizes"][k]
            } == {"verify_devices"} and set(four["sizes"]) == set(one["sizes"])
    assert four["sizes"]["verify_devices"] == 4
    # the guarantees word for word, and everything the comparison reads
    for key in ("guarantees", "reduced", "accounts", "builder", "terminal",
                "rejected", "dups", "losses", "rx", "balances",
                "device_tile", "rehearse", "rehearse_accounts"):
        assert four[key] == one[key], key
    assert set(four) == set(one) and len(four["source"]) <= 200
    assert set(four["reduced_why"]) == set(four["reduced"])
    assert four["assumed"].keys() - one["assumed"].keys() == {"devices"}
    assert "four chips" in four["topology"]


def test_the_cell_is_leader_paced_but_for_its_width():
    one, four = _load("workloads", "leader.paced.json"), _load(
        "workloads", f"{CELL}.json")
    assert {k: v for k, v in four.items() if k not in OWN} == {
        k: v for k, v in one.items() if k not in OWN}
    assert (four["config"], four["chips"]) == ("leader4", 4)
    assert len(four["why"]) <= 200 and four["who"] and four["rate_why"]


@pytest.mark.parametrize("e2e", [True, False])
def test_the_cell_reports_what_it_names_and_the_four_new_rows(e2e):
    named = set(_load("workloads", f"{CELL}.json")["metrics"])
    found = set(RUN.load_metrics(ROOT, CELL, end_to_end=e2e))
    if e2e:
        assert found == E2E
    else:
        # at least: a later PR's row whose own file lists the cell is found too
        assert rows_at_least(found, (named - E2E) | NEW)
        assert "bank.e2e_p50_us.leader" not in found
    # and the accepted cells none of the new rows
    for cell in ("leader.paced", "ingress.flood"):
        assert not NEW & set(RUN.load_metrics(ROOT, cell, end_to_end=False))


def test_the_manifest_holds_the_cell_on_four_chips_and_appends_only():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    # behind what was there at PR 29, wherever later PRs' entries stand
    assert in_order([c["name"] for c in b["configs"]],
                    ["leader", "ingress", "leader4"])
    assert in_order([w["name"] for w in b["workloads"]],
                    ["leader.paced", "ingress.flood", CELL])
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["chips"]) == ("leader4", 4)
    # ONE four-chip cell (PERF.md section 4): a second needs its argument
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    # the four rows in order among themselves, not the manifest's last four
    assert in_order([m["name"] for m in b["per_layer"]], NEW_IN_ORDER)
    named = set(_load("workloads", f"{CELL}.json")["metrics"])
    for m in b["end_to_end"] + b["per_layer"]:
        own = _load("metrics", m["name"] + ".json").get("workloads", [])
        # the manifest lists the cell where the cell names the metric or
        # the metric's own file lists the cell, appended behind the file's
        assert (CELL in m.get("workloads", [])) == (
            m["name"] in named or CELL in own), m["name"]
        assert appended_only(m.get("workloads", []), own), m["name"]
        if m["name"] in NEW:
            assert own == [CELL]
            assert m["layer"] == "verify tile (host)"


# ---- the two new readers, on hand-made ctx dicts --------------------------

def _counters(before, after):
    return {"before": {"verify0": before}, "after": {"verify0": after}}


DEVS = [["verify0", f"dev{i}_landed"] for i in range(4)]


@pytest.mark.parametrize("before,after,want", [
    ([0, 0, 0, 0], [25, 25, 25, 25], 25.0),      # even
    ([10, 10, 10, 10], [40, 20, 20, 60], 10.0),  # window deltas, the least
    ([5, 5, 5, 5], [45, 45, 45, 5], 0.0),        # a chip that took nothing
    ([5, 5, 5, 5], [5, 5, 5, 5], None),          # nothing landed at all
    ([5, 5, 5, 9], [9, 9, 9, 5], None),          # a restart: never a number
    ([0, 0, 0], [9, 9, 9], None),                # a pool of three: not ours
])
def test_counters_min_share(before, after, want):
    read = RUN.load_reader(ROOT, "counters_min_share")
    ctx = _counters(*({f"dev{i}_landed": v for i, v in enumerate(side)}
                      for side in (before, after)))
    assert read(ctx, counters=DEVS, scale=100) == want
    assert read({"before": {}, "after": {}}, counters=DEVS) is None


def _trace(*lines, device=()):
    return {"trace": {"events": [
        ("/host:CPU", f"thread{i}", [(n, s, d) for n, s, d in ev])
        for i, ev in enumerate(lines)] + [
        ("/device:TPU:0", "XLA Ops", list(device))]}}


D = "fdt.verify.dispatch#seq=%d,lanes=35,dev=%d#"


@pytest.mark.parametrize("lines,want", [
    # two workers, one after the other: they queue
    (([(D % (1, 0), 0, 10)], [(D % (2, 1), 10, 10)]), 0.0),
    # the second opens half way through the first: 5 + 5 of 20
    (([(D % (1, 0), 0, 10)], [(D % (2, 1), 5, 10)]), 50.0),
    # four side by side all the time
    (tuple([(D % (i, i), 0, 8)] for i in range(4)), 100.0),
    # three open over [4, 6) of three spans of 10
    (([(D % (1, 0), 0, 10)], [(D % (2, 1), 4, 10)], [(D % (3, 2), -4, 10)]),
     100.0 * (2 * 2 + 3 * 2 + 2 * 4 + 2 * 2) / 30),
    # one span alone; other spans do not count
    (([(D % (1, 0), 0, 10), ("fdt.verify.land#seq=1#", 2, 50)],), 0.0),
    # the same span on two lines of the trace counts once
    (([(D % (1, 0), 0, 10)], [(D % (1, 0), 0, 10)]), 0.0),
    (([("fdt.verify.land#seq=1#", 0, 9)],), None),
])
def test_trace_span_overlap(lines, want):
    read = RUN.load_reader(ROOT, "trace_span_overlap")
    pattern = r"^fdt\.verify\.dispatch"
    got = read(_trace(*lines, device=[(D % (9, 9), 0, 99)]),
               pattern=pattern, scale=100)
    assert got == pytest.approx(want) if want is not None else got is None
    assert read({"trace": None}, pattern=pattern) is None
    assert read({}, pattern=pattern) is None


def test_the_two_new_hist_and_counter_rows_read_nothing_from_the_parent():
    """The parent's tile writes neither word: the rows are left out of
    its line, they do not read 0 (and they do not raise)."""
    files = RUN.load_metrics(ROOT, CELL, end_to_end=False)
    ctx = _counters({"device_batches": 5, "batch_drain_us": dict(
        buckets=[0] * 24, sum=10, count=5)}, {"device_batches": 9,
        "batch_drain_us": dict(buckets=[0] * 24, sum=90, count=9)})
    for name in ("verify.reorder_ms_per_batch.leader4",
                 "verify.reordered_batch_share.leader4"):
        m = files[name]
        assert RUN.load_reader(ROOT, m["reader"])(ctx, **m["args"]) is None
    ctx["after"]["verify0"].update(reordered_batches=3, batch_reorder_us=dict(
        buckets=[0] * 24, sum=2_000, count=9))
    ctx["before"]["verify0"].update(reordered_batches=1, batch_reorder_us=dict(
        buckets=[0] * 24, sum=0, count=5))
    got = {n: RUN.load_reader(ROOT, files[n]["reader"])(
        ctx, **files[n]["args"]) for n in NEW - TRACE_ROWS}
    assert got["verify.reorder_ms_per_batch.leader4"] == 0.5
    assert got["verify.reordered_batch_share.leader4"] == 50.0


# ---- the cell, run whole on the CPU ---------------------------------------

@pytest.mark.parametrize("trace", [False, True])
def test_a_whole_run_of_the_cell_with_four_stubbed_devices(monkeypatch, trace):
    """test_benchmark.py's rig at width four: thread runtime, the strict
    host verifier as each domain's device; sound -> `correct`, and the
    line holds every row that needs no profiler."""
    from benchmark.lib.deploy import Deployment
    from firedancer_tpu.ops.ed25519.hostpath import verify_batch_digest_host
    from firedancer_tpu.tiles.verify import VerifyTile

    widths = []
    monkeypatch.setattr(
        VerifyTile, "_make_device_fns", lambda self: widths.append(
            self.n_devices) or [verify_batch_digest_host] * self.n_devices)
    monkeypatch.setattr(Deployment, "parent_backend_initialized",
                        lambda self: False)
    res = RUN.run_cell(
        ROOT, CELL, seed=(1 << 31) + 29, seconds=1.0, trace=trace,
        rehearse=True, require_chip=False,
        overrides={"topo": {"runtime": "thread", "stem": "python"}})
    assert widths == [4]
    bad = {k for k, (v, lim) in res["checks"].items() if v > lim}
    assert res["correct"] and not bad, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    if not trace:
        assert set(res["metrics"]) == E2E
        return
    # (no native stem under the rig's python stem: its coverage row too)
    want = set(RUN.load_metrics(ROOT, CELL, end_to_end=False)) - TRACE_ROWS - {
        "runtime.stem_coverage.leader"}
    assert set(res["metrics"]) == want, want ^ set(res["metrics"])
    val = {n: m["value"] for n, m in res["metrics"].items()}
    assert (val["verify.reorder_ms_per_batch.leader4"]
            <= val["verify.drain_ms_per_batch.leader"])
    assert 0 <= val["verify.device_share_min.leader4"] <= 25.0
    assert 0 <= val["verify.reordered_batch_share.leader4"] < 100.0


@pytest.mark.slow
def test_cpu_rehearsal_on_four_virtual_devices_prints_every_row():
    """The one command end to end (process runtime, hook, profiler) with
    the pool on four virtual CPU devices.  Slow: the CPU lowers and
    compiles the verify program once a device, ~4 min of boot and ~28
    CPU-minutes (the same run at width one: ~1.5 min)."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "run.py"), "--workload", CELL,
         "--seed", str((1 << 31) + 29), "--seconds", "4", "--trace", "1",
         "--rehearse"],
        cwd=REPO, capture_output=True, text=True, timeout=1500,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS":
             "--xla_force_host_platform_device_count=4"})
    assert r.returncode == RUN.EXIT_REHEARSED, r.stderr[-3000:]
    tag = "benchmark: REHEARSAL, not a chip run: "
    line = [ln for ln in r.stdout.splitlines() if ln.startswith(tag)][-1]
    res = json.loads(line[len(tag):])
    assert res["device"]["count"] == 4
    assert res["checks"]["device_batches_missing"][0] == 0
    # (the CPU has no device plane: the two device rows stay out)
    want = set(RUN.load_metrics(ROOT, CELL, end_to_end=False)) - {
        "device.idle_share.leader", "verify_core.ms_per_batch"}
    assert want <= set(res["metrics"]), want - set(res["metrics"])
    val = {n: m["value"] for n, m in res["metrics"].items()}
    assert val["verify.dispatch_ms_per_batch.leader"] > 0
    assert (val["verify.reorder_ms_per_batch.leader4"]
            <= val["verify.drain_ms_per_batch.leader"])
