"""What files alone can add since ISSUE 28: a cell that names the metrics
it reports — proved in a COPY of benchmark/ in which no file that is
there is edited — beside the pins that hold the two accepted cells to
what they read before: their metric name sets, the corpus's bytes, the
control, and the two workload files but for their `why` / `found` /
`rate_why` text.
"""

import hashlib
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from append_only import rows_at_least  # noqa: E402  (this directory)

from benchmark import control as CONTROL  # noqa: E402
from benchmark import run as RUN  # noqa: E402
from benchmark.lib import corpus as C  # noqa: E402

ROOT = os.path.join(REPO, "benchmark")

#: what load_metrics returned at the commit before ISSUE 28 (864cd8d): the
#: accepted cells still read at least these (later PRs append rows)
AT_HEAD = {
    ("leader.paced", True): {
        "lag_p50_ms", "lag_p95_ms", "landed_tps", "setup_s"},
    ("leader.paced", False): {
        "bank.e2e_p50_us.leader", "device.idle_share.leader",
        "pack.txns_per_microblock.leader", "runtime.stem_coverage.leader",
        "sender.late_p99_us.leader", "verify.batch_fill.leader",
        "verify.dispatch_ms_per_batch.leader",
        "verify.drain_ms_per_batch.leader", "verify.fill_ms_per_batch.leader",
        "verify.held_batch_share.leader",
        "verify.inflight_ms_per_batch.leader",
        "verify.origin_to_dedup_ms.leader",
        "verify.queue_ms_per_batch.leader", "verify_core.ms_per_batch",
        "wire.drop_share.leader"},
    ("ingress.flood", True): {"setup_s", "verified_tps"},
    ("ingress.flood", False): {
        "quic.tps_median_1s.ingress", "verify.batch_fill.ingress",
        "verify.expand_us_per_txn.ingress", "verify.fill_ms_per_batch.ingress",
        "verify.frags_per_burst.ingress", "verify.held_batch_share.ingress",
        "verify.inflight_ms_per_batch.ingress",
        "verify.mux_busy_share.ingress", "verify.pool_full_share.ingress",
        "verify.publish_us_per_txn.ingress",
        "verify.queue_ms_per_batch.ingress",
        "verify.results_us_per_txn.ingress",
        "verify.submit_us_per_txn.ingress", "wire.drop_share.ingress"},
}
#: the keys of the two workload files that are text about the cell and that
#: ISSUE 28 brought up to date; nothing that a run reads
TEXT = ("why", "found", "rate_why")
#: sha256 of each accepted workload file at that commit, `TEXT` left out
#: (json.dumps, sorted keys)
REST_AT_HEAD = {
    "leader.paced":
        "5c9637417a343b0282a68e2bd29475f631644952875dde193be49cfc6d5b4b7b",
    "ingress.flood":
        "9e755c27ab7f159a79eb6d8b143b31a08eb5bb924507c7122785eee74bcdb46a",
}
#: sha256 of send + kind + tags of lib/corpus.make_corpus(256, 16, 16, 64,
#: seed=5), taken at that commit
CORPUS_AT_HEAD = (
    "f6217655a8d01fb7d862a5c0fb1ec7534a599ee4e87702a44dd2a96c8a56a470")

PER_LAYER = "verify.batch_fill.ingress"


@pytest.fixture
def copy(tmp_path):
    """benchmark/ copied, and cells laid beside what is there, under the
    configurations and metrics that are there: files added, none edited."""
    root = tmp_path / "benchmark"
    shutil.copytree(ROOT, root, ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(root)
    common = dict(config="ingress", chips=1, warmup_s=0.5, dup_every=8,
                  bad_every=16, sender_stake=1000000, why="w", who="w")
    closed = dict(common, loop="closed", window_txns=128, corpus_tps=400)
    for name, cell in {
        "ingress.steady": dict(
            common, loop="open", rate_tps=120, burst=8, margin_s=0.5,
            metrics=["verified_tps", "lag_p50_ms", PER_LAYER]),
        "ingress.trickle": dict(closed, metrics=["verified_tps", PER_LAYER]),
        # a closed loop has no lag: its file may not promise one
        "ingress.wrong": dict(closed, metrics=["verified_tps", "lag_p50_ms"]),
        "ingress.typo": dict(closed, metrics=["verified_tsp"]),
        # what a further leader cell does (as `leader4.paced` did, PR 29):
        # leader.paced's rows, by name.  A name no real file has: the
        # fixture adds files, it may not write over one
        "leader9.paced": dict(
            json.load(open(root / "workloads" / "leader.paced.json")),
            metrics=sorted(AT_HEAD["leader.paced", True] - {"setup_s"}
                           | AT_HEAD["leader.paced", False])),
    }.items():
        (root / "workloads" / f"{name}.json").write_text(json.dumps(cell))
    assert {k: v for k, v in _digests(root).items() if k in before} == before
    return str(root)


def _digests(root) -> dict:
    return {os.path.relpath(os.path.join(d, f), root):
            hashlib.sha256(open(os.path.join(d, f), "rb").read()).hexdigest()
            for d, _, files in os.walk(root) for f in files}


def test_the_accepted_cells_read_what_they_read():
    for (cell, e2e), names in AT_HEAD.items():
        assert rows_at_least(RUN.load_metrics(ROOT, cell, end_to_end=e2e),
                             names), (cell, e2e)
    for cell, digest in REST_AT_HEAD.items():
        d = json.load(open(os.path.join(ROOT, "workloads", f"{cell}.json")))
        # neither names a metric: the metrics' own files list them
        assert "metrics" not in d
        rest = {k: v for k, v in d.items() if k not in TEXT}
        assert hashlib.sha256(json.dumps(rest, sort_keys=True).encode()
                              ).hexdigest() == digest, cell


def test_the_corpus_is_the_same_bytes():
    corp = C.make_corpus(256, 16, 16, 64, seed=5, workers=1)
    tags = corp["send"][:, C.SIG_OFF:C.SIG_OFF + 8].copy().view("<u8").ravel()
    assert hashlib.sha256(corp["send"].tobytes() + corp["kind"].tobytes()
                          + tags.tobytes()).hexdigest() == CORPUS_AT_HEAD
    assert len(corp["send"]) == 256 + 16 + 4


def test_a_cell_names_the_metrics_it_reports(copy):
    e2e = RUN.load_metrics(copy, "ingress.steady", end_to_end=True)
    assert set(e2e) == {"verified_tps", "lag_p50_ms", "setup_s"}
    assert set(RUN.load_metrics(copy, "ingress.steady", end_to_end=False)
               ) == {PER_LAYER}
    # the kind is still the metric file's: a name adds a cell, no more
    assert e2e["verified_tps"]["workloads"] == ["ingress.flood"]
    assert set(RUN.load_metrics(copy, "ingress.trickle", end_to_end=True)
               ) == {"verified_tps", "setup_s"}
    # the accepted cells' sets are not moved by their new neighbours
    for (cell, kind), names in AT_HEAD.items():
        found = set(RUN.load_metrics(copy, cell, end_to_end=kind))
        assert found == set(RUN.load_metrics(ROOT, cell, end_to_end=kind))
        assert rows_at_least(found, names)
    with pytest.raises(RUN.Malformed, match="verified_tsp"):
        RUN.load_metrics(copy, "ingress.typo", end_to_end=True)


@pytest.mark.parametrize("e2e", [True, False])
def test_a_second_leader_cell_reports_the_first_ones_rows_by_name(copy, e2e):
    """A further leader cell needs of the harness only this."""
    everyones = set(RUN.load_metrics(copy, "leader9.unnamed", end_to_end=e2e))
    # a cell that names nothing gets what lists every cell, and no more
    assert rows_at_least(everyones, {"setup_s"} if e2e else set())
    assert all("workloads" not in m for m in RUN.load_metrics(
        copy, "leader9.unnamed", end_to_end=e2e).values())
    assert set(RUN.load_metrics(copy, "leader9.paced", end_to_end=e2e)
               ) == AT_HEAD["leader.paced", e2e] | everyones


def _host_verifier(digests, sigs, pubs):
    from firedancer_tpu.ops.ed25519 import hostpath

    return hostpath.verify_batch_digest_host(digests, sigs, pubs)


def _rig(monkeypatch, root, cell, trace=False):
    """test_benchmark.py's rig: the harness's look for a chip skipped,
    thread runtime, the strict host verifier standing in for the device."""
    from benchmark.lib.deploy import Deployment
    from firedancer_tpu.tiles.verify import VerifyTile

    monkeypatch.setattr(VerifyTile, "_make_device_fns",
                        lambda self: [_host_verifier] * self.n_devices)
    # under the thread runtime the tiles run in THIS process, and an
    # earlier test file of the same worker may have initialised JAX here:
    # whether the topology's parent holds a backend says nothing in the rig
    monkeypatch.setattr(Deployment, "parent_backend_initialized",
                        lambda self: False)
    return RUN.run_cell(
        root, cell, seed=(1 << 31) + 28, seconds=1.0, trace=trace,
        rehearse=True, require_chip=False,
        overrides={"topo": {"runtime": "thread", "stem": "python"}})


@pytest.mark.parametrize("cell,trace,reports", [
    ("ingress.trickle", False, {"verified_tps", "setup_s"}),
    ("ingress.steady", False, {"verified_tps", "lag_p50_ms", "setup_s"}),
    ("ingress.trickle", True, {PER_LAYER}),
])
def test_a_whole_run_of_a_cell_added_as_files(monkeypatch, copy, cell, trace,
                                              reports):
    res = _rig(monkeypatch, copy, cell, trace)
    bad = {k for k, (v, lim) in res["checks"].items() if v > lim}
    assert res["correct"] and not bad, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == reports
    # (a rate may read 0 in a one-second window on a loaded sandbox)
    assert all(m["value"] >= 0 for m in res["metrics"].values())


def test_a_cell_may_not_promise_what_its_run_cannot_give(monkeypatch, copy):
    with pytest.raises(RUN.Malformed, match="lag_p50_ms"):
        _rig(monkeypatch, copy, "ingress.wrong")


@pytest.mark.parametrize("cell,rehearse", [
    ("leader.paced", True), ("ingress.flood", True),
    # the paced cell's own rate too (the flood's corpus is 150,000 txns
    # a second: PERF.md holds its control at full size)
    ("leader.paced", False),
])
def test_control_fails_with_either_guarantee_off(monkeypatch, capsys, cell,
                                                 rehearse):
    """control.py as it is run, at the files' tiny sizes and at the paced
    cell's own."""
    load_cell = RUN.load_cell
    monkeypatch.setattr(RUN, "load_cell", lambda root, name, _:
                        load_cell(root, name, rehearse))
    rc = CONTROL.main(["--workload", cell, "--seconds", "2", "--seeds", "28",
                       str((1 << 31) + 28)])
    out = capsys.readouterr().out
    assert rc == 0, out
    lines = [ln for ln in out.splitlines() if ln.startswith("control")]
    assert len(lines) == 4 and all("correct=False" in ln for ln in lines)
    assert sum("verify=off" in ln for ln in lines) == 2
    assert sum("dedup=off" in ln for ln in lines) == 2
