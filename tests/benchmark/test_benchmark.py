"""The benchmark's own arithmetic and control flow, on the CPU, with no
JAX compile: the lag from a counter, the ledger and its control, the
trace reduction, the manifest against its files, that cells and metrics
are found as files, and a whole run driven in this process with the
device stubbed — sound, and broken underneath, where `correct` has to
come out false.
"""

import glob
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import run as RUN  # noqa: E402
from benchmark.lib import corpus as C  # noqa: E402
from benchmark.lib import ledger, reference, sampler, tracered  # noqa: E402

ROOT = os.path.join(REPO, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_lag_is_timed_from_due_and_a_stall_shows_in_p95():
    # 100 requests due 1 ms apart; the counter follows 2 ms behind, but
    # stalls from request 90 on for 50 ms and then catches up at once
    ms = 1_000_000
    t_due = np.arange(100) * ms
    ts = np.concatenate([t_due[:90] + 2 * ms, [t_due[89] + 52 * ms]])
    cs = 7 + np.concatenate([np.arange(1, 91), [100]])
    lag, landed = sampler.completion_lags(t_due, ts, cs, 7, 10**12)
    assert landed.all()
    assert (lag[:90] == 2 * ms).all()
    # every request queued behind the stall is charged from ITS due time
    assert lag[90] == 51 * ms and lag[99] == 42 * ms
    assert sampler.percentile(lag, 50) == 2 * ms
    assert sampler.percentile(lag, 95) > 40 * ms
    # a request the counter never reached fails, with the drain as its lag
    lag, landed = sampler.completion_lags(t_due, ts, cs - 1, 7, 10**9)
    assert landed.sum() == 99 and lag[99] == 10**9 - t_due[99]
    assert sampler.count_at(ts, cs, t_due[10] + 2 * ms, 7) == 7 + 11
    assert sampler.count_at(ts, cs, 0, 7) == 7


@pytest.fixture(scope="module")
def corp():
    return C.make_corpus(512, 16, 16, 64, seed=(1 << 31) + 12345, workers=1)


def _observed(out: dict, n_sent: int) -> dict:
    return dict(ledger.sound_observation(out, n_sent), losses={"quic": 0})


def test_corpus_is_seeded_and_the_reference_agrees_with_the_program(corp):
    """The benchmark's own generator and plain reference, held against
    the program's signer, parser and executor."""
    from firedancer_tpu.ballet import txn as T
    from firedancer_tpu.flamenco.accounts import Account, AccountMgr
    from firedancer_tpu.flamenco.runtime import Executor
    from firedancer_tpu.funk.funk import Funk
    from firedancer_tpu.ops.ed25519 import hostpath

    again = C.make_corpus(512, 16, 16, 64, seed=(1 << 31) + 12345, workers=1)
    assert (again["send"] == corp["send"]).all()
    kind, send, src = corp["kind"], corp["send"], corp["src"]
    assert [(kind == k).sum() for k in range(3)] == [512, 32, 8]
    first = {int(s): i for i, s in reversed(list(enumerate(src)))}
    for i in np.flatnonzero(kind != C.KIND_UNIQUE):
        j = first[int(src[i])]
        assert j < i and kind[j] == C.KIND_UNIQUE, "copy before its original"
        diff = np.unpackbits(send[i] ^ send[j]).sum()
        assert diff == (0 if kind[i] == C.KIND_DUP else 1)
        assert kind[i] == C.KIND_DUP or (send[i] ^ send[j])[1:9].any()
    rng = np.random.default_rng((1 << 31) + 12345)
    secret = rng.integers(0, 256, (16, 32), np.uint8)[3].tobytes()
    raw = send[first[3]].tobytes()  # txn 3 is signed by account 3
    desc = T.parse(raw)
    assert desc is not None and len(raw) == C.TXN_SZ
    assert raw[1:65] == hostpath.sign(secret, desc.message(raw))
    funk = Funk()
    mgr = AccountMgr(funk)
    pubs = [p.tobytes() for p in corp["pubs"]]
    for p in pubs:
        mgr.store(p, Account(C.START_LAMPORTS))
    ex = Executor(funk)
    ex.begin_slot(0)
    for row in send[kind == C.KIND_UNIQUE]:
        assert ex.execute_txn(row.tobytes()).ok
    want = np.array([mgr.lamports(p) for p in pubs], np.uint64)
    assert (reference.outcome(corp, len(send))["balances"] == want).all()


def test_ledger_closes_and_a_lost_txn_opens_it(corp):
    n = len(corp["send"])
    exp = reference.outcome(corp, n)
    checks = ledger.compare(_observed(exp, n), exp)
    assert ledger.correct(checks) and all(lim == 0 for _, _, lim in checks)
    # one txn vanished with no counter to name it
    lost = _observed(dict(exp, landed=exp["landed"] - 1), n)
    failing = {k for k, v, lim in ledger.compare(lost, exp) if v > lim}
    assert failing == {"landed_off", "ledger_open"}
    # the same txn dropped under a named counter still fails: the cell is
    # designed to lose none
    lost["losses"] = {"quic": 1}
    failing = {k for k, v, lim in ledger.compare(lost, exp) if v > lim}
    assert failing == {"landed_off", "loss_quic"}
    # a correct pipeline that verified on the host is a different result
    slow = _observed(exp, n)
    slow["fallback_batches"] = 2
    assert not ledger.correct(ledger.compare(slow, exp))


def test_control_with_a_guarantee_broken_is_not_correct(corp):
    """The control: the plain reference in the program's place with one
    stated guarantee switched off.  The comparison has to fail both."""
    n = len(corp["send"])
    exp = reference.outcome(corp, n)
    for broken, extra in (("verify", 8), ("dedup", 32)):
        control = _observed(reference.outcome(corp, n, **{broken: False}), n)
        checks = dict((k, v) for k, v, _ in ledger.compare(control, exp))
        assert not ledger.correct(ledger.compare(control, exp))
        assert checks["landed_off"] == extra
        assert checks["balances_differ"] > 0
        # the corrupted copies carry tags of their own; re-sends do not
        assert checks["tags_differ"] == (8 if broken == "verify" else 32)


def test_trace_reduction_busy_union_and_kernel_time():
    trace = [
        ("/device:TPU:0", "XLA Ops", [
            ("fusion.1", 0, 100), ("verify_core.3", 50, 100),  # overlap
            ("verify_core.3", 400, 200), ("copy.2", 1000, 0)]),
        ("/device:TPU:0", "XLA Modules", [("jit_f", 0, 5000)]),  # not ops
        ("/host:CPU", "tf_pjrt", [("TransferToDevice", 160, 200),
                                  ("short", 610, 5)]),
    ]
    assert tracered.union_ns(trace[0][2]) == 150 + 200
    assert tracered.busy_s(trace) == 350e-9
    assert tracered.kernel(trace, "verify_core") == (300e-9, 2)
    assert tracered.top_ops(trace)[0] == ["verify_core.3", 300e-9]
    gaps = tracered.idle_gaps(trace)
    assert gaps[0] == ["short", 400e-9]
    assert gaps[1] == ["TransferToDevice", 250e-9]
    # a trace with no device op yields nothing, never a zero
    assert tracered.busy_s([("/host:CPU", "x", [("a", 0, 5)])]) is None
    reader = RUN.load_reader(ROOT, "trace_busy")
    assert reader({"trace": None}) is None
    assert reader({"trace": dict(busy_s=1.0, window_s=4.0)}) == 75.0


def test_manifest_agrees_with_its_files():
    b = _manifest()
    assert b["command"] == ["python3", "benchmark/run.py"]
    assert b["paths"] == ["benchmark", "tests/benchmark"]
    configs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"]: w for w in b["workloads"]}
    assert list(cells)[:2] == ["leader.paced", "ingress.flood"]
    for c in b["configs"]:
        conf = json.load(open(os.path.join(REPO, c["file"])))
        assert c["source"] == conf["source"] and len(c["source"]) <= 200
        assert c["reduced"] == conf["reduced"]
        assert os.path.exists(os.path.join(ROOT, "configs", conf["toml"]))
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e
    reports = {w: {n for n, m in e2e.items()
                   if w in m.get("workloads", list(cells))} for w in cells}
    for w in b["workloads"]:
        cell = RUN.load_cell(ROOT, w["name"], False)
        assert (w["config"], w["chips"], w["why"]) == (
            cell["config"], cell["chips"], cell["why"])
        assert w["config"] in configs and len(w["why"]) <= 200
        assert w["traffic"] == w["name"].split(".", 1)[1]
        assert len(reports[w["name"]]) >= 2 and cell["who"]
    files = {os.path.basename(p)[:-5]: json.load(open(p)) for p in
             glob.glob(os.path.join(ROOT, "metrics", "*.json"))}
    # a cell may name the metrics it reports in its own file: the manifest
    # then lists it behind the cells the metric's file lists (a PR that
    # adds a cell appends its name there)
    named = {w: RUN.load_cell(ROOT, w, False).get("metrics", [])
             for w in cells}

    def listed(name):
        f = files[name]
        return None if "workloads" not in f else f["workloads"] + [
            w for w in cells if name in named[w] and w not in f["workloads"]]

    assert all(n in files for ns in named.values() for n in ns)
    # every metric of either kind is a file, and the file says the same
    assert {m["name"] for m in b["per_layer"]} == {
        n for n, f in files.items() if not f.get("end_to_end")}
    assert set(e2e) == {n for n, f in files.items() if f.get("end_to_end")}
    for m in b["end_to_end"]:
        f = files[m["name"]]
        assert [m.get(k) for k in ("unit", "better", "source")] == [
            f.get(k) for k in ("unit", "better", "source")], m["name"]
        assert m.get("workloads") == listed(m["name"]), m["name"]
        assert os.path.exists(os.path.join(ROOT, "readers",
                                           f["reader"] + ".py"))
    for m in b["per_layer"]:
        f = files[m["name"]]
        for k in ("unit", "better", "source", "layer", "moves"):
            assert m[k] == f[k], (m["name"], k)
        assert m["workloads"] == listed(m["name"]), m["name"]
        assert os.path.exists(os.path.join(ROOT, "readers",
                                           f["reader"] + ".py"))
        for w in m["workloads"]:
            assert m["moves"] in reports[w], (m["name"], w)
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for name in list(configs) + list(cells):
        assert NAME.match(name)


def test_a_cell_and_a_metric_added_as_files_are_found_with_no_edit(tmp_path):
    root = tmp_path / "benchmark"
    shutil.copytree(ROOT, root, ignore=shutil.ignore_patterns("__pycache__"))
    cell = json.load(open(root / "workloads" / "ingress.flood.json"))
    cell["window_txns"] = 4096
    # the cell names what it reports of the metrics that are there
    cell["metrics"] = ["verified_tps", "verify.batch_fill.ingress"]
    (root / "workloads" / "ingress.trickle.json").write_text(json.dumps(cell))
    (root / "readers" / "always_seven.py").write_text(
        "def read(ctx, plus):\n    return 7 + plus\n")
    (root / "metrics" / "quic.sevens.json").write_text(json.dumps(dict(
        unit="txn", better="higher", source="program_counter", layer="wire "
        "edge", moves="verified_tps", workloads=["ingress.trickle"],
        reader="always_seven", args={"plus": 1})))
    # an end-to-end metric is a file too: the lag's 99th percentile
    (root / "metrics" / "lag_p99_ms.json").write_text(json.dumps(dict(
        end_to_end=True, unit="ms", better="lower", source="host_clock",
        workloads=["ingress.trickle"], reader="lag_percentile",
        args={"q": 99})))
    # and a deployment's builder is a module found by the name it gives
    (root / "builders" / "ingress_twin.py").write_text(
        "def build(*a):\n    return 'twin'\n")
    root = str(root)
    assert RUN.load_cell(root, "ingress.trickle", False)["window_txns"] == 4096
    found = RUN.load_metrics(root, "ingress.trickle", end_to_end=False)
    assert set(found) == {"quic.sevens", "verify.batch_fill.ingress"}
    m = found["quic.sevens"]
    assert RUN.load_reader(root, m["reader"])({}, **m["args"]) == 8
    assert "quic.sevens" not in RUN.load_metrics(root, "ingress.flood", False)
    e2e = RUN.load_metrics(root, "ingress.trickle", end_to_end=True)
    assert set(e2e) == {"lag_p99_ms", "setup_s", "verified_tps"}
    assert "verified_tps" not in RUN.load_metrics(root, "no.such", True)
    lag = RUN.load_reader(root, "lag_percentile")
    assert lag({"lag_ns": np.arange(101) * 1e6}, q=99) == 99.0
    assert lag({"lag_ns": None}, q=99) is None  # a closed loop has no lag
    from benchmark.lib.deploy import load_builder
    assert load_builder(root, "ingress_twin")() == "twin"
    with pytest.raises(ValueError, match="no_such"):
        load_builder(root, "no_such")
    med = RUN.load_reader(root, "per_second_median")
    pts = [(i * 10**9, c) for i, c in enumerate([0, 100, 200, 300, 1000, 1100])]
    assert med(dict(by_second=pts, t0_ns=0, t1_ns=5 * 10**9)) == 100.0
    with pytest.raises(RUN.Malformed, match="ingress.trickle"):
        RUN.load_cell(root, "no.such", False)


def test_without_a_tpu_there_is_no_result(tmp_path):
    cmd = [sys.executable, os.path.join(ROOT, "run.py"), "--workload",
           "leader.paced", "--seed", "1", "--seconds", "2", "--trace", "0"]
    r = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True,
                       timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == RUN.EXIT_NO_RESULT and "no TPU" in r.stderr
    assert "correct" not in r.stdout
    # alone in a directory (only BENCHMARK.json and the paths): the
    # program's import fails, and so does the run
    shutil.copytree(ROOT, tmp_path / "benchmark")
    r = subprocess.run(cmd[:1] + ["benchmark/run.py"] + cmd[2:],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=120, env={k: v for k, v in os.environ.items()
                                         if k not in ("JAX_PLATFORMS",
                                                      "PYTHONPATH")})
    assert r.returncode != 0 and "correct" not in r.stdout
    assert "firedancer_tpu" in r.stderr


def _host_verifier(digests, sigs, pubs):
    from firedancer_tpu.ops.ed25519 import hostpath

    return hostpath.verify_batch_digest_host(digests, sigs, pubs)


def _admits_all(digests, sigs, pubs):
    return np.ones(len(digests), bool)  # an answer altered where it is made


def _drops_half(digests, sigs, pubs):
    ok = _host_verifier(digests, sigs, pubs)
    ok[::2] = False  # half of every batch left out
    return ok


@pytest.mark.parametrize("cell,device,failing", [
    ("leader.paced", _host_verifier, set()),
    ("leader.paced", _admits_all,
     {"landed_off", "rejected_off", "balances_differ"}),
    ("ingress.flood", _host_verifier, set()),
    ("ingress.flood", _drops_half,
     {"landed_off", "rejected_off", "tags_differ"}),
])
def test_a_whole_run_with_the_timed_path_stubbed_or_broken(
        monkeypatch, cell, device, failing):
    """Skips the harness's look for a chip and drives the rest of a run
    (thread runtime, tiny sizes, the strict host verifier standing in
    for the device): sound -> correct; broken underneath -> not."""
    from benchmark.lib.deploy import Deployment
    from firedancer_tpu.tiles.verify import VerifyTile

    monkeypatch.setattr(VerifyTile, "_make_device_fns",
                        lambda self: [device] * self.n_devices)
    # under the thread runtime the tiles run in THIS process, and an
    # earlier test file of the same worker may have initialised JAX here:
    # whether the topology's parent holds a backend says nothing in the rig
    monkeypatch.setattr(Deployment, "parent_backend_initialized",
                        lambda self: False)
    # two seconds: on a loaded host a one-second window has read a rate
    # of 0 (the thread runtime's tiles and the sender share one GIL)
    res = RUN.run_cell(
        ROOT, cell, seed=(1 << 31) + 7, seconds=2.0, trace=False,
        rehearse=True, require_chip=False,
        overrides={"topo": {"runtime": "thread", "stem": "python"}})
    bad = {k for k, (v, lim) in res["checks"].items() if v > lim}
    assert res["correct"] == (not failing) and failing <= bad, res["checks"]
    assert list(res)[-1] == "checks" and res["attempted"] > 0
    if not failing:
        assert res["failed"] == 0 and not bad
        assert set(res["metrics"]) == (
            {"landed_tps", "lag_p50_ms", "lag_p95_ms", "setup_s"}
            if cell == "leader.paced" else {"verified_tps", "setup_s"})
        assert ("tags_differ" in res["checks"]) == (cell == "ingress.flood")
        assert all(m["value"] > 0 for m in res["metrics"].values())
