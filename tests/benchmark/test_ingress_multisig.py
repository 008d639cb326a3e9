"""The committed multi-signature deployment and its cell
(`configs/ingress_multisig.json`, `workloads/ingress_multisig.flood.json`):
found through the harness's own resolution, `ingress.flood` but for the
txn shape and the corpus's rate; a whole run of the committed files at
their rehearsal size, correct with the strict verifier and not correct
with the tile's join cut to lane 0 (test_multisig.py's rig); the three
controls at the same size; and the reader of the kernel's time a tile.
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from test_multisig import SEED, _rig  # noqa: E402  (this directory)

from benchmark import control as CONTROL  # noqa: E402
from benchmark import run as RUN  # noqa: E402
from benchmark.lib import corpus as C  # noqa: E402
from benchmark.lib.deploy import load_config  # noqa: E402

ROOT = os.path.join(REPO, "benchmark")
CELL = "ingress_multisig.flood"
PER_TILE = "verify_core.ms_per_tile.ingress_multisig"
#: the workload files' text about their cell; nothing a run reads
TEXT = ("why", "who", "found", "window_why", "corpus_why")


def test_the_configuration_is_ingress_with_a_txn_shape():
    conf, ingress = load_config(ROOT, "ingress_multisig"), load_config(
        ROOT, "ingress")
    assert conf["toml"] == ingress["toml"] == "ingress.toml"
    assert conf["toml_text"] == ingress["toml_text"]
    assert set(conf) == set(ingress) | {"txn_shape"}
    said = ("source", "topology", "assumed", "txn_shape")
    assert {k: v for k, v in conf.items() if k not in said} == {
        k: v for k, v in ingress.items() if k not in said}
    assert set(conf["assumed"]) == set(ingress["assumed"]) | {"txn_shape"}
    for where in ("fd_txn.h:68", "fd_txn.h:103", "config.c:681-712"):
        assert where in conf["source"]
    assert len(conf["source"]) <= 200
    # every signer count of a transfer that fits the MTU, at equal weight;
    # the rehearsal group leaves the shape alone (signer_weights refuses
    # one that changes it)
    w = C.signer_weights(conf)
    assert len(w) == C.MAX_SIGNERS + 1 and w[0] == 0
    assert (w[1:] == w[1]).all() and w[1] > 0
    assert "txn_shape" not in conf["rehearse"]


@pytest.mark.parametrize("rehearse", [False, True])
def test_the_cell_is_the_flood_but_for_its_corpus_rate(rehearse):
    cell = RUN.load_cell(ROOT, CELL, rehearse)
    flood = RUN.load_cell(ROOT, "ingress.flood", rehearse)
    assert set(cell) == set(flood) | {"metrics"}
    differ = {k for k in flood if k not in TEXT and cell[k] != flood[k]}
    assert differ == ({"config"} if rehearse else {"config", "corpus_tps"})
    assert cell["config"] == "ingress_multisig" and cell["chips"] == 1
    assert cell["corpus_tps"] == (400 if rehearse else 90_000)
    assert len(cell["why"]) <= 200


def test_the_cell_reports_the_verify_paths_rows_and_its_own():
    e2e = RUN.load_metrics(ROOT, CELL, end_to_end=True)
    assert set(e2e) == {"verified_tps", "setup_s"}
    per_layer = RUN.load_metrics(ROOT, CELL, end_to_end=False)
    named = RUN.load_cell(ROOT, CELL, False)["metrics"]
    assert set(per_layer) == set(named) - {"verified_tps"} | {PER_TILE}
    assert all(m["moves"] == "verified_tps" for m in per_layer.values())
    # rows whose direction is known to be wrong at load, and the kernel's
    # time a batch, which moves a lag this closed loop does not have
    for left_out in ("verify.held_batch_share.ingress",
                     "device.idle_share.leader", "verify_core.ms_per_batch"):
        assert left_out not in per_layer
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert [c["name"] for c in manifest["configs"]][-1] == "ingress_multisig"
    assert manifest["workloads"][-1] == dict(
        name=CELL, config="ingress_multisig", traffic="flood", chips=1,
        why=RUN.load_cell(ROOT, CELL, False)["why"])
    assert manifest["per_layer"][-1]["name"] == PER_TILE


@pytest.mark.parametrize("lane0,failing", [
    (False, set()),
    (True, {"landed_off", "rejected_off", "tags_differ"}),
])
def test_a_whole_run_of_the_committed_cell(monkeypatch, capsys, lane0,
                                           failing):
    """test_multisig.py's laid-as-files run, on the committed files."""
    res = _rig(monkeypatch, ROOT, lane0)
    bad = {k for k, (v, lim) in res["checks"].items() if v > lim}
    assert res["correct"] == (not failing) and failing <= bad, res["checks"]
    assert res["attempted"] > 0
    if not failing:
        assert not bad and res["failed"] == 0
        assert set(res["metrics"]) == {"verified_tps", "setup_s"}
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines()
                if ln.startswith("benchmark corpus:"))
    kv = dict(w.split("=", 1) for w in line.split()[2:])
    assert int(kv["txn_bytes_max"]) == C.txn_size(C.MAX_SIGNERS)
    assert int(kv["lanes"]) > 5 * int(kv["rows"])


def test_each_control_fails_at_the_rehearsal_size(monkeypatch, capsys):
    """control.py as it is run, on the committed cell at its `rehearse`
    group's size: verify off, dedup off, and the verifier that checks
    each txn's signature 0 alone."""
    load_cell = RUN.load_cell
    monkeypatch.setattr(RUN, "load_cell", lambda root, name, _:
                        load_cell(root, name, True))
    rc = CONTROL.main(["--workload", CELL, "--seconds", "1", "--seeds", "39",
                       str(SEED)])
    out = capsys.readouterr().out
    assert rc == 0, out
    lines = [ln for ln in out.splitlines() if ln.startswith("control")]
    assert len(lines) == 6 and all("correct=False" in ln for ln in lines)
    for how in ("verify=off", "dedup=off", "verify=lane0"):
        assert sum(how in ln for ln in lines) == 2, how


def _ctx(trace, tiles=(100, 130), batches=(40, 50)):
    return {"trace": trace,
            "before": {"verify0": {"device_tiles": tiles[0],
                                   "device_batches": batches[0]}},
            "after": {"verify0": {"device_tiles": tiles[1],
                                  "device_batches": batches[1]}}}


def _trace(kernel_ms=(1.8, 1.8, 1.8)):
    ms = 1_000_000
    return {"events": [
        ("/device:TPU:0", "XLA Ops",
         [("verify_core.1", 10 * i * ms, int(d * ms))
          for i, d in enumerate(kernel_ms)]
         + [("concatenate.12", 5 * ms, ms)]),        # not the kernel
        ("/host:CPU", "verify0-dev0",
         [("fdt.verify.land", 10 * i * ms, 2 * ms)
          for i in range(len(kernel_ms))]
         + [("fdt.verify.dispatch", 0, ms)]),
    ]}


def _read(ctx):
    with open(os.path.join(ROOT, "metrics", f"{PER_TILE}.json")) as f:
        m = json.load(f)
    return RUN.load_reader(ROOT, m["reader"])(ctx, **m["args"])


def test_the_kernel_per_tile_reads_the_slice_over_its_tiles(capsys):
    # three calls, 5.4 ms of kernel; the window's batches ran 30 tiles in
    # 10, three a batch: 5.4 ms over 9 tiles
    assert _read(_ctx(_trace())) == pytest.approx(0.6)
    line = capsys.readouterr().out.strip()
    assert line.startswith("benchmark kernel_tiles: slice_calls=3 "
                           "slice_spans=3 ")
    assert "tiles_per_batch=3.0000" in line and "ms_per_tile=0.60000" in line
    # a batch twice the tiles: the time a tile does not move with it
    assert _read(_ctx(_trace((3.6, 3.6, 3.6)), tiles=(0, 60))
                 ) == pytest.approx(0.6)


@pytest.mark.parametrize("ctx", [
    {"trace": None},                                 # an untraced run
    {},
    _ctx({"events": []}),                            # no kernel call
    _ctx(_trace(), tiles=(7, 7)),                    # no tile counted
    _ctx(_trace(), batches=(5, 5)),
    # the parent commit: no `device_tiles` counter at either edge
    {"trace": _trace(),
     "before": {"verify0": {"device_batches": 40}},
     "after": {"verify0": {"device_batches": 50}}},
])
def test_the_kernel_per_tile_finds_nothing_to_read(ctx):
    assert _read(ctx) is None
