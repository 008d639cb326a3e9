"""CPU rehearsal of chip_smoke.py: control flow and failure paths.

The tier-1 cases run the smoke's phases IN THIS PROCESS at the rehearsal
size with the device stubbed in the test (the strict host verifier stands
in for the accelerator, the host signer for the device signer), so they
cost no JAX compile and check what a CPU can check: the phases' control
flow, the drop ledger, the balance comparison — and that the smoke FAILS
when it must: a device error that the verify tile's host fallback would
otherwise hide fails the phase on `fallback_batches`, a phase exception
gives a non-zero exit, no TPU gives no `"ok": true`.

The slow case is rehearsal 1 of the on-chip-measurement guide's section
2: the command itself, end to end, every phase a real child process and
the real (plain-XLA, CPU) verify program.  A rehearsal that passes is
not a chip run and the script says so: exit code 3, never `"ok": true`.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke as CS
from firedancer_tpu.ops.ed25519 import hostpath

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
SEED = 5


class _HostSigner:
    """ops/ed25519/sign.py's surface on the host (tiles/bench.py imports
    the module as `dsign`): same signatures, no device program."""

    @staticmethod
    def public_keys(secrets):
        return [hostpath.public_from_secret(s) for s in secrets]

    @staticmethod
    def sign_many(pairs, pubs=None):
        return [hostpath.sign(sk, m) for sk, m in pairs]


@pytest.fixture
def corpus_dir(tmp_path, monkeypatch):
    """A workdir holding the rehearsal corpus, built by the smoke's own
    build_corpus (host-signed here)."""
    from firedancer_tpu.tiles import bench as tbench

    monkeypatch.setattr(tbench, "dsign", _HostSigner)
    np.savez(tmp_path / "corpus.npz", **CS.build_corpus(CS.TINY, SEED))
    return str(tmp_path)


def _stub_device(monkeypatch, fn):
    """Every verify tile built from here on dispatches to `fn` in place
    of its jitted kernel (the tile cannot tell: FallbackPolicy wraps
    whatever _make_device_fns returns)."""
    from firedancer_tpu.tiles.verify import VerifyTile

    monkeypatch.setattr(
        VerifyTile, "_make_device_fns",
        lambda self: [fn] * self.n_devices,
    )


def _host_verifier(digests, sigs, pubs):
    return hostpath.verify_batch_digest_host(digests, sigs, pubs)


def test_corpus_is_seeded_and_copies_follow_their_originals(corpus_dir):
    c = np.load(os.path.join(corpus_dir, "corpus.npz"))
    sz = CS.TINY
    kind, send = c["kind"], c["send"]
    assert [(kind == k).sum() for k in (0, 1, 2)] == [
        sz.n_unique, sz.n_dup, sz.n_bad
    ]
    body = [r[9:].tobytes() for r in send]  # past the dedup-tag bytes
    for i in np.flatnonzero(kind != 0):
        first = body.index(body[i])
        assert first < i and kind[first] == 0, "copy before its original"
    # exact duplicates are byte-identical; corrupted copies differ from
    # their original in exactly one bit of the dedup tag (sig[0:8])
    uniq = {r.tobytes() for r in send[kind == 0]}
    assert all(r.tobytes() in uniq for r in send[kind == 1])
    for i in np.flatnonzero(kind == 2):
        orig = send[body.index(body[i])]
        diff = np.unpackbits(send[i] ^ orig)
        assert diff.sum() == 1 and (send[i] ^ orig)[1:9].any()
    again = CS.build_corpus(sz, SEED)
    assert (again["send"] == send).all()
    assert (again["expected"] == c["expected"]).all()


def test_leader_phase_passes_and_closes_its_ledger(corpus_dir, monkeypatch):
    _stub_device(monkeypatch, _host_verifier)
    res = CS.phase_leader(CS.TINY, SEED, True, corpus_dir, runtime="thread")
    sz, led = CS.TINY, res["ledger"]
    assert led["landed"] == led["executed"] == sz.n_unique
    assert led["verify_rejected"] == sz.n_bad
    assert led["dup_pre_dedup"] + led["dup_dedup_tile"] == sz.n_dup
    assert led["sent"] == led["corpus"] == sz.n_unique + sz.n_dup + sz.n_bad
    assert res["balances_equal"] and not res["failed_tiles"]
    assert res["verify"]["device_batches"] >= sz.min_device_batches
    assert res["verify"]["fallback_batches"] == 0
    assert res["compiles_in_window"] == 0
    assert (res["runtime"], res["stem"]) == ("thread", "python")


def test_forced_device_error_fails_the_phase_on_fallback_batches(
    corpus_dir, monkeypatch
):
    """The verify tile survives a broken device by verifying on the host
    — a slower, CORRECT pipeline.  The smoke must not take that for a
    pass."""

    def broken(digests, sigs, pubs):
        raise RuntimeError("injected device error")

    _stub_device(monkeypatch, broken)
    with pytest.raises(CS.PhaseFailed, match="fallback_batches"):
        CS.phase_leader(CS.TINY, SEED, True, corpus_dir, runtime="thread")


def test_ingress_phase_counts_no_compile_while_serving(
    corpus_dir, monkeypatch
):
    _stub_device(monkeypatch, _host_verifier)
    res = CS.phase_ingress(CS.TINY, SEED, True, corpus_dir)
    assert res["sunk"] == sum(CS.TINY.trickle)
    assert res["compiles_in_window"] == 0
    assert res["verify"]["device_batches"] >= len(CS.TINY.trickle)


def test_ingress_topology_pads_to_one_shape():
    """What phase (d) relies on: the config-built ingress topology boots
    its verify tiles with one compiled shape, like the validator's: the
    configured `max_lanes`, which every batch pads to."""
    from firedancer_tpu.app import config as C

    cfg = C.parse("[tiles.verify]\nmax_lanes = 512\n")
    topo, _ = C.build_ingress_topology(cfg, b"\x07" * 32)
    assert topo.tiles["verify0"].tile.max_lanes == 512


def _run(args, cwd=REPO, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, capture_output=True, text=True,
        timeout=600, env={**os.environ, **env},
    )


def test_phase_exception_gives_nonzero_exit(tmp_path):
    """A phase that raises (here: no corpus in its workdir) ends its
    child with a traceback and a non-zero code, not a result line."""
    r = _run([SMOKE, "--phase", "leader-thread", "--rehearse",
              "--workdir", str(tmp_path)])
    assert r.returncode not in (0, CS.EXIT_REHEARSED)
    assert "FileNotFoundError" in r.stderr
    assert CS._RESULT_TAG not in r.stdout and '"ok": true' not in r.stdout


def test_without_a_tpu_the_smoke_fails_and_prints_no_ok():
    r = _run([SMOKE])
    assert r.returncode == CS.EXIT_FAILED
    assert "no TPU" in r.stdout and '"ok"' not in r.stdout


def test_alone_in_a_directory_the_smoke_fails(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(open(SMOKE).read())
    r = _run(["chip_smoke.py"], cwd=str(tmp_path), PYTHONPATH="")
    assert r.returncode != 0 and '"ok"' not in r.stdout
    assert "firedancer_tpu" in r.stderr  # the import that fails


def _fake_children(monkeypatch, platform="tpu", count=1, crash=None):
    """Replace the runner's phase children: each 'runs' instantly and
    reports `platform`; phase `crash` crashes.  Returns the start log."""
    started = []

    def run(name, args, workdir, cap_s):
        started.append(name)
        if name == crash:
            return CS.EXIT_CRASHED, None, 0.1
        cold = name == "kernel"
        prog = {"jit(verify_batch_digest)": dict(
            trace_s=1.0, lower_s=1.0, compile_s=9.0 if cold else 0.5,
            cache="miss" if cold else "hit")}
        return 0, {"device": {"platform": platform, "kind": "k",
                              "count": count, "cache_dir": "/x"},
                   "programs": prog}, 0.1

    monkeypatch.setattr(CS, "_run_child", run)
    return started


def test_runner_fails_when_a_phase_child_fails(monkeypatch, capsys):
    """The top level turns a phase's failure into its own: non-zero
    exit, the failing phase named, no later phase started."""
    started = _fake_children(monkeypatch, crash="corpus")
    assert CS.main([]) == CS.EXIT_CRASHED
    assert started == ["kernel", "corpus"]
    out = capsys.readouterr().out
    assert "FAILED in phase corpus" in out and '"ok"' not in out


def test_runner_prints_the_contract_line_last_only_on_a_tpu(
    monkeypatch, capsys
):
    import json

    _fake_children(monkeypatch, platform="tpu")
    assert CS.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {
        "ok": True, "device": {"platform": "tpu", "kind": "k", "count": 1}
    }
    # the same run on any other platform is a rehearsal, whatever passed
    _fake_children(monkeypatch, platform="cpu")
    assert CS.main([]) == CS.EXIT_REHEARSED
    assert '"ok"' not in capsys.readouterr().out


def test_chips4_runs_the_pool_phase_and_no_other(monkeypatch, capsys):
    started = _fake_children(monkeypatch, count=4)
    assert CS.main(["--chips", "4"]) == 0
    assert started == ["pool4"]
    assert '"count": 4' in capsys.readouterr().out.strip().splitlines()[-1]


@pytest.mark.slow
def test_rehearsal_end_to_end():
    """`python chip_smoke.py --rehearse`: every phase as a real child,
    the process runtime included, on the CPU's plain-XLA verify program
    (minutes: real compiles)."""
    r = subprocess.run(
        [sys.executable, SMOKE, "--rehearse", "--seed", str(SEED)],
        cwd=REPO, capture_output=True, text=True, timeout=3000,
    )
    assert r.returncode == CS.EXIT_REHEARSED, r.stdout[-4000:]
    assert "REHEARSAL PASSED" in r.stdout and '"ok"' not in r.stdout
    for phase in CS.DEFAULT_RUN:
        assert f"chip_smoke {phase}: seconds=" in r.stdout


@pytest.mark.slow
def test_rehearsal_four_virtual_devices():
    """`--chips 4 --rehearse` on four virtual CPU devices: the pool
    phase alone, four distinct placements, order equal to width 1."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    r = subprocess.run(
        [sys.executable, SMOKE, "--chips", "4", "--rehearse"],
        cwd=REPO, capture_output=True, text=True, timeout=3000, env=env,
    )
    assert r.returncode == CS.EXIT_REHEARSED, r.stdout[-4000:]
    assert "order_equal=True" in r.stdout
    assert "chip_smoke kernel" not in r.stdout
