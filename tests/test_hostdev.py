"""Device selection and start-up (utils/hostdev.py and its callers): no
silent choice of device, one process per chip, one place that decides
where the compile cache lives."""

import os
import subprocess
import sys

import pytest

from firedancer_tpu.utils import hostdev

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _py(code: str, **env) -> subprocess.CompletedProcess:
    e = {k: v for k, v in os.environ.items()
         if k != "JAX_COMPILATION_CACHE_DIR"}
    e.update(env)
    return subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=e,
        capture_output=True, text=True, timeout=300,
    )


# ---- compile cache ---------------------------------------------------------


def test_cache_dir_unset_is_the_fixed_checkout_directory(monkeypatch):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert hostdev.compilation_cache_dir() == os.path.join(
        REPO, ".jax_cache"
    )
    assert hostdev.enable_compilation_cache() == os.path.join(
        REPO, ".jax_cache"
    )
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        REPO, ".jax_cache"
    )
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cache_dir_from_environment_is_left_to_jax(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the code names no directory
    at all: JAX read the variable itself."""
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: updates.append(k)
    )
    assert hostdev.enable_compilation_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in updates


_COMPILE_SOMETHING = (
    "from firedancer_tpu.utils import hostdev; import jax, jax.numpy as jnp\n"
    "print(hostdev.enable_compilation_cache())\n"
    "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)\n"
    "jax.jit(lambda x: jnp.cos(x) @ x.T + {salt})(jnp.ones((4, 4)))\n"
)


def test_cache_files_land_where_the_environment_says(tmp_path):
    r = _py(_COMPILE_SOMETHING.format(salt=1.25),
            JAX_COMPILATION_CACHE_DIR=str(tmp_path), JAX_PLATFORMS="cpu")
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == str(tmp_path)
    assert any(n.endswith("-cache") for n in os.listdir(tmp_path))


def test_cache_files_land_in_the_checkout_when_unset():
    salt = float(os.getpid())  # a program no earlier run has cached
    cache = os.path.join(REPO, ".jax_cache")
    before = set(os.listdir(cache)) if os.path.isdir(cache) else set()
    r = _py(_COMPILE_SOMETHING.format(salt=salt), JAX_PLATFORMS="cpu")
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == cache
    assert set(os.listdir(cache)) - before


# ---- device inventory ------------------------------------------------------


def test_local_device_count_leaves_the_caller_off_the_backend():
    """"auto" in a topology parent: the count comes from a child that
    exits, and the caller has still not initialised a backend — the
    chip stays free for the verify tile's own process."""
    r = _py(
        "from firedancer_tpu.utils import hostdev\n"
        "n = hostdev.local_device_count()\n"
        "print(n, hostdev.backend_initialized())\n"
        "from firedancer_tpu.disco.topo import device_assignments\n"
        "print(device_assignments('auto', 2), hostdev.backend_initialized())",
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=3",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split("\n")[:2] == [
        "3 False", "[[0, 2], [1]] False"
    ]


def test_local_device_count_in_a_process_that_owns_the_backend():
    import jax

    n = len(jax.local_devices())  # conftest's virtual mesh, now live
    assert hostdev.backend_initialized()
    assert hostdev.local_device_count() == n


def test_local_device_count_raises_when_jax_fails(monkeypatch):
    """No guessed 1: the caller asked for the real inventory."""
    monkeypatch.setattr(hostdev, "backend_initialized", lambda: False)
    monkeypatch.setattr(
        subprocess, "run",
        lambda *a, **k: subprocess.CompletedProcess(
            a, 1, "", "RuntimeError: Unable to initialize backend 'tpu'"
        ),
    )
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        hostdev.local_device_count()


def test_verify_tile_auto_width_is_the_local_inventory():
    import jax

    from firedancer_tpu.tiles.verify import VerifyTile

    t = VerifyTile(devices="auto")
    assert t.device_indices == list(range(len(jax.local_devices())))
    assert t.device_ordinals() == tuple(t.device_indices)
    # host-only and stubbed tiles have no accelerator behind them
    assert VerifyTile(device="off").device_ordinals() == ()
    assert VerifyTile(device_fn=lambda *a: None).device_ordinals() == ()


# ---- one process per chip --------------------------------------------------


def _two_verify_topology(devs_a, devs_b):
    from firedancer_tpu.disco import Topology
    from firedancer_tpu.tiles import wire
    from firedancer_tpu.tiles.synth import SynthTile
    from firedancer_tpu.tiles.verify import VerifyTile

    import numpy as np

    topo = Topology(name=f"own{os.getpid()}", runtime="process")
    topo.link("src", depth=64, mtu=wire.LINK_MTU)
    topo.tile(
        SynthTile(np.zeros((1, wire.LINK_MTU), np.uint8),
                  np.zeros(1, np.uint16), total=0),
        outs=["src"],
    )
    for i, d in enumerate((devs_a, devs_b)):
        topo.link(f"out{i}", depth=64, mtu=wire.LINK_MTU)
        topo.tile(
            VerifyTile(devices=d, shard=(i, 2), name=f"verify{i}"),
            ins=[("src", True)], outs=[f"out{i}"],
        )
    return topo


def test_two_tile_processes_on_one_chip_fail_at_build(monkeypatch):
    monkeypatch.setattr(hostdev, "cpu_pinned", lambda: False)
    topo = _two_verify_topology(1, 1)  # the default: both on ordinal 0
    with pytest.raises(ValueError, match="one process at a time"):
        topo.build()
    assert topo.wksp is None  # refused before anything was allocated
    # disjoint ordinals are each tile's own
    _two_verify_topology([0], [1])._check_device_owners()


def test_virtual_devices_may_be_shared_between_processes():
    assert hostdev.cpu_pinned()  # conftest pins the CPU platform
    _two_verify_topology(1, 1)._check_device_owners()


def test_store_tile_recovery_needs_no_device(monkeypatch):
    """The store tile's FEC resolver recovers one set at a time.  That
    must not dispatch to the device: under the process runtime the
    store tile's process cannot get the chip (the verify tile's owns
    it), and before PR 22 `recover` had no host path at all."""
    import numpy as np

    from firedancer_tpu.ops import reedsol as RS

    def no_device(*a):
        raise AssertionError("a one-set recovery went to the device")

    monkeypatch.setattr(RS, "_apply_bitmatrix", no_device)
    D, P, N = 6, 4, 64
    data = np.random.default_rng(3).integers(0, 256, (D, N)).astype(np.uint8)
    shreds = np.concatenate([data, RS.encode(data, P)])
    present = np.ones(D + P, bool)
    present[[1, 4, 7]] = False
    shreds[~present] = 0xAA
    assert (RS.recover(shreds, present, D) == data).all()


# ---- no silent choice of device -------------------------------------------


def test_configure_device_stage_is_not_ok_without_a_tpu():
    from firedancer_tpu.app import configure as CF

    (r,) = CF.run("check", ("device",))
    assert not r.ok and "no TPU backend" in r.detail


def test_configure_cache_stage_reports_the_one_location(monkeypatch):
    from firedancer_tpu.app import configure as CF

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    (r,) = CF.run("init", ("cache",))
    assert r.ok and hostdev.compilation_cache_dir() in r.detail


def test_graft_entry_is_the_verify_kernel():
    import __graft_entry__ as g

    fn, args = g.entry()
    assert [a.shape for a in args] == [(128, 64), (128,), (128, 64),
                                       (128, 32)]


def test_fdtctl_boot_log_addresses_under_each_runtime():
    """Under the process runtime the parent's tile copy never boots, so
    its socket properties do not exist: the boot log names the
    configured ports instead of crashing after a successful start."""
    from firedancer_tpu.app import config as C
    from firedancer_tpu.app.fdtctl import _wire_addrs
    from firedancer_tpu.disco import Topology

    cfg = C.parse("[tiles.quic]\nquic_port = 9001\nudp_port = 9002\n")

    class Booted:
        quic_addr, udp_addr = ("127.0.0.1", 41000), ("127.0.0.1", 41001)

    assert _wire_addrs(Topology(runtime="thread"), Booted, cfg) == (
        Booted.quic_addr, Booted.udp_addr
    )
    assert _wire_addrs(Topology(runtime="process"), object(), cfg) == (
        ("0.0.0.0", 9001), ("0.0.0.0", 9002)
    )
