"""Device selection and start-up: which platform this process runs on,
how many accelerators an "auto" spec means, and where compiled programs
are cached.

An accelerator belongs to ONE process at a time: the first process that
initialises a JAX backend owns the chip until it exits, and a second
one that needs it fails or hangs.  So the rules here are about who may
touch the backend.  A topology parent under the process runtime never
does (the verify tile child owns the chip); `local_device_count` takes
the inventory in a child that exits; tests and sharding dry runs pin a
virtual CPU mesh through `ensure_cpu_devices` before anything else.
"""

from __future__ import annotations

import os
import re
import subprocess
import sys

#: compile cache of a checkout with no JAX_COMPILATION_CACHE_DIR: a
#: FIXED path inside the checkout (a directory that moves never hits),
#: git-ignored
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def ensure_cpu_devices(n: int) -> None:
    """Pin the CPU platform with >= n virtual devices.

    Must be called before any jax backend initialization (jax.devices(),
    jit execution, ...); afterwards the platform and device count are
    frozen and the caller's own device-count assert is what catches it.
    The pin goes into the environment too, so spawned tile children and
    the `local_device_count` probe see the same platform.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    elif int(m.group(1)) < n:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"--xla_force_host_platform_device_count={n}"
        )
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    enable_compilation_cache()


def backend_initialized() -> bool:
    """True once THIS process has initialised a JAX backend (and so, on
    an accelerator host, owns the chip).  Never initialises one."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def cpu_pinned() -> bool:
    """True when JAX is pinned to the CPU platform (tests, rehearsals):
    its devices are virtual, so several processes may share an ordinal.
    Reads the pin only — never initialises a backend."""
    if "jax" in sys.modules:
        import jax

        pin = jax.config.jax_platforms
    else:
        pin = os.environ.get("JAX_PLATFORMS")
    return (pin or "").strip().lower() == "cpu"


def local_device_count() -> int:
    """Local accelerator inventory for "auto" device specs (the verify
    pool, disco.topo.device_assignments).

    The count is taken WITHOUT making this process the chip's owner: a
    short-lived child initialises the backend, prints the count and
    exits, so a topology parent under the process runtime stays off the
    backend and its verify child finds the chip free.  A process that
    already owns a backend counts in place (a child could not get the
    chip from it).  Any JAX failure raises: the caller asked for the
    real inventory, and a guessed 1 would hide a missing accelerator.
    """
    if backend_initialized():
        import jax

        return len(jax.local_devices())
    r = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(len(jax.local_devices()))"],
        capture_output=True, text=True, timeout=600,
    )
    if r.returncode != 0:
        raise RuntimeError(
            "devices = \"auto\": the device-count probe failed "
            f"(exit {r.returncode}):\n{r.stderr[-2000:]}"
        )
    return int(r.stdout.split()[-1])


def compilation_cache_dir() -> str:
    """Where compiled programs persist: JAX_COMPILATION_CACHE_DIR when
    the environment sets it, else the checkout's fixed `.jax_cache`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    THE one place the cache location is decided — fdtctl run, JAX-using
    tile children, bench.py, chip_smoke.py and tests/conftest.py all
    come through here.  With JAX_COMPILATION_CACHE_DIR set, JAX reads
    the directory itself and this code sets none; otherwise it is the
    checkout's `.jax_cache`.  Never the home directory, a temp name or a
    per-process path: a cold verify-kernel compile costs about a minute
    and only a stable directory is ever hit again.  Idempotent; touches
    jax.config only, never a backend."""
    import jax

    path = compilation_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # A Pallas kernel is serialized INTO its program together with its
    # MLIR locations, and by default a location carries the Python call
    # stack the kernel was traced from.  The verify program traced from
    # fdtctl, from a tile's thread, from a tile's child process or from
    # a test then differs in its bytes, gets a different cache key, and
    # never hits (found on the chip: a leader boot recompiled the
    # program chip_smoke's kernel phase had just cached).  One frame
    # per location — file and line of the op itself — is the same from
    # every entry point.
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    return path
