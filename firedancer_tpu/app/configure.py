"""System-setup stages: `fdtctl configure` (check / init).

Reference model: src/app/fdctl/configure/ — an ordered list of idempotent
stages (hugepages, shmem mounts, sysctl, XDP install, workspace creation)
each exposing check/init so operators can verify or fix the host before
`run`.  The TPU host's needs differ (no hugetlbfs/XDP requirements), so
the stages here are the ones this runtime actually depends on: /dev/shm
capacity for workspaces, file-descriptor headroom, the XLA compilation
cache, accelerator visibility, and an identity keypair.
"""

from __future__ import annotations

import os
import resource
from dataclasses import dataclass

#: ulimit target: topologies open sockets + shm maps + log files
NOFILE_TARGET = 4096
#: workspaces allocate up to a few GiB of /dev/shm at production depths
SHM_MIN_BYTES = 1 << 30


@dataclass
class StageResult:
    name: str
    ok: bool
    detail: str


def _stage_shm(fix: bool) -> StageResult:
    try:
        st = os.statvfs("/dev/shm")
    except OSError as e:
        return StageResult("shm", False, f"/dev/shm unavailable: {e}")
    avail = st.f_bavail * st.f_frsize
    ok = avail >= SHM_MIN_BYTES
    return StageResult(
        "shm", ok,
        f"/dev/shm available {avail >> 20} MiB"
        + ("" if ok else f" (< {SHM_MIN_BYTES >> 20} MiB)"),
    )


def _stage_ulimit(fix: bool) -> StageResult:
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft >= NOFILE_TARGET:
        return StageResult("ulimit", True, f"nofile {soft}")
    if fix:
        try:
            want = min(NOFILE_TARGET, hard) if hard > 0 else NOFILE_TARGET
            resource.setrlimit(resource.RLIMIT_NOFILE, (want, hard))
            return StageResult("ulimit", True, f"nofile raised to {want}")
        except (ValueError, OSError) as e:
            return StageResult("ulimit", False, f"raise failed: {e}")
    return StageResult(
        "ulimit", False, f"nofile {soft} < {NOFILE_TARGET} (init raises)"
    )


def _stage_cache(fix: bool) -> StageResult:
    # the location is hostdev's decision alone (JAX_COMPILATION_CACHE_DIR,
    # else the checkout's fixed directory); this stage only reports it
    from firedancer_tpu.utils.hostdev import compilation_cache_dir

    cache_dir = compilation_cache_dir()
    if os.path.isdir(cache_dir):
        n = len(os.listdir(cache_dir))
        return StageResult("cache", True, f"{cache_dir} ({n} entries)")
    if fix:
        os.makedirs(cache_dir, exist_ok=True)
        return StageResult("cache", True, f"created {cache_dir}")
    return StageResult("cache", False, f"{cache_dir} missing (init creates)")


def _stage_device(fix: bool) -> StageResult:
    """ok only for a TPU backend: a CPU backend runs the tests, not the
    product, and reporting it ok is how a missing accelerator stays
    unnoticed until the verify tile is found on its host fallback.
    NOTE: this initialises the backend, so it takes the chip for the
    life of the configure process — run it before `fdtctl run`, not
    beside it."""
    try:
        import jax

        devs = jax.devices()
        backend = jax.default_backend()
    except Exception as e:  # noqa: BLE001 — report, don't crash configure
        return StageResult("device", False, f"jax backend failed: {e}")
    detail = f"{backend}: " + ", ".join(str(d) for d in devs[:4])
    if backend != "tpu":
        return StageResult("device", False, detail + " (no TPU backend)")
    return StageResult("device", True, detail)


def _stage_keys(fix: bool, keyfile: str | None = None) -> StageResult:
    path = keyfile or os.path.expanduser("~/.fdt/identity.key")
    if os.path.exists(path):
        return StageResult("keys", True, path)
    if fix:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd = os.open(path, os.O_CREAT | os.O_WRONLY, 0o600)
        os.write(fd, os.urandom(32))
        os.close(fd)
        return StageResult("keys", True, f"generated {path}")
    return StageResult("keys", False, f"{path} missing (init generates)")


STAGES = ("shm", "ulimit", "cache", "device", "keys")


def run(
    mode: str = "check",
    stages: tuple[str, ...] = STAGES,
    keyfile: str | None = None,
) -> list[StageResult]:
    """mode 'check' reports; 'init' fixes what it can (idempotent)."""
    fix = mode == "init"
    fns = {
        "shm": _stage_shm,
        "ulimit": _stage_ulimit,
        "cache": _stage_cache,
        "device": _stage_device,
        "keys": lambda f: _stage_keys(f, keyfile),
    }
    return [fns[s](fix) for s in stages if s in fns]
