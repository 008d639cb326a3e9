"""Multi-chip dry run: the verify tile's device pool on virtual CPU devices.

Run by the driver with XLA_FLAGS=--xla_force_host_platform_device_count=N to
show that the multi-device path compiles and lands batches in order without
real chips.
"""

from __future__ import annotations

import numpy as np


def run_verify_pool(n_devices: int, lanes: int = 16) -> None:
    """Dry-run the verify tile's DEVICE POOL across the first n local
    devices:
    one pinned executable per device (ops.ed25519.verify_batch_digest_on),
    a `_DevicePool` of per-device `DevicePolicy` fault domains, 2x
    batches submitted through the least-in-flight scheduler, and the
    in-order landing asserted.  This is the production multi-device
    scale-out path (tiles/verify.py) compiled and executed without real
    chips."""
    import hashlib
    import time

    import jax

    from firedancer_tpu.ops.ed25519 import hostpath
    from firedancer_tpu.ops.ed25519 import verify as fver
    from firedancer_tpu.tiles.verify import DevicePolicy, _DevicePool

    devs = jax.local_devices()[:n_devices]
    rng = np.random.default_rng(2)
    sk = rng.integers(0, 256, 32, np.uint8).tobytes()
    pk = hostpath.public_from_secret(sk)
    digests = np.zeros((lanes, 64), np.uint8)
    sigs = np.zeros((lanes, 64), np.uint8)
    pubs = np.tile(np.frombuffer(pk, np.uint8), (lanes, 1))
    for i in range(lanes):
        msg = rng.integers(0, 256, 32, np.uint8).tobytes()
        sig = hostpath.sign(sk, msg)
        sigs[i] = np.frombuffer(sig, np.uint8)
        digests[i] = np.frombuffer(
            hashlib.sha512(sig[:32] + pk + msg).digest(), np.uint8
        )
    fns = [fver.verify_batch_digest_on(d) for d in devs]
    for fn in fns:
        # warm each device's compile BEFORE the pool boots, exactly as
        # the verify tile does (_make_device_fns): a cold compile
        # (~95 s here, concurrent on one core) inside a worker's first
        # dispatch would outlast the 120 s per-device stall patience —
        # the watchdog would quarantine every "stalled" device and pile
        # all batches on whichever recovers first
        np.asarray(fn(digests, sigs, pubs))
    policies = [
        DevicePolicy(fn, hostpath.verify_batch_digest_host, index=i)
        for i, fn in enumerate(fns)
    ]
    pool = _DevicePool(policies, depth=2, name="dryrun")
    try:
        n_batches = 2 * len(devs)
        submitted = 0
        landed = []
        deadline = time.monotonic() + 600.0
        while len(landed) < n_batches and time.monotonic() < deadline:
            while submitted < n_batches and pool.submit(
                {"lanes": lanes, "i": submitted}, (digests, sigs, pubs)
            ):
                submitted += 1
            pool.poll()
            while pool.ready:
                meta, ok = pool.ready.popleft()
                assert ok[:lanes].all(), "pool verify rejected valid sigs"
                landed.append(meta)
            time.sleep(0.001)
        assert [m["i"] for m in landed] == list(range(n_batches)), (
            "pool landing out of order or incomplete"
        )
        used = sum(1 for w in pool.workers if w.landed_n > 0)
        assert used >= min(2, len(devs)), "pool did not spread work"
        print(
            f"dryrun_verify_pool ok: {n_batches} batches in order over "
            f"{used}/{len(devs)} devices"
        )
    finally:
        pool.stop(timeout_s=30.0)


def run(n_devices: int) -> None:
    """Pin n virtual CPU devices, then drive the verify pool on two of
    them (one, given one): the multi-device path `leader4.paced`
    measures.  Two, not n, because each pinned placement is its own cold
    compile (~95 s on the CPU host)."""
    # must run before any jax backend init in this process (see
    # utils/hostdev.py for the platform-pinning rationale)
    from firedancer_tpu.utils.hostdev import ensure_cpu_devices

    ensure_cpu_devices(n_devices)
    import jax

    assert len(jax.devices()) >= n_devices, (
        f"need {n_devices} devices, have {len(jax.devices())}; "
        "set --xla_force_host_platform_device_count"
    )
    run_verify_pool(min(n_devices, 2))
    print("dryrun_multichip ok")
