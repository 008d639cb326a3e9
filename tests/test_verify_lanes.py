"""The batch's real lane count rides to the device (tier-1).

With the tile's stub device fn (JAX-free, `test_verify.py`'s rig): the count
the device fn receives, the `kernel_lanes` and `device_tiles` counters, the
worker's spans, a three-argument stub, the host fallback.  With the tile's own device fn on the plain XLA path (what a
CPU run picks; one compile, shared by the module): the count picks no
program, and rows at or past it read False.  Kept apart from
`test_verify.py`, whose tier-1 tests initialise no JAX backend.
"""

import numpy as np
import pytest

from firedancer_tpu.ops.ed25519 import golden
from firedancer_tpu.tiles import verify as VT

from test_verify import _Rig, _admit_all, rig_factory  # noqa: F401

@pytest.mark.parametrize("lanes", [1, 255, 256, 257, 4095, 4096])
def test_the_device_fn_is_told_the_batchs_real_lanes(rig_factory, lanes):
    """Beside the three arrays padded to the compiled shape (`max_lanes`,
    whatever the batch holds: the tile has no other) the device fn gets
    the real lane count, as the int32 array the boot-time warm-up sends,
    and `kernel_lanes` grows by the whole kernel tiles it fills — on both
    sides of each 256-lane tile edge."""
    seen = []

    def dev(digests, sigs, pubs, n_lanes):
        seen.append((len(digests), n_lanes))
        return np.ones(len(digests), bool)

    rig = rig_factory(device_fn=dev, max_lanes=4096).boot()
    before = rig.counters()
    for at in range(0, lanes, 256):
        rig.burst(min(256, lanes - at))
    rig.settle(1)
    ((padded, n),) = seen
    assert padded == 4096
    assert isinstance(n, np.ndarray) and (n.dtype, n.shape) == (np.int32, ())
    assert int(n) == lanes == rig.published[0]["lanes"]
    after = rig.counters()
    tiles = -(-lanes // VT.KERNEL_TILE)
    assert VT.KERNEL_TILE == 256 and tiles == {
        1: 1, 255: 1, 256: 1, 257: 2, 4095: 16, 4096: 16}[lanes]
    assert after["kernel_lanes"] - before["kernel_lanes"] == 256 * tiles
    assert after["device_tiles"] - before["device_tiles"] == tiles
    assert after["verified_sigs"] - before["verified_sigs"] == lanes
    assert after["fallback_batches"] == after["device_errors"] == 0


def test_a_device_fn_written_for_three_arrays_is_given_three(rig_factory):
    """A stub that takes the padded arrays alone (every test rig written
    before the count) is not a failing device: no error, no fallback."""
    rig = rig_factory(device_fn=_admit_all).boot()
    rig.burst(3)
    rig.settle(1)
    c = rig.counters()
    assert c["device_errors"] == c["fallback_batches"] == 0
    assert (c["verified_sigs"], c["kernel_lanes"]) == (3, 256)
    assert c["device_tiles"] == 1


def test_the_host_fallback_lands_a_batch_that_carries_the_count(rig_factory):
    """The count is the fourth of a batch's args; the host verifier takes
    the three arrays and the lanes by name, and still serves the batch a
    lost device leaves."""
    def lost(digests, sigs, pubs, n_lanes):
        raise RuntimeError("device lost")

    rig = rig_factory(device_fn=lost).boot()
    rig.burst(3)
    rig.settle(1)
    c = rig.counters()
    assert (c["device_errors"], c["fallback_batches"]) == (1, 1)
    # the host served it: the kernel's lanes are counted, no device tile
    assert (c["kernel_lanes"], c["device_tiles"]) == (256, 0)
    # the pool's txns are signed: the strict host path admits all three
    assert c["verified_sigs"] == c["out_frags"] == 3
    assert c["verify_fail_txns"] == 0


def _n_signer_rows(n_txns: int, n_signers: int):
    """Unsigned N-signer transfers as the tile's rows, each with a first
    signature (and so a dedup tag) of its own: N lanes a txn."""
    from firedancer_tpu.ballet import txn as T
    from firedancer_tpu.tiles import wire

    rows = np.zeros((n_txns, wire.LINK_MTU), np.uint8)
    szs = np.zeros(n_txns, np.uint16)
    for i in range(n_txns):
        keys = [bytes([i, j]) + bytes(30) for j in range(n_signers + 2)]
        body = T.build(
            [bytes([i + 1]) + bytes(63)] + [bytes(64)] * (n_signers - 1),
            keys, bytes(32), [(n_signers + 1, [0, n_signers], bytes(12))],
            readonly_signed_cnt=n_signers - 1, readonly_unsigned_cnt=1)
        full = wire.append_trailer(body, T.parse(body))
        rows[i, :len(full)] = np.frombuffer(full, np.uint8)
        szs[i] = len(full)
    return rows, szs


@pytest.mark.parametrize("n_txns,device,tiles", [
    (100, "served", 2),   # 300 lanes: two kernel tiles, on the device
    (4, "lost", 0),       # 12 lanes asked of a lost device: the host's
])
def test_the_worker_spans_carry_txns_and_the_device_tiles(
        rig_factory, n_txns, device, tiles):
    """Three signatures a txn: the worker's `fdt.verify.dispatch` and
    `fdt.verify.land` spans say the batch's txns beside its lanes, and
    the kernel tiles the device computed, which `device_tiles` counts;
    a batch the host path served computed none."""
    built = []

    class Span:
        def __init__(self, name, **kw):
            built.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    def served(digests, sigs, pubs, n_lanes):
        return np.ones(len(digests), bool)

    def lost(digests, sigs, pubs, n_lanes):
        raise RuntimeError("device lost")

    rig = rig_factory(device_fn=served if device == "served" else lost,
                      max_lanes=4096)
    rig.rows, rig.szs = _n_signer_rows(n_txns, 3)
    rig.tile._span = Span            # as _make_device_fns binds jax's
    rig.boot()
    rig.burst(n_txns)
    rig.settle(1)
    lanes = 3 * n_txns
    asked = -(-lanes // VT.KERNEL_TILE)
    spans = dict(built)
    assert spans["fdt.verify.dispatch"] == dict(
        seq=0, lanes=lanes, txns=n_txns, tiles=asked, dev=0)
    assert spans["fdt.verify.land"] == dict(
        seq=0, lanes=lanes, txns=n_txns, tiles=tiles, dev=0)
    c = rig.counters()
    assert (c["device_batches"], c["verified_sigs"]) == (1, lanes)
    assert c["kernel_lanes"] == asked * VT.KERNEL_TILE
    assert c["device_tiles"] == tiles
    assert c["fallback_batches"] == (device == "lost")


@pytest.fixture(scope="module")
def xla_rig():
    """A tile with its own device fn on the plain XLA path (what a CPU
    run picks), booted once: the boot compiles and warms the program."""
    rig = _Rig(device_fn=None)
    rig.boot()
    yield rig
    rig.close()


def test_the_lane_count_picks_no_program(xla_rig):
    """The traced-operand guarantee: after the warm-up and three batches
    of different lane counts the tile holds the programs it held after
    the warm-up (the benchmark's `device_programs` / `compiles_in_window`
    checks, here on the XLA path), and the device served every batch."""
    rig, tile = xla_rig, xla_rig.tile
    warm = tile._program_count()
    assert warm == 1
    for i, n in enumerate((1, 3, 8)):
        rig.burst(n)
        rig.settle(i + 1)
    c = rig.counters()
    assert tile._program_count() == c["device_programs"] == warm
    assert c["device_errors"] == c["fallback_batches"] == 0
    assert c["device_batches"] == 3
    # signed txns, really verified: all twelve leave
    assert c["verified_sigs"] == c["out_frags"] == 12
    assert c["kernel_lanes"] == 3 * 256 == c["device_tiles"] * 256


def test_rows_at_or_past_the_count_read_false(xla_rig):
    """verify_batch_digest(d, s, p, n) through the tile's jitted fn: rows
    before n read what they read with every row counted (and what the
    strict host path says); rows at or past n read False, valid
    signatures among them included."""
    import hashlib

    from firedancer_tpu.ops.ed25519 import hostpath

    fn = xla_rig.tile._fns[0]
    rng = np.random.default_rng(5)
    b = xla_rig.tile.max_lanes
    sk = rng.integers(0, 256, 32, np.uint8).tobytes()
    pk = golden.public_from_secret(sk)
    sigs = np.zeros((b, 64), np.uint8)
    digests = np.zeros((b, 64), np.uint8)
    pubs = np.tile(np.frombuffer(pk, np.uint8), (b, 1))
    for i in range(b):
        m = rng.integers(0, 256, 40, np.uint8).tobytes()
        s = golden.sign(sk, m)
        sigs[i] = np.frombuffer(s, np.uint8)
        digests[i] = np.frombuffer(
            hashlib.sha512(s[:32] + pk + m).digest(), np.uint8)
    sigs[2, 5] ^= 1
    every = np.asarray(fn(digests, sigs, pubs, np.asarray(b, np.int32)))
    assert every.tolist() == [i != 2 for i in range(b)]
    assert (every == hostpath.verify_batch_digest_host(
        digests, sigs, pubs)).all()
    for n in (0, 1, 3, b - 1):
        got = np.asarray(fn(digests, sigs, pubs, np.asarray(n, np.int32)))
        assert (got[:n] == every[:n]).all() and not got[n:].any(), (n, got)
    assert xla_rig.tile._program_count() == 1
