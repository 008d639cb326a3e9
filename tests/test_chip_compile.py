"""Compile the main path's device programs for the real chip, without it.

The TPU's compiler is installed here and compiles for a chip that is
DESCRIBED, not attached (on-chip-measurement guide, section 2).  That
shows what interpret mode and the CPU backend cannot: a Mosaic kernel the
chip's compiler refuses (tiling, VMEM), a program that does not fit the
device's memory.  Every other test that touches a device kernel is in the
slow tier and runs on the CPU; these are what guard the chip path in
tier-1.  A compile that passes is not a chip run — `chip_smoke.py` is.

Rules this file keeps (same guide): the topology is described inside a
module-scoped fixture that skips when it cannot be — never at import, in
a skipif, in parametrize or in conftest; every compile runs in this
process; the persistent compilation cache is off around them (an entry
compiled for a described chip is written but cannot be read back without
the chip, so the next run would warn and compile anyway).
"""

import os

import numpy as np
import pytest

#: one v5e chip's HBM (Google Cloud documentation, "TPU v5e")
HBM_BYTES = 16 * 1000**3


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means: no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes, **static):
    import jax

    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    return fn.lower(*args, **static).compile()


def _assert_fits_and_has_mosaic(compiled):
    assert compiled.as_text().count("tpu_custom_call") >= 1, (
        "the compiled program holds no Mosaic call: the Pallas verify "
        "kernel did not make it into the program"
    )
    m = compiled.memory_analysis()
    need = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes + m.generated_code_size_in_bytes
    )
    assert need < HBM_BYTES, f"needs {need} bytes of a {HBM_BYTES}-byte chip"


def test_verify_tile_program_compiles_for_v5e(one_chip):
    """`_verify_digest_impl(use_pallas=True)` — the verify tile's program
    — at the config's default max_lanes, the batch's lane count its fourth
    operand (it bounds the kernel's grid: a traced bound Mosaic must take)."""
    from firedancer_tpu.app import config as C
    from firedancer_tpu.ops.ed25519 import verify as fver

    lanes = C.parse("").verify_max_lanes
    compiled = _compile(
        fver._verify_digest_impl, one_chip,
        ((lanes, 64), np.uint8), ((lanes, 64), np.uint8),
        ((lanes, 32), np.uint8), ((), np.int32), use_pallas=True,
    )
    _assert_fits_and_has_mosaic(compiled)
    # the lane count is an operand of the ONE kernel: no second Mosaic
    # call for the batches that stop short of the last tile
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_verify_message_entry_compiles_for_v5e(one_chip):
    """`_verify_impl(use_pallas=True)` — the device SHA-512 entry — at a
    modest lane count and the config's default message width."""
    from firedancer_tpu.app import config as C
    from firedancer_tpu.ops.ed25519 import verify as fver

    lanes, width = 1024, C.parse("").verify_msg_width
    compiled = _compile(
        fver._verify_impl, one_chip,
        ((lanes, width), np.uint8), ((lanes,), np.int32),
        ((lanes, 64), np.uint8), ((lanes, 32), np.uint8),
        msg_len=width, use_pallas=True,
    )
    _assert_fits_and_has_mosaic(compiled)


def test_pallas_program_bytes_do_not_depend_on_the_call_stack(one_chip):
    """A Pallas kernel is serialized into its program WITH its MLIR
    locations.  With JAX's default (full Python tracebacks in every
    location) the same kernel traced from two call stacks has different
    bytes, so a different persistent-cache key: found on the chip, where
    a leader boot recompiled the verify program that chip_smoke's kernel
    phase had cached a minute earlier.  hostdev.enable_compilation_cache
    keeps one frame per location; this pins that it is enough."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from firedancer_tpu.utils.hostdev import enable_compilation_cache

    enable_compilation_cache()  # as every entry point does (idempotent)

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 3 + 1

    def lowered_text():
        f = jax.jit(lambda x: pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x))
        x = jax.ShapeDtypeStruct((8, 128), jnp.int32, sharding=one_chip)
        return f.lower(x).as_text()

    def from_another_stack():
        return (lambda: lowered_text())()

    a = lowered_text()
    b = from_another_stack()
    assert "tpu_custom_call" in a
    assert a == b
