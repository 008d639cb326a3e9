"""Reed-Solomon shred coding on the MXU.

The reference's hot erasure-coding path (src/ballet/reedsol/ — AVX2/GFNI
kernels, ~38k LoC of generated butterflies) reformulated for TPU:

GF(2^8) matrix application is GF(2)-LINEAR in the bits.  Expanding each
field constant to its 8x8 GF(2) multiply matrix (ballet/gf256.expand_bits)
turns "parity = M · data over GF(2^8)" into ONE binary matrix product

    parity_bits (8P, N) = B (8P, 8D) @ data_bits (8D, N)   (mod 2)

over all N byte positions at once — a dense int8 matmul with int32
accumulation, exactly what the MXU does natively, replacing per-byte
table lookups (which TPUs hate) with systolic-array work.  A full 32:32
shred set is a (256, 256) @ (256, shred_sz·batch) matmul.

Recovery inverts the surviving rows' matrix on the host (tiny, GF(2^8))
and reuses the same device matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from firedancer_tpu.ballet import gf256 as GF

DATA_SHREDS_MAX = 67  # FD_REEDSOL_DATA_SHREDS_MAX
PARITY_SHREDS_MAX = 67


@functools.lru_cache(maxsize=64)
def _parity_bits_matrix(data_cnt: int, parity_cnt: int) -> np.ndarray:
    return GF.expand_bits(GF.parity_matrix(data_cnt, parity_cnt))


def _unpack_bits(x: jnp.ndarray) -> jnp.ndarray:
    """(D, N) u8 -> (8D, N) int8 bits (bit i of row d at row 8d+i)."""
    D, N = x.shape
    xi = x.astype(jnp.int32)
    bits = [(xi >> i) & 1 for i in range(8)]
    return (
        jnp.stack(bits, axis=1).reshape(8 * D, N).astype(jnp.int8)
    )


def _pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """(8P, N) int -> (P, N) u8."""
    P8, N = bits.shape
    b = bits.reshape(P8 // 8, 8, N).astype(jnp.int32)
    out = jnp.zeros((P8 // 8, N), jnp.int32)
    for i in range(8):
        out = out | (b[:, i, :] << i)
    return out.astype(jnp.uint8)


@jax.jit
def _apply_bitmatrix(B: jnp.ndarray, data: jnp.ndarray) -> jnp.ndarray:
    """parity (P, N) u8 = unpack-matmul-mod2-pack of data (D, N) u8."""
    bits = _unpack_bits(data)
    acc = jax.lax.dot_general(
        B.astype(jnp.int8),
        bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return _pack_bits(acc & 1)


#: below this many data bytes a single encode runs on the HOST: one
#: FEC set's worth of work does not amortize a device dispatch, and a
#: dispatch from the shred tile would queue behind the verify kernel on
#: the same chip (under the process runtime it could not reach the chip
#: at all: the verify tile's process owns it).  The MXU path owns
#: batch/recovery scale.  The threshold is not measured on this
#: installation (ROADMAP D4).
HOST_MAX_BYTES = int(
    __import__("os").environ.get("FDT_RS_HOST_MAX", str(1 << 20))
)


def _apply_bitmatrix_host(B: np.ndarray, data: np.ndarray) -> np.ndarray:
    """Host twin of _apply_bitmatrix: identical math, numpy int ops."""
    D, N = data.shape
    P = B.shape[0] // 8
    xi = data.astype(np.int32)
    bits = np.stack(
        [(xi >> i) & 1 for i in range(8)], axis=1
    ).reshape(8 * D, N)
    acc = (B.astype(np.int32) @ bits) & 1                     # (8P, N)
    b = acc.reshape(P, 8, N)
    out = np.zeros((P, N), np.int32)
    for i in range(8):
        out |= b[:, i, :] << i
    return out.astype(np.uint8)


def encode(data: np.ndarray, parity_cnt: int,
           device: bool | None = None) -> np.ndarray:
    """data (D, N) u8 (D shreds of N bytes) -> parity (parity_cnt, N) u8.

    Reference semantics: fd_reedsol_encode_init/add/fini one-shot.
    device: None = auto by size (host under HOST_MAX_BYTES), True/False
    force the MXU / host path."""
    data_np = np.asarray(data, np.uint8)
    if device is None:
        device = data_np.size > HOST_MAX_BYTES
    if not device:
        return _apply_bitmatrix_host(
            _parity_bits_matrix(data_np.shape[0], parity_cnt), data_np
        )
    data = jnp.asarray(data_np, jnp.uint8)
    D = data.shape[0]
    B = jnp.asarray(_parity_bits_matrix(D, parity_cnt))
    return np.asarray(_apply_bitmatrix(B, data))


def recover(
    shreds: np.ndarray,
    present: np.ndarray,
    data_cnt: int,
    device: bool | None = None,
) -> np.ndarray | None:
    """Reconstruct the data shreds from any data_cnt surviving rows.

    shreds (total, N) u8 with garbage in missing rows; present (total,)
    bool.  Returns (data_cnt, N) u8 or None if fewer than data_cnt
    survive (FD_REEDSOL_ERR_PARTIAL).  device: as encode — None = auto
    by size, so the store tile's one-set recoveries stay on the host
    (its process never needs the chip, which under the process runtime
    it could not get: the verify tile's process owns it).
    """
    total = len(shreds)
    idx = np.flatnonzero(np.asarray(present))
    if len(idx) < data_cnt:
        return None
    idx = idx[:data_cnt]
    M = GF.code_matrix(data_cnt, total)
    sub = M[idx]  # (data_cnt, data_cnt): survivors = sub @ original data
    B = GF.expand_bits(GF.mat_inv(sub))
    surv = np.asarray(shreds, np.uint8)[idx]
    if device is None:
        device = surv.size > HOST_MAX_BYTES
    if not device:
        return _apply_bitmatrix_host(B, surv)
    return np.asarray(_apply_bitmatrix(jnp.asarray(B), jnp.asarray(surv)))
