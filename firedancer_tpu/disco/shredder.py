"""Shredder: entry batches → merkle data+parity shreds (FEC sets).

Behavior contract: src/disco/shred/fd_shredder.{h,c} —
  * an entry batch splits into FEC sets of up to 31200 payload bytes
    (the tail set absorbs the remainder; a set is never smaller than
    half-normal unless the batch is)
  * data shred count ceil-divides the chunk by the per-shred payload;
    parity count comes from the data_to_parity table (32:32 for full
    sets); payload size is 1115 - 20*tree_depth bytes
  * Reed-Solomon runs over each data shred's bytes [0x40, 0x58+payload)
    (everything after the signature), producing the parity payloads
  * every shred's merkle leaf hashes prefix || bytes [0x40, end of its
    RS-covered region); the 20-byte-node tree's root is signed by the
    leader and the per-leaf proof is appended to each shred
  * data shred flags: reference tick, DATA_COMPLETE on the batch's last
    shred, SLOT_COMPLETE when the block ends

TPU-native notes: parity generation is the MXU bit-matmul
(ops/reedsol.encode) over the whole set at once, and leaf hashing is one
batched SHA-256 dispatch (ballet/bmtree) per layer.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from firedancer_tpu.ballet import bmtree as BM
from firedancer_tpu.ballet import shred as SH
from firedancer_tpu.ops import reedsol as RS

NORMAL_FEC_SET_PAYLOAD_SZ = 31200
#: the most ONE 32:32 set carries: 32 data shreds of 995 payload bytes
#: (tree depth 6).  The reference gives a batch's tail of up to twice the
#: normal size one odd-sized set of up to 64:64; this build's sets (and the
#: shred tile's pending store, PD_MAX) stop at 32:32, so a tail above this
#: goes out as a normal set and a last, smaller one
MAX_FEC_SET_PAYLOAD_SZ = 32 * 995

DATA_TO_PARITY = [
    0, 17, 18, 19, 19, 20, 21, 21,
    22, 23, 23, 24, 24, 25, 25, 26,
    26, 26, 27, 27, 28, 28, 29, 29,
    29, 30, 30, 31, 31, 31, 32, 32, 32,
]


def tree_depth_for(leaf_cnt: int) -> int:
    """Non-root layer count (fd_bmtree_depth(leaves) - 1)."""
    if leaf_cnt <= 1:
        return max(leaf_cnt - 1, 0)
    return (leaf_cnt - 1).bit_length()


def count_data_shreds(chunk: int) -> int:
    if chunk <= 9135:
        return max(1, (chunk + 1014) // 1015)
    return (chunk + 994) // 995


def count_parity_shreds(chunk: int) -> int:
    return DATA_TO_PARITY[count_data_shreds(chunk)]


@dataclass(frozen=True)
class EntryBatchMeta:
    parent_offset: int = 1
    reference_tick: int = 0
    block_complete: bool = False


@dataclass
class FecSet:
    data_shreds: list[bytes]
    parity_shreds: list[bytes]
    merkle_root: bytes
    signature: bytes


def _default_signer(root: bytes) -> bytes:
    return b"\0" * 64


class Shredder:
    """Stateful across batches of a slot (shred index offsets)."""

    def __init__(self, shred_version: int, signer=None):
        self.shred_version = shred_version
        self.signer = signer or _default_signer
        self.slot = None
        self.data_idx = 0
        self.parity_idx = 0

    def start_slot(self, slot: int) -> None:
        self.slot = slot
        self.data_idx = 0
        self.parity_idx = 0

    def shred_batch(self, entry_batch: bytes, meta: EntryBatchMeta) -> list[FecSet]:
        assert self.slot is not None, "start_slot first"
        assert entry_batch
        out = []
        offset = 0
        total = len(entry_batch)
        while offset < total:
            remaining = total - offset
            chunk = (
                NORMAL_FEC_SET_PAYLOAD_SZ
                if remaining > MAX_FEC_SET_PAYLOAD_SZ
                else remaining
            )
            fec, consumed = self._build_fec_set(
                entry_batch, offset, chunk, total, meta
            )
            out.append(fec)
            offset += consumed
        return out

    def _build_fec_set(
        self, batch: bytes, offset: int, chunk: int, total: int,
        meta: EntryBatchMeta,
    ) -> tuple[FecSet, int]:
        d_cnt = count_data_shreds(chunk)
        p_cnt = count_parity_shreds(chunk)
        depth = tree_depth_for(d_cnt + p_cnt)
        data_payload_sz = 1115 - 20 * depth
        parity_payload_sz = data_payload_sz + SH.DATA_HEADER_SZ - 0x40
        proof_sz = depth * SH.MERKLE_NODE_SZ

        last_in_batch = offset + chunk == total
        flags_last = (
            (SH.FLAG_SLOT_COMPLETE if (last_in_batch and meta.block_complete) else 0)
            | (SH.FLAG_DATA_COMPLETE if last_in_batch else 0)
        )

        # ---- data shreds (unsigned, no proof yet) ----
        data_bufs = []
        consumed = 0
        for i in range(d_cnt):
            payload_sz = min(chunk - consumed, data_payload_sz)
            payload = batch[offset + consumed : offset + consumed + payload_sz]
            consumed += payload_sz
            flags = (
                (flags_last if i == d_cnt - 1 else 0)
                | (meta.reference_tick & SH.REF_TICK_MASK)
            )
            buf = bytearray(SH.MIN_SZ)
            buf[0x40] = SH.TYPE_MERKLE_DATA | depth
            import struct

            struct.pack_into(
                "<QIHI", buf, 0x41,
                self.slot, self.data_idx + i, self.shred_version, self.data_idx,
            )
            struct.pack_into(
                "<HBH", buf, 0x53,
                meta.parent_offset, flags, SH.DATA_HEADER_SZ + payload_sz,
            )
            buf[SH.DATA_HEADER_SZ : SH.DATA_HEADER_SZ + payload_sz] = payload
            data_bufs.append(buf)

        # ---- parity payloads: RS over data bytes [0x40, 0x40+cov) ----
        cov = parity_payload_sz
        data_mat = np.zeros((d_cnt, cov), np.uint8)
        for i, buf in enumerate(data_bufs):
            data_mat[i] = np.frombuffer(bytes(buf[0x40 : 0x40 + cov]), np.uint8)
        parity_mat = RS.encode(data_mat, p_cnt)

        parity_bufs = []
        for j in range(p_cnt):
            buf = bytearray(SH.MAX_SZ)
            buf[0x40] = SH.TYPE_MERKLE_CODE | depth
            import struct

            struct.pack_into(
                "<QIHI", buf, 0x41,
                self.slot, self.parity_idx + j, self.shred_version,
                self.parity_idx,
            )
            struct.pack_into("<HHH", buf, 0x53, d_cnt, p_cnt, j)
            buf[SH.CODE_HEADER_SZ : SH.CODE_HEADER_SZ + cov] = parity_mat[j].tobytes()
            parity_bufs.append(buf)

        # ---- merkle tree over all shreds' covered regions ----
        # data leaves cover [0x40, 0x58+payload) = cov bytes; parity
        # leaves additionally cover their own code header:
        # [0x40, 0x59+cov) (fd_shredder.c data/parity_merkle_sz)
        leaves = [bytes(b[0x40 : 0x40 + cov]) for b in data_bufs] + [
            bytes(b[0x40 : SH.CODE_HEADER_SZ + cov]) for b in parity_bufs
        ]
        layers = BM.layers_of(leaves, 20)
        root = bytes(layers[-1][0])
        sig = self.signer(root)

        # ---- write signature + proofs ----
        def proof_for(idx: int) -> bytes:
            nodes = []
            k = idx
            for layer in layers[:-1]:
                sib = k ^ 1
                nodes.append(
                    bytes(layer[sib]) if sib < len(layer) else bytes(layer[k])
                )
                k >>= 1
            return b"".join(nodes)

        for i, buf in enumerate(data_bufs):
            buf[0:0x40] = sig
            buf[SH.MIN_SZ - proof_sz : SH.MIN_SZ] = proof_for(i)
        for j, buf in enumerate(parity_bufs):
            buf[0:0x40] = sig
            buf[SH.MAX_SZ - proof_sz : SH.MAX_SZ] = proof_for(d_cnt + j)

        self.data_idx += d_cnt
        self.parity_idx += p_cnt
        return (
            FecSet(
                [bytes(b) for b in data_bufs],
                [bytes(b) for b in parity_bufs],
                root,
                sig,
            ),
            consumed,
        )
