"""Differential sBPF testing: the VM vs an independent mini-oracle.

Reference analog: the reference leans on solana-conformance fixtures and
differential fuzzing (fuzz_*_diff.c pattern: two implementations, same
inputs, byte-identical verdicts).  No external sBPF oracle ships in this
environment, so the oracle here is a SECOND, independently written
interpreter — a naive dict-driven big-int evaluator with none of the VM's
structure — run over thousands of randomly generated programs.

Round-4 corpus widening (round-3 review, item 5): memory ops over every
region (stack/heap/input, all widths, ST/STX/LDX), out-of-bounds
accesses (fault-class agreement), BACKWARD jumps via bounded counter
loops, lddw, and syscalls (memset/memcpy/memcmp/sha256) with the
documented CU cost contract.  Any divergence in result value, final
memory state hash, or fault class fails.
"""

import hashlib
import struct

import numpy as np
import pytest

from firedancer_tpu.ballet import sbpf
from firedancer_tpu.flamenco.vm import Vm, VmError

U64 = (1 << 64) - 1
U32 = (1 << 32) - 1

INPUT_SZ = 128
HEAP_SZ = 32 * 1024
STACK_SZ = 4096 * 64


def ins(op, dst=0, src=0, off=0, imm=0):
    return struct.pack("<BBhI", op, (src << 4) | dst, off, imm & 0xFFFFFFFF)


class Oracle:
    """Independent evaluator: big-int semantics from the sBPF spec text,
    a flat region list for memory, and the documented syscall cost
    contract — written without reference to flamenco/vm.py's structure."""

    STEP_LIMIT = 10_000

    def __init__(self, words, input_sz=INPUT_SZ, rodata=b""):
        self.words = words  # list of (op, dst, src, off, imm)
        self.input = bytearray(input_sz)
        self.heap = bytearray(HEAP_SZ)
        self.stack = bytearray(STACK_SZ)
        self.rodata = bytes(rodata)
        self.budget = self.STEP_LIMIT

    def _mem(self, addr, sz, write=False):
        from firedancer_tpu.ballet.sbpf import (
            MM_HEAP, MM_INPUT, MM_PROGRAM, MM_STACK,
        )

        for base, region, writable in (
            (MM_PROGRAM, self.rodata, False),
            (MM_INPUT, self.input, True), (MM_HEAP, self.heap, True),
            (MM_STACK, self.stack, True),
        ):
            rel = addr - base
            if 0 <= rel and rel + sz <= len(region):
                if write and not writable:
                    raise MemoryError("read-only")
                return region, rel
        raise MemoryError(hex(addr))

    def _load(self, addr, sz):
        region, rel = self._mem(addr, sz)
        return int.from_bytes(region[rel:rel + sz], "little")

    def _store(self, addr, sz, val):
        region, rel = self._mem(addr, sz, write=True)
        region[rel:rel + sz] = (val & ((1 << (8 * sz)) - 1)).to_bytes(
            sz, "little")

    def _charge(self, n):
        self.budget -= n
        if self.budget < 0:
            raise TimeoutError

    def _syscall(self, fnid, regs):
        self._charge(100)  # flat call cost contract
        r1, r2, r3, r4 = regs[1], regs[2], regs[3], regs[4]
        if fnid == sbpf.syscall_hash(b"sol_memset_"):
            self._charge(r3 // 250 + 1)
            if r3:
                region, rel = self._mem(r1, r3, write=True)
                region[rel:rel + r3] = bytes([r2 & 0xFF]) * r3
        elif fnid == sbpf.syscall_hash(b"sol_memcpy_"):
            self._charge(r3 // 250 + 1)
            if r3:
                sregion, srel = self._mem(r2, r3)
                data = bytes(sregion[srel:srel + r3])
                dregion, drel = self._mem(r1, r3, write=True)
                dregion[drel:drel + r3] = data
        elif fnid == sbpf.syscall_hash(b"sol_memcmp_"):
            self._charge(r3 // 250 + 1)
            a = b = b""
            if r3:
                ra, oa = self._mem(r1, r3)
                rb, ob = self._mem(r2, r3)
                a, b = bytes(ra[oa:oa + r3]), bytes(rb[ob:ob + r3])
            diff = 0
            for x, y in zip(a, b):
                if x != y:
                    diff = (x - y) & U32
                    break
            self._store(r4, 4, diff)
        elif fnid == sbpf.syscall_hash(b"sol_sha256"):
            self._charge(85)
            h = hashlib.sha256()
            for i in range(r2):
                addr = self._load(r1 + 16 * i, 8)
                ln = self._load(r1 + 16 * i + 8, 8)
                self._charge(ln // 100)
                if ln:
                    region, rel = self._mem(addr, ln)
                    h.update(bytes(region[rel:rel + ln]))
            region, rel = self._mem(r3, 32, write=True)
            region[rel:rel + 32] = h.digest()
        else:
            raise LookupError(hex(fnid))
        return 0

    def run(self):
        from firedancer_tpu.ballet.sbpf import MM_INPUT, MM_STACK
        from firedancer_tpu.flamenco.vm import STACK_FRAME_SZ

        regs = {i: 0 for i in range(11)}
        regs[1] = MM_INPUT
        regs[10] = MM_STACK + STACK_FRAME_SZ
        pc = 0
        while True:
            if not 0 <= pc < len(self.words):
                raise IndexError
            self._charge(1)
            op, dst, src, off, imm = self.words[pc]
            pc += 1
            if op == 0x95:
                return regs[0]
            if op == 0x18:  # lddw: next word's imm is the high half
                if pc >= len(self.words):
                    raise IndexError
                hi = self.words[pc][4] & U32
                regs[dst] = ((imm & U32) | (hi << 32)) & U64
                pc += 1
                continue
            if op == 0x85:  # syscall only (generator emits no bpf calls)
                regs[0] = self._syscall(imm & U32, regs)
                continue
            klass = op & 0x07
            use_reg = bool(op & 0x08)
            code = op & 0xF0
            if klass in (4, 7):
                wide = klass == 7
                mask = U64 if wide else U32
                a = regs[dst] & mask
                b = (regs[src] if use_reg else imm) & mask
                if code == 0x00:
                    r = a + b
                elif code == 0x10:
                    r = a - b
                elif code == 0x20:
                    r = a * b
                elif code == 0x30:
                    if b == 0:
                        raise ZeroDivisionError
                    r = a // b
                elif code == 0x40:
                    r = a | b
                elif code == 0x50:
                    r = a & b
                elif code == 0x60:
                    r = a << (b & (63 if wide else 31))
                elif code == 0x70:
                    r = a >> (b & (63 if wide else 31))
                elif code == 0x80:
                    r = -a
                elif code == 0x90:
                    if b == 0:
                        raise ZeroDivisionError
                    r = a % b
                elif code == 0xA0:
                    r = a ^ b
                elif code == 0xB0:
                    r = b
                elif code == 0xC0:
                    sa = a - (1 << (64 if wide else 32)) if a >> (
                        63 if wide else 31
                    ) else a
                    r = sa >> (b & (63 if wide else 31))
                else:
                    raise ValueError
                regs[dst] = r & mask
            elif klass in (5, 6):
                wide = klass == 5
                mask = U64 if wide else U32
                a = regs[dst] & mask
                b = (regs[src] if use_reg else imm) & mask
                top = 63 if wide else 31
                sa = a - (mask + 1) if a >> top else a
                sb = b - (mask + 1) if b >> top else b
                taken = {
                    0x00: True,
                    0x10: a == b, 0x20: a > b, 0x30: a >= b,
                    0x40: bool(a & b), 0x50: a != b,
                    0x60: sa > sb, 0x70: sa >= sb,
                    0xA0: a < b, 0xB0: a <= b,
                    0xC0: sa < sb, 0xD0: sa <= sb,
                }[code]
                if taken:
                    pc += off
            elif klass == 1:  # ldx
                sz = {0x10: 1, 0x08: 2, 0x00: 4, 0x18: 8}[op & 0x18]
                regs[dst] = self._load((regs[src] + off) & U64, sz)
            elif klass == 2:  # st imm
                sz = {0x10: 1, 0x08: 2, 0x00: 4, 0x18: 8}[op & 0x18]
                self._store((regs[dst] + off) & U64, sz, imm & U64)
            elif klass == 3:  # stx
                sz = {0x10: 1, 0x08: 2, 0x00: 4, 0x18: 8}[op & 0x18]
                self._store((regs[dst] + off) & U64, sz, regs[src])
            else:
                raise ValueError

    def mem_digest(self):
        return hashlib.sha256(
            bytes(self.input) + bytes(self.heap)
        ).hexdigest()


ALU_CODES = (0x00, 0x10, 0x20, 0x30, 0x40, 0x50, 0x60, 0x70,
             0x90, 0xA0, 0xB0, 0xC0)
JMP_CODES = (0x10, 0x20, 0x30, 0x40, 0x50, 0x60, 0x70, 0xA0, 0xB0, 0xC0, 0xD0)
MEM_SZ_BITS = (0x10, 0x08, 0x00, 0x18)
SYSCALLS = (b"sol_memset_", b"sol_memcpy_", b"sol_memcmp_", b"sol_sha256")


def lddw_words(dst, val):
    lo = val & U32
    hi = (val >> 32) & U32
    return [(0x18, dst, 0, 0, lo - (1 << 32) if lo >> 31 else lo),
            (0x00, 0, 0, 0, hi - (1 << 32) if hi >> 31 else hi)]


def _rand_addr(rng, oob_pct=6):
    """A VM address: usually valid (input/heap/stack), sometimes junk."""
    from firedancer_tpu.ballet.sbpf import MM_HEAP, MM_INPUT, MM_STACK

    roll = rng.integers(0, 100)
    if roll < oob_pct:
        return int(rng.integers(0, 1 << 34))  # likely out of bounds
    base, span = [
        (MM_INPUT, INPUT_SZ), (MM_HEAP, HEAP_SZ), (MM_STACK, STACK_SZ),
    ][rng.integers(0, 3)]
    return base + int(rng.integers(0, span))


def gen_program(rng, n=24):
    """Random program: ALU + mem ops + bounded backward loops + syscalls
    + forward jumps + exit.  r9 is reserved as the loop counter so loops
    always terminate (both sides also have a step budget as backstop)."""
    words = []
    snippets = n
    for _ in range(snippets):
        kind = int(rng.integers(0, 14))
        dst = int(rng.integers(0, 9))
        src = int(rng.integers(0, 9))
        imm = int(rng.integers(0, 1 << 32)) - (1 << 31)
        if kind < 5:  # ALU
            code = int(ALU_CODES[rng.integers(0, len(ALU_CODES))])
            klass = 7 if rng.integers(0, 2) else 4
            use_reg = int(rng.integers(0, 2)) * 0x08
            if code in (0x30, 0x90):  # div/mod: mostly nonzero imm so
                # programs run deep; zero divisors still occur via regs
                if rng.integers(0, 4):
                    use_reg = 0
                    imm = imm or 7
            words.append((code | klass | use_reg, dst, src, 0, imm))
        elif kind < 7:  # mem store then load (mostly in-bounds)
            addr = _rand_addr(rng)
            szb = int(MEM_SZ_BITS[rng.integers(0, 4)])
            words += lddw_words(8, addr)
            if rng.integers(0, 2):
                words.append((0x60 | szb | 0x03, 8, src, 0, 0))  # stx
            else:
                words.append((0x60 | szb | 0x02, 8, 0, 0, imm))  # st imm
            words.append((0x60 | szb | 0x01, dst, 8, 0, 0))      # ldx
        elif kind < 9:  # bounded backward loop over 1-3 ALU ops
            trip = int(rng.integers(1, 6))
            words.append((0xB7, 9, 0, 0, trip))  # mov64 r9, trip
            body = []
            for _ in range(int(rng.integers(1, 4))):
                code = int(ALU_CODES[rng.integers(0, len(ALU_CODES))])
                body.append((code | 7 | (int(rng.integers(0, 2)) * 0x08),
                             dst, src, 0, imm))
            words += body
            words.append((0x07, 9, 0, 0, -1))    # add64 r9, -1
            # jne r9, 0, back over body+decrement
            words.append((0x55, 9, 0, -(len(body) + 2), 0))
        elif kind < 11:  # syscall
            name = SYSCALLS[rng.integers(0, len(SYSCALLS))]
            a1 = _rand_addr(rng, oob_pct=3)
            a2 = _rand_addr(rng, oob_pct=3)
            ln = int(rng.integers(0, 64))
            words += lddw_words(1, a1)
            if name == b"sol_memset_":
                words.append((0xB7, 2, 0, 0, imm & 0xFF))
                words.append((0xB7, 3, 0, 0, ln))
            elif name == b"sol_memcpy_":
                words += lddw_words(2, a2)
                words.append((0xB7, 3, 0, 0, ln))
            elif name == b"sol_memcmp_":
                words += lddw_words(2, a2)
                words.append((0xB7, 3, 0, 0, ln))
                words += lddw_words(4, _rand_addr(rng, oob_pct=0))
            else:  # sha256: build one slice in input[0:16] -> out
                from firedancer_tpu.ballet.sbpf import MM_INPUT

                words += lddw_words(8, MM_INPUT)
                words += lddw_words(2, a2)
                words.append((0x7B, 8, 2, 0, 0))      # slice addr
                words.append((0xB7, 2, 0, 0, ln))
                words.append((0x7B, 8, 2, 8, 0))      # slice len... via r2
                words += lddw_words(1, MM_INPUT)
                words.append((0xB7, 2, 0, 0, 1))
                words += lddw_words(3, _rand_addr(rng, oob_pct=0))
            words.append((0x85, 0, 0, 0, sbpf.syscall_hash(name)))
        elif kind < 12:  # lddw constant
            words += lddw_words(dst, int(rng.integers(0, 1 << 63)))
        else:  # forward jump over 1-3 upcoming words
            code = int(JMP_CODES[rng.integers(0, len(JMP_CODES))])
            klass = 5 if rng.integers(0, 2) else 6
            use_reg = int(rng.integers(0, 2)) * 0x08
            skip = int(rng.integers(1, 4))
            words.append((code | klass | use_reg, dst, src, skip, imm))
            for _ in range(skip):
                words.append((0xB7, dst, 0, 0, 7))
    words.append((0x95, 0, 0, 0, 0))
    return words


def encode(words):
    return b"".join(ins(op, d, s, o, i) for op, d, s, o, i in words)


def _fault_class(msg: str) -> str:
    if "division" in msg:
        return "div"
    if "memory access violation" in msg or "read-only" in msg:
        return "oob"
    if "budget" in msg:
        return "timeout"
    return "fault"


def run_differential(seed, n_progs):
    rng = np.random.default_rng(seed)
    diverged = []
    for k in range(n_progs):
        words = gen_program(rng)
        text = encode(words)
        prog = sbpf.load(sbpf.build_elf(text))
        vm = Vm(prog, cu_limit=Oracle.STEP_LIMIT)
        vm.input_mem = bytearray(INPUT_SZ)
        try:
            got = ("ok", vm.run(),
                   hashlib.sha256(
                       bytes(vm.input_mem) + bytes(vm.heap)).hexdigest())
        except VmError as e:
            got = (_fault_class(str(e)), None, None)
        oracle = Oracle(words, rodata=prog.rodata)
        try:
            want = ("ok", oracle.run(), oracle.mem_digest())
        except ZeroDivisionError:
            want = ("div", None, None)
        except MemoryError:
            want = ("oob", None, None)
        except TimeoutError:
            want = ("timeout", None, None)
        except (IndexError, ValueError, KeyError, LookupError):
            want = ("fault", None, None)
        if got != want:
            diverged.append((k, got[:2], want[:2], words))
    assert not diverged, (len(diverged), diverged[:2])


@pytest.mark.parametrize("seed", range(4))
def test_differential_random_programs(seed):
    run_differential(seed, 600)


@pytest.mark.slow
@pytest.mark.parametrize("seed", range(100, 104))
def test_differential_random_programs_deep(seed):
    run_differential(seed, 1900)
