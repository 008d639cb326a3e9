"""PoH tile: the proof-of-history clock, slot state machine, and mixins.

Reference model: src/app/fdctl/run/tiles/fd_poh.c (design essay at
:10-250) — the validator's one sequential component: iterate
state = SHA-256(state) continuously, track the slot boundary every
ticks_per_slot ticks, follow the leader schedule (become leader when our
identity holds the slot, hand off when it passes), and mix executed
microblocks into the chain ONLY while leader.

The chain itself runs on the HOST: it is a sequential sha256 ladder
with no batch parallelism for an accelerator to exploit (the reference
burns a dedicated CPU core on it, fd_poh.c).  The DEVICE'S job is what
parallelizes — ops/poh.verify_entries batch-checks entries, which is
why entries out carry (prev_state, hashcnt, mixin, state).  Slot
boundaries emit a tick entry with the slot number in the sig field.

ISSUE 12 (native block egress): the ladder no longer pays a Python
hashlib call per row.  The chain state, pacing clock and slot machine
live in a SHARED words block (the tile's workspace arena in the process
runtime) mutated identically by this file's Python loop and by
tango/native/fdt_poh.c — the stem frag handler (mixins) plus an
after-credit hook (the paced tick batch), so steady state is zero
Python per frag AND per tick batch.  Every emission arms a small
journal (pre-state, mix, in/out seqs) before mutating the chain:
PohTile._recover re-derives an interrupted emission deterministically
and skips the publishes the out mcache already carries, making each
microblock mix-in EXACTLY-ONCE across SIGKILL + supervisor replay and
the entry stream gapless (prev/state chain continuity holds across a
crash)."""

from __future__ import annotations

import numpy as np

from firedancer_tpu.disco.metrics import MetricsSchema
from firedancer_tpu.disco.mux import MuxCtx, Tile, drain_straggler_ins
from firedancer_tpu.tango import rings as R
from firedancer_tpu.tango import tempo
import hashlib as _hashlib

ENTRY_SZ = 32 + 8 + 32 + 32  # prev_state | hashcnt u64 | mixin | state

#: mainnet: 64 ticks per slot (the reference derives it from genesis)
TICKS_PER_SLOT = 64

#: slot-boundary entries publish tag = SLOT_BOUNDARY_TAG | slot, keeping
#: them disjoint from mixin/tick entry tags (small hashcnt values)
SLOT_BOUNDARY_TAG = 1 << 63

#: shared words (i64) — layout pinned to tango/native/fdt_poh.h
_W_HASHCNT, _W_SLOT, _W_TICKS, _W_NEXT_NS = 0, 1, 2, 3
_W_INTERVAL, _W_TICK_BATCH, _W_TPS, _W_LEADER = 4, 5, 6, 7
_W_HW0 = 8  # per-in consumed high-water marks, words 8..15
_W_MAGIC = 16  # host-side init flag (never read by C)
_W_CNT = 24

#: journal words (u64; prev/mix bytes from word 8) — fdt_poh.h layout
_J_PHASE, _J_INIDX, _J_INSEQ, _J_OUTSEQ0 = 0, 1, 2, 3
_J_HASHCNT, _J_TICKS, _J_SLOT = 4, 5, 6
_J_PREV, _J_MIX = 8, 12
#: tick_batch / ticks_per_slot AT ARM TIME: recovery must re-derive
#: the emission with the DEAD incarnation's config (a restart may
#: carry a config change)
_J_TB, _J_TPS = 16, 17
_J_WORDS = 24


class PohTile(Tile):
    """ins = bank_poh microblock rings; outs[0] = entries ring."""

    schema = MetricsSchema(
        counters=(
            "hashcnt",
            "mixins",
            "entries",
            "slots",
            "leader_slots",
            "dropped_mixins",
            # supervisor replay of a microblock a previous incarnation
            # already mixed (skipped below the consumed high-water mark
            # — the exactly-once discipline, not an anomaly)
            "replayed_mixins",
        ),
    )

    def __init__(
        self,
        *,
        tick_batch: int = 64,
        ticks_per_slot: int = TICKS_PER_SLOT,
        slot_ms: float = 400.0,
        leaders=None,
        identity: bytes | None = None,
        slot0: int = 0,
        name: str = "poh",
    ):
        """leaders/identity: an EpochLeaders schedule (flamenco.leaders)
        plus our pubkey drive the leader-slot state machine; with
        leaders=None the tile is always leader (single-node tests).

        slot_ms paces the clock to wall time (mainnet: 400 ms slots,
        hashcnt rate derived from it — fd_poh.c's hashcnt_duration_ns).
        Unpaced ticking would burn a full core spinning sha256 (the
        reference DEDICATES a core; shared-core hosts cannot) and starve
        every other tile.  slot_ms=0 disables pacing (tests)."""
        self.name = name
        self.tick_batch = tick_batch
        self.ticks_per_slot = ticks_per_slot
        self.leaders = leaders
        self.identity = identity
        #: ns between tick batches (0 = free-run)
        self._interval_ns = int(
            slot_ms * 1e6 * tick_batch / ticks_per_slot
        ) if slot_ms else 0
        # host-local backing until on_boot rebinds to the shared block
        # (tests construct the tile and poke .slot before any boot)
        self._chain = np.zeros(32, dtype=np.uint8)
        self._w = np.zeros(_W_CNT, dtype=np.int64)
        self._jnl = np.zeros(_J_WORDS, dtype=np.uint64)
        self._w[_W_SLOT] = slot0
        self._w[_W_INTERVAL] = self._interval_ns
        self._w[_W_TICK_BATCH] = tick_batch
        self._w[_W_TPS] = ticks_per_slot
        self._scratch = np.zeros(ENTRY_SZ, dtype=np.uint8)
        #: test hook: called between the journal arm and the publish to
        #: exercise the crash window deterministically (Python path)
        self._crash_probe = None

    # ---- shared-word views (both loop modes mutate the SAME words) -------

    @property
    def state(self) -> np.ndarray:
        return self._chain

    @state.setter
    def state(self, v) -> None:
        self._chain[:] = v

    @property
    def hashcnt(self) -> int:
        return int(self._w[_W_HASHCNT])

    @hashcnt.setter
    def hashcnt(self, v: int) -> None:
        self._w[_W_HASHCNT] = v

    @property
    def slot(self) -> int:
        return int(self._w[_W_SLOT])

    @slot.setter
    def slot(self, v: int) -> None:
        self._w[_W_SLOT] = v

    @property
    def ticks_in_slot(self) -> int:
        return int(self._w[_W_TICKS])

    @ticks_in_slot.setter
    def ticks_in_slot(self, v: int) -> None:
        self._w[_W_TICKS] = v

    # ---- leader state ----------------------------------------------------

    def is_leader(self, slot: int | None = None) -> bool:
        if self.leaders is None:
            return True
        s = self.slot if slot is None else slot
        if not self.leaders.contains(s):
            return False  # outside the schedule's epoch window
        return self.leaders.leader_for_slot(s) == self.identity

    # ---- boot / recovery -------------------------------------------------

    def wksp_footprint(self) -> int:
        return 1024

    def on_boot(self, ctx: MuxCtx) -> None:
        # the chain block lives in the workspace (shm in the process
        # runtime): state survives a SIGKILL, so the restarted
        # incarnation CONTINUES the chain instead of restarting it
        blk = ctx.alloc("poh_chain", 32 + (_W_CNT + _J_WORDS) * 8)
        chain = blk[:32]
        words = blk[32 : 32 + _W_CNT * 8].view(np.int64)
        jnl = blk[32 + _W_CNT * 8 :][: _J_WORDS * 8].view(np.uint64)
        if int(words[_W_MAGIC]) == 0:
            # first boot: seed the shared block from the ctor state
            chain[:] = self._chain
            words[:] = self._w
            words[_W_MAGIC] = 1
        else:
            # config words are always the ctor's (a restart may carry a
            # config change); chain/clock/slot words are the survivors'
            words[_W_INTERVAL] = self._interval_ns
            words[_W_TICK_BATCH] = self.tick_batch
            words[_W_TPS] = self.ticks_per_slot
        self._chain = chain
        self._w = words
        self._jnl = jnl
        words[_W_LEADER] = 1 if self.leaders is None else 0
        self._recover(ctx)
        if self.is_leader():
            ctx.metrics.inc("leader_slots")

    def _recover(self, ctx: MuxCtx) -> None:
        """Complete an emission a dead incarnation left mid-window: the
        journal carries everything needed to re-derive it
        deterministically; the out mcache's (producer_rejoin-repaired)
        seq names how many of its publishes already landed."""
        jw = self._jnl
        phase = int(jw[_J_PHASE])
        if phase == 0:
            return
        prev = jw[_J_PREV : _J_PREV + 4].tobytes()
        out = ctx.outs[0] if ctx.outs else None
        already = 0
        if out is not None:
            already = max(
                R.seq_diff(out.mcache.seq_query(), int(jw[_J_OUTSEQ0])), 0
            )
        if phase == 1:  # mixin
            mix = jw[_J_MIX : _J_MIX + 4].tobytes()
            self._chain[:] = np.frombuffer(
                _hashlib.sha256(prev + mix).digest(), np.uint8
            )
            self._w[_W_HASHCNT] = int(jw[_J_HASHCNT]) + 1
            ii = int(jw[_J_INIDX])
            hw = int(jw[_J_INSEQ]) + 1
            if ii < 8 and R.seq_diff(hw, int(self._w[_W_HW0 + ii])) > 0:
                self._w[_W_HW0 + ii] = hw
            if out is not None and already < 1:
                self._emit(
                    ctx, np.frombuffer(prev, np.uint8), 1,
                    np.frombuffer(mix, np.uint8), self._chain,
                )
        elif phase == 2:  # tick batch (+ any slot boundaries)
            tb = int(jw[_J_TB]) or self.tick_batch
            tps = int(jw[_J_TPS]) or self.ticks_per_slot
            st = prev
            for _ in range(tb):
                st = _hashlib.sha256(st).digest()
            self._chain[:] = np.frombuffer(st, np.uint8)
            self._w[_W_HASHCNT] = int(jw[_J_HASHCNT]) + tb
            ticks = int(jw[_J_TICKS]) + tb
            slot = int(jw[_J_SLOT])
            entries = [
                (np.frombuffer(prev, np.uint8), tb,
                 np.zeros(32, np.uint8), self._chain, None)
            ]
            while ticks >= tps:
                ticks -= tps
                slot += 1
                entries.append(
                    (self._chain, 0, np.zeros(32, np.uint8), self._chain,
                     SLOT_BOUNDARY_TAG | slot)
                )
            self._w[_W_TICKS] = ticks
            self._w[_W_SLOT] = slot
            if out is not None:
                for prev_a, n, mix_a, st_a, tag in entries[already:]:
                    self._emit(ctx, prev_a, n, mix_a, st_a, tag=tag)
        jw[_J_PHASE] = 0

    # ---- native stem (ISSUE 12) -----------------------------------------

    def native_handler(self, ctx: MuxCtx):
        """Native fast path: fdt_poh_mixins drains microblock frags
        (mix → append → emit, journal-armed) and fdt_poh_tick runs the
        paced tick batch + slot machine as the stem's after-credit hook
        — the fdt_pack_sched shape.  Requires always-leader (a leader
        schedule is host-side Python state) and a dcache-backed single
        entries out."""
        if (
            self.leaders is not None
            or len(ctx.outs) != 1
            or ctx.outs[0].dcache is None
            or any(il.dcache is None for il in ctx.ins)
            or len(ctx.ins) > 8
        ):
            return None
        args = np.zeros(8, np.uint64)
        args[0] = self._chain.ctypes.data
        args[1] = self._w.ctypes.data
        args[2] = self._jnl.ctypes.data
        args[3] = self._scratch.ctypes.data
        return R.StemSpec(
            R.STEM_H_POH, args,
            counters=("hashcnt", "mixins", "entries", "slots",
                      "leader_slots", "replayed_mixins"),
            keepalive=(args, self._scratch),
            ready=lambda: self._crash_probe is None,
            ac_handler=R.STEM_AC_POH,
            ac_args=args,
        )

    # ---- emission (Python reference path) --------------------------------

    def _emit(self, ctx: MuxCtx, prev, hashcnt, mix, state, tag=None) -> None:
        buf = np.zeros(ENTRY_SZ, dtype=np.uint8)
        buf[0:32] = prev
        buf[32:40].view("<u8")[0] = hashcnt
        buf[40:72] = mix
        buf[72:104] = state
        ctx.publish(
            np.array([tag if tag is not None else (hashcnt or 1)],
                     dtype=np.uint64),
            buf[None, :],
            np.array([ENTRY_SZ], dtype=np.uint16),
        )
        ctx.metrics.inc("entries")

    def on_frags(self, ctx: MuxCtx, in_idx: int, frags: np.ndarray) -> None:
        il = ctx.ins[in_idx]
        rows = il.gather(frags)
        leader = self.is_leader()  # constant within one callback
        jw = self._jnl
        w = self._w
        for i in range(len(rows)):
            seq = int(frags["seq"][i])
            hw = int(w[_W_HW0 + in_idx]) if in_idx < 8 else 0
            if hw and R.seq_diff(R.seq_u64(seq + 1), hw) <= 0:
                # supervisor replay of an already-mixed microblock:
                # exactly-once means skip (the entry is already out)
                ctx.metrics.inc("replayed_mixins")
                continue
            if not leader:
                # a bank handed us a microblock outside our leader slot:
                # fail-safe drop (the reference cannot reach this state
                # because pack only schedules while leader; we count it)
                ctx.metrics.inc("dropped_mixins")
                if in_idx < 8:
                    w[_W_HW0 + in_idx] = R.seq_u64(seq + 1)
                continue
            mb = rows[i, : frags["sz"][i]]
            # microblock hash = SHA-256 of its bytes (stand-in for the
            # entry merkle root the reference mixes in)
            mix = np.frombuffer(
                _hashlib.sha256(mb.tobytes()).digest(), np.uint8
            )
            # arm the journal BEFORE mutating the chain (fdt_poh.h crash
            # discipline — byte-identical to the native handler's)
            jw[_J_PREV : _J_PREV + 4] = np.frombuffer(
                self._chain.tobytes(), np.uint64
            )
            jw[_J_MIX : _J_MIX + 4] = np.frombuffer(mix.tobytes(), np.uint64)
            jw[_J_INIDX] = in_idx
            jw[_J_INSEQ] = seq
            jw[_J_OUTSEQ0] = R.seq_u64(ctx.outs[0].seq) if ctx.outs else 0
            jw[_J_HASHCNT] = int(w[_W_HASHCNT])
            jw[_J_PHASE] = 1
            prev = self._chain.copy()
            self._chain[:] = np.frombuffer(
                _hashlib.sha256(
                    prev.tobytes() + mix.tobytes()
                ).digest(), np.uint8,
            )
            w[_W_HASHCNT] += 1
            ctx.metrics.inc("hashcnt")
            ctx.metrics.inc("mixins")
            if self._crash_probe is not None:
                self._crash_probe()
            self._emit(ctx, prev, 1, mix, self._chain)
            if in_idx < 8:
                w[_W_HW0 + in_idx] = R.seq_u64(seq + 1)
            jw[_J_PHASE] = 0

    def on_halt(self, ctx: MuxCtx) -> None:
        # drain straggler bank mixins so the last microblocks of a run
        # still enter the chain (banks may publish right up to HALT)
        drain_straggler_ins(self, ctx, deadline_s=2.0)

    def after_credit(self, ctx: MuxCtx) -> None:
        w = self._w
        now = 0
        if int(w[_W_INTERVAL]):
            now = tempo.tickcount()
            if now < int(w[_W_NEXT_NS]):
                return
        # one firing emits the tick entry PLUS every slot-boundary entry
        # the batch crosses: gate the WHOLE emission on a live credit
        # read (a boundary firing at cr==1 would overrun a reliable
        # consumer — the poh-emit-over-credit mutant class); the pacing
        # deadline is only re-armed once the firing is admitted, so a
        # credit-starved tick retries instead of skipping
        needed = 1 + (
            int(w[_W_TICKS]) + self.tick_batch
        ) // self.ticks_per_slot
        if ctx.outs and ctx.outs[0].cr_avail() < needed:
            return
        if int(w[_W_INTERVAL]):
            nxt = int(w[_W_NEXT_NS])
            w[_W_NEXT_NS] = (
                now + int(w[_W_INTERVAL])
                if now - nxt > 1_000_000_000
                else nxt + int(w[_W_INTERVAL])
            )
        # batch-advance the clock.  The PoH chain is a SEQUENTIAL sha256
        # ladder — there is no batch parallelism for the device to
        # exploit, and every dispatch would serialize against the verify
        # tile's executions on the same chip (and, under the process
        # runtime, the verify tile's process owns the chip).  The
        # reference burns a dedicated CPU core on this chain
        # (fd_poh.c); ops/poh.verify_entries keeps the DEVICE for what
        # parallelizes — verifying many entries at once.
        jw = self._jnl
        jw[_J_PREV : _J_PREV + 4] = np.frombuffer(
            self._chain.tobytes(), np.uint64
        )
        jw[_J_OUTSEQ0] = R.seq_u64(ctx.outs[0].seq) if ctx.outs else 0
        jw[_J_HASHCNT] = int(w[_W_HASHCNT])
        jw[_J_TICKS] = int(w[_W_TICKS])
        jw[_J_SLOT] = int(w[_W_SLOT])
        jw[_J_TB] = self.tick_batch
        jw[_J_TPS] = self.ticks_per_slot
        jw[_J_PHASE] = 2
        prev = self._chain.copy()
        st = self._chain.tobytes()
        for _ in range(self.tick_batch):
            st = _hashlib.sha256(st).digest()
        self._chain[:] = np.frombuffer(st, np.uint8)
        w[_W_HASHCNT] += self.tick_batch
        ctx.metrics.inc("hashcnt", self.tick_batch)
        self._emit(ctx, prev, self.tick_batch, np.zeros(32, np.uint8),
                   self._chain)
        # slot state machine: tick_batch counts as tick_batch ticks
        w[_W_TICKS] += self.tick_batch
        while int(w[_W_TICKS]) >= self.ticks_per_slot:
            w[_W_TICKS] -= self.ticks_per_slot
            w[_W_SLOT] += 1
            ctx.metrics.inc("slots")
            if self.is_leader():
                ctx.metrics.inc("leader_slots")
            # slot-boundary entry: tag = high bit | slot number — a tag
            # space disjoint from mixin (sig=1) and tick (sig=hashcnt)
            # entries so downstream consumers can detect boundaries
            self._emit(
                ctx, self._chain, 0, np.zeros(32, np.uint8), self._chain,
                tag=SLOT_BOUNDARY_TAG | int(w[_W_SLOT]),
            )
        jw[_J_PHASE] = 0
