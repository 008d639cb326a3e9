#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the leader path still starts,
and still does its work on the device, on the attached TPU.

    python3 chip_smoke.py              # one chip: phases a-d below
    python3 chip_smoke.py --chips 4    # four chips: the verify pool only

It drives the system through the entry points a user calls (a TOML
config -> app.config.parse -> build_*_topology -> build -> start, UDP at
the txn port, RPC getTransactionCount) at the sizes the config defaults
to, checks every result against the repo's own references, and prints as
its LAST line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

only if every phase passed on a TPU.  Anything else exits non-zero and
prints no such line.  It never pins the CPU: with no chip it fails.

This top-level process never imports JAX.  A chip belongs to one process
at a time, so each phase runs as a child that exits — and releases the
chip — before the next starts; that is also what lets the process-runtime
phase hand the chip to the verify tile's own child, and what exercises
interpreter teardown on the real runtime (no os._exit anywhere).  All
phases share one compile cache (utils.hostdev.enable_compilation_cache)
and ONE verify shape (max_lanes, padded full), so a cold run pays the
trace+compile of each distinct program about once, not once per phase.

Phases of the default run:
  kernel          (a) jax.devices(); verify_batch and the verify tile's
                  own digest program at the tile's lane count on a
                  seeded batch holding every reject class; every lane
                  against ops/ed25519/hostpath, a sample against
                  golden; the Mosaic call found in the compiled text;
                  dispatch/sync/H2D probes (ROADMAP S0's first question)
  corpus          the seeded transfer corpus, signed on the device by a
                  child that exits; the plain execute_txn reference
  leader-thread   (b) the full validator, default runtime
  leader-process  (c) the same, [topo] runtime="process" stem="native";
                  the parent stays off the JAX backend
  ingress         (d) the ingress topology under a trickle of sub-batch
                  sizes, no compile inside the serving window

`--rehearse` is the CPU rehearsal of on-chip-measurement section 2: the
same phases at a tiny size on whatever backend JAX finds.  It can pass
its checks but it is not a chip run: it never prints "ok": true and
exits 3 when everything passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

#: exit codes: 0 passed on a TPU; 1 a check failed; 2 a phase crashed;
#: 3 a rehearsal passed (not a chip run)
EXIT_FAILED, EXIT_CRASHED, EXIT_REHEARSED = 1, 2, 3

#: the driver's limit is 1200 s, compilation included
BUDGET_S = 1150.0
#: lamports every corpus account starts with
START_LAMPORTS = 1 << 40


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a run is sized by.  FULL leaves every config key at the
    default the product ships (the upstream sizes: 4,194,302-entry dedup
    cache, 1232-byte msg_width, 4096 lanes, 16,384-slot bank table) and
    only names the traffic; TINY is the CPU rehearsal."""

    max_lanes: int
    n_unique: int
    n_dup: int
    n_bad: int
    #: corpus accounts (payers AND recipients): at most half the bank
    #: table's slots, so the table holds every one and final balances
    #: can be read back out of it under either runtime
    n_accounts: int
    golden_sample: int
    #: txns the sender keeps inside the pipeline (< pack's 4096-slot
    #: pool: a full pool REJECTS inserts, and UDP has no backpressure)
    window: int
    #: ingress trickle: txns per burst, each waited out before the next
    trickle: tuple[int, ...]
    #: --chips 4 stream (txns through the verify pool), corrupted share
    pool_txns: int
    pool_bad: int
    min_device_batches: int


FULL = Sizes(
    max_lanes=4096, n_unique=65_536, n_dup=4_096, n_bad=1_024,
    n_accounts=8_192, golden_sample=256, window=3_072,
    trickle=(1, 7, 33, 150, 700, 3_000),
    pool_txns=65_536, pool_bad=1_024, min_device_batches=17,
)
TINY = Sizes(
    max_lanes=32, n_unique=96, n_dup=16, n_bad=8,
    n_accounts=16, golden_sample=12, window=48,
    trickle=(1, 3, 9, 20),
    pool_txns=256, pool_bad=16, min_device_batches=3,
)


class PhaseFailed(Exception):
    """A check of the phase did not hold (exit 1, no traceback)."""


def check(cond, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def say(phase: str, **kv) -> None:
    parts = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"chip_smoke {phase}: {parts}", flush=True)


# ---------------------------------------------------------------------------
# compile accounting (in-process phases)


class CompileLog:
    """jax.monitoring listeners: per-program trace / lowering / backend-
    compile seconds, whether the persistent cache served it, and a count
    of backend compiles — the thing that must stay zero while serving."""

    _TRACE = "/jax/core/compile/jaxpr_trace_duration"
    _LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon

        self.lock = threading.Condition()
        self.programs: dict[str, dict] = {}
        self.n_backend = 0
        self._cache_ev: dict[int, str] = {}  # thread -> hit|miss
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name, **_kw):
        if name.endswith("/cache_hits"):
            self._cache_ev[threading.get_ident()] = "hit"
        elif name.endswith("/cache_misses"):
            self._cache_ev[threading.get_ident()] = "miss"

    def _on_duration(self, name, dur, fun_name=None, **_kw):
        key = {self._TRACE: "trace_s", self._LOWER: "lower_s",
               self._BACKEND: "compile_s"}.get(name)
        if key is None:
            return
        fn = str(fun_name)
        if not fn.startswith("jit("):
            fn = f"jit({fn})"  # trace events carry the bare name
        with self.lock:
            p = self.programs.setdefault(
                fn, dict(trace_s=0.0, lower_s=0.0, compile_s=0.0,
                         compiles=0, hits=0, misses=0, cache="off"),
            )
            p[key] += dur
            if key == "compile_s":
                p["compiles"] += 1
                self.n_backend += 1
                # cache = what served the LAST compile of this program
                p["cache"] = self._cache_ev.pop(
                    threading.get_ident(), p["cache"]
                )
                p["hits"] += p["cache"] == "hit"
                p["misses"] += p["cache"] == "miss"
            self.lock.notify_all()

    def wait_lowered(self, fn: str, timeout: float) -> bool:
        """Block until program `fn` has been lowered, i.e. until its
        Python-bound tracing is over and what remains of its compile
        runs without the GIL."""
        with self.lock:
            return self.lock.wait_for(
                lambda: self.programs.get(fn, {}).get("lower_s", 0) > 0,
                timeout,
            )

    def _compiled(self) -> dict:
        """Entries that became programs (an inner function traced into
        another jit has trace time only, already inside the outer's)."""
        with self.lock:
            return {fn: dict(p) for fn, p in self.programs.items()
                    if p["compiles"]}

    def big(self, min_s: float = 0.5) -> dict:
        """Programs that cost at least min_s in all, seconds rounded."""
        return {
            fn: {k: (round(v, 2) if isinstance(v, float) else v)
                 for k, v in p.items()}
            for fn, p in self._compiled().items()
            if p["trace_s"] + p["lower_s"] + p["compile_s"] >= min_s
        }

    def totals(self) -> dict:
        ps = list(self._compiled().values())
        return dict(
            programs=len(ps),
            trace_lower_s=round(
                sum(p["trace_s"] + p["lower_s"] for p in ps), 2),
            compile_s=round(sum(p["compile_s"] for p in ps), 2),
            cache_hits=sum(p["hits"] for p in ps),
            cache_misses=sum(p["misses"] for p in ps),
        )


def _device_line() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _start_jax_phase(rehearse: bool) -> tuple[CompileLog, dict]:
    """Common start of a phase that owns the device in THIS process."""
    from firedancer_tpu.utils.hostdev import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    log = CompileLog()
    dev = _device_line()
    check(
        rehearse or dev["platform"] == "tpu",
        f"no TPU: JAX found {dev} (chip_smoke never falls back to the "
        f"CPU; the CPU rehearsal is --rehearse)",
    )
    dev["cache_dir"] = cache_dir
    return log, dev


# ---------------------------------------------------------------------------
# phase (a): device + kernel


def _torsion_encoding(golden) -> bytes:
    """A nontrivial small-order point encoding, derived via the oracle."""
    y = 2
    while True:
        cand = golden.point_decompress(int(y).to_bytes(32, "little"))
        if cand is not None:
            t = golden.scalar_mul(golden.L, cand)
            if t != golden.IDENT:
                return golden.point_compress(t)
        y += 1


def kernel_batch(sz: Sizes, msg_width: int, seed: int):
    """A seeded batch of sz.max_lanes lanes: valid signatures over
    messages of every length class, plus every reject class.  Returns
    (msgs, lens, sigs, pubs, digests, labels) — labels[i] names lane i's
    class; "valid" lanes must verify, every other class must not."""
    import hashlib

    import numpy as np

    from firedancer_tpu.ops.ed25519 import golden, hostpath

    rng = np.random.default_rng(seed)
    n = sz.max_lanes
    keys = [rng.integers(0, 256, 32, np.uint8).tobytes() for _ in range(8)]
    pks = [hostpath.public_from_secret(k) for k in keys]
    tors = _torsion_encoding(golden)
    rejects = ("noncanonical_s", "small_order_A", "small_order_R",
               "bad_R", "wrong_msg", "flipped_sig_bit")
    cases = []
    for i in range(n):
        k = i % len(keys)
        mlen = int(rng.integers(0, msg_width + 1)) if i % 5 else msg_width
        m = rng.integers(0, 256, mlen, np.uint8).tobytes()
        sig = hostpath.sign(keys[k], m)
        pk = pks[k]
        # one lane in four carries a reject class, cycling through all
        label = rejects[(i // 4) % len(rejects)] if i % 4 == 3 else "valid"
        if label == "noncanonical_s":
            s = int.from_bytes(sig[32:], "little") + golden.L
            sig = sig[:32] + s.to_bytes(32, "little")
        elif label == "small_order_A":
            pk = tors
        elif label == "small_order_R":
            sig = tors + sig[32:]
        elif label == "bad_R":
            other = hostpath.sign(keys[k], m + b"x")
            sig = other[:32] + sig[32:]
        elif label == "wrong_msg":
            m = (bytes([m[0] ^ 1]) + m[1:]) if m else b"\x01"
        elif label == "flipped_sig_bit":
            b = bytearray(sig)
            b[int(rng.integers(0, 64))] ^= 1 << int(rng.integers(0, 8))
            sig = bytes(b)
        cases.append((m, sig, pk, label))
    msgs = np.zeros((n, msg_width), np.uint8)
    lens = np.zeros(n, np.int32)
    sigs = np.zeros((n, 64), np.uint8)
    pubs = np.zeros((n, 32), np.uint8)
    digests = np.zeros((n, 64), np.uint8)
    for i, (m, sig, pk, _) in enumerate(cases):
        msgs[i, : len(m)] = np.frombuffer(m, np.uint8)
        lens[i] = len(m)
        sigs[i] = np.frombuffer(sig, np.uint8)
        pubs[i] = np.frombuffer(pk, np.uint8)
        digests[i] = np.frombuffer(
            hashlib.sha512(sig[:32] + pk + m).digest(), np.uint8
        )
    return msgs, lens, sigs, pubs, digests, [c[3] for c in cases]


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


def _device_probes(fn, args) -> dict:
    """ROADMAP S0's first question, answered by reading clocks around
    real calls: does block_until_ready wait for the device, what does a
    trivial dispatch round trip cost, how fast is a host->device put.
    `fn(*args)` is the warm verify program (a known-long execution)."""
    import jax
    import numpy as np

    out: dict = {}
    # (1) a long execution: where does the wall time go?
    d_disp, d_bur, d_d2h = [], [], []
    for _ in range(5):
        t0 = time.perf_counter()
        r = fn(*args)
        t1 = time.perf_counter()
        r.block_until_ready()
        t2 = time.perf_counter()
        np.asarray(r)
        t3 = time.perf_counter()
        d_disp.append(t1 - t0)
        d_bur.append(t2 - t1)
        d_d2h.append(t3 - t2)
    out["verify_dispatch_ms"] = _median(d_disp) * 1e3
    out["verify_block_until_ready_ms"] = _median(d_bur) * 1e3
    out["verify_d2h_after_ready_ms"] = _median(d_d2h) * 1e3
    # it synchronises if the wait sits in block_until_ready, not in the
    # device-to-host copy that follows it
    out["block_until_ready_syncs"] = bool(
        _median(d_bur) > 4 * _median(d_d2h)
    )
    # (2) trivial dispatch round trip: x + 1 on 8 int32, D2H included
    inc = jax.jit(lambda x: x + 1)
    x = jax.device_put(np.arange(8, dtype=np.int32))
    np.asarray(inc(x))
    rt = []
    for _ in range(200):
        t0 = time.perf_counter()
        np.asarray(inc(x))
        rt.append(time.perf_counter() - t0)
    out["trivial_roundtrip_us_median"] = _median(rt) * 1e6
    out["trivial_roundtrip_us_p99"] = sorted(rt)[int(len(rt) * 0.99)] * 1e6
    # (3) host->device put of 16 MiB, waited out
    buf = np.random.default_rng(0).integers(0, 256, 16 << 20, np.uint8)
    jax.device_put(buf).block_until_ready()
    h2d = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.device_put(buf).block_until_ready()
        h2d.append(time.perf_counter() - t0)
    out["h2d_16MiB_MBps_median"] = len(buf) / _median(h2d) / 1e6
    return out


def phase_kernel(sz: Sizes, seed: int, rehearse: bool, workdir: str) -> dict:
    import numpy as np

    log, dev = _start_jax_phase(rehearse)
    from firedancer_tpu.app import config as C
    from firedancer_tpu.ops.ed25519 import golden, hostpath
    from firedancer_tpu.ops.ed25519 import verify as fver
    from firedancer_tpu.tiles.verify import VerifyTile

    cfg = C.parse(_verify_toml(sz))
    msg_width = cfg.verify_msg_width
    msgs, lens, sigs, pubs, digests, labels = kernel_batch(
        sz, msg_width, seed
    )
    lab = np.array(labels)
    # a seeded sample, every reject class included, for golden
    rng = np.random.default_rng(seed + 1)
    sample = [int(np.flatnonzero(lab == c)[0]) for c in sorted(set(labels))]
    rest = rng.permutation(sz.max_lanes)
    sample += [int(i) for i in rest if int(i) not in set(sample)][
        : max(sz.golden_sample - len(sample), 0)
    ]

    # Three long jobs, two kinds of time.  Tracing + lowering a verify
    # program and the host references are Python, bound by the GIL; a
    # backend compile runs without it.  So the Python parts take turns
    # (two threads that both trace thrash the GIL and each takes three
    # times as long) and every backend compile overlaps the next job's
    # Python: tile program -> message entry -> host references.
    ref: dict = {}

    def host_references():
        log.wait_lowered("jit(_verify_impl)", 600.0)
        t0 = time.perf_counter()
        ref["hostpath"] = hostpath.verify_batch_digest_host(
            digests, sigs, pubs
        )
        ref["hostpath_s"] = time.perf_counter() - t0
        ref["golden"] = {
            i: golden.verify(
                msgs[i, : lens[i]].tobytes(), sigs[i].tobytes(),
                pubs[i].tobytes(),
            ) == golden.ERR_OK
            for i in sample
        }
        ref["golden_s"] = time.perf_counter() - t0 - ref["hostpath_s"]

    def message_entry():
        log.wait_lowered("jit(verify_batch_digest)", 600.0)
        t0 = time.perf_counter()
        ref["got_msg"] = np.asarray(
            fver.verify_batch(msgs, lens, sigs, pubs)
        )
        ref["msg_first_s"] = time.perf_counter() - t0

    side = [threading.Thread(target=f, name=f.__name__)
            for f in (host_references, message_entry)]
    for t in side:
        t.start()

    # the tile's own program, built and warmed by the tile's own code at
    # the shape every later phase boots with (same program, same bytes
    # -> the later phases' compile is a persistent-cache hit)
    tile = VerifyTile(msg_width=msg_width, max_lanes=cfg.verify_max_lanes)
    t0 = time.perf_counter()
    fn = tile._make_device_fns()[0]
    tile_warm_s = time.perf_counter() - t0
    # with the count as the tile sends it (an int32 array): the program
    # the tile warmed, and no other
    count = np.asarray(sz.max_lanes, np.int32)
    got_digest = np.asarray(fn(digests, sigs, pubs, count))
    # and a count inside the second kernel tile: the same program stops
    # after it, and every lane from the count on reads False
    part = min(257, sz.max_lanes - 1)
    got_part = np.asarray(fn(digests, sigs, pubs, np.asarray(part, np.int32)))
    check((got_part[:part] == got_digest[:part]).all()
          and not got_part[part:].any(),
          f"n_lanes={part}: verdicts before it differ or one past it is set")
    check(got_digest.shape == (sz.max_lanes,) and got_digest.dtype == bool,
          f"digest entry returned {got_digest.shape} {got_digest.dtype}")
    for t in side:
        t.join()
    check("golden" in ref and "got_msg" in ref,
          "a side thread of the kernel phase died (traceback above)")
    got_msg, msg_first_s = ref["got_msg"], ref["msg_first_s"]

    # every lane against the strict host verifier
    want = ref["hostpath"]
    for name, got in (("verify_batch_digest", got_digest),
                      ("verify_batch", got_msg)):
        bad = np.flatnonzero(got != want)
        check(len(bad) == 0,
              f"{name} disagrees with hostpath on {len(bad)} lanes, first "
              f"{[(int(i), labels[i]) for i in bad[:5]]}")
    check(want[lab == "valid"].all(), "a valid lane was rejected")
    check(not want[lab != "valid"].any(), "a reject-class lane verified")
    # the sample against golden
    for i, g in ref["golden"].items():
        check(bool(got_digest[i]) == g and bool(got_msg[i]) == g,
              f"lane {i} ({labels[i]}): device says {got_digest[i]}/"
              f"{got_msg[i]}, golden says {g}")
    hostpath_s, golden_s = ref["hostpath_s"], ref["golden_s"]

    # the Mosaic kernel is IN the compiled programs (not inferred from
    # _use_pallas(): that flag picks plain XLA silently off-TPU)
    t0 = time.perf_counter()
    texts = {
        "verify_batch_digest": fn.lower(digests, sigs, pubs, count)
        .compile().as_text(),
        "verify_batch": fver._verify_impl.lower(
            msgs, lens, sigs, pubs, msgs.shape[1],
            use_pallas=fver._use_pallas(),
        ).compile().as_text(),
    }
    mosaic = {k: t.count("tpu_custom_call") for k, t in texts.items()}
    aot_s = time.perf_counter() - t0
    if dev["platform"] == "tpu":
        for k, cnt in mosaic.items():
            check(cnt >= 1, f"{k}: no Mosaic call (tpu_custom_call) in "
                            f"the compiled program")
    probes = _device_probes(fn, (digests, sigs, pubs, count))
    res = dict(
        device=dev, lanes=sz.max_lanes, msg_width=msg_width,
        valid=int(want.sum()), rejected=int((~want).sum()),
        classes=sorted(set(labels)), golden_sample=len(sample),
        mosaic_calls=mosaic, tile_warm_s=round(tile_warm_s, 2),
        verify_batch_first_call_s=round(msg_first_s, 2),
        aot_recompile_s=round(aot_s, 2),
        hostpath_s=round(hostpath_s, 2), golden_s=round(golden_s, 2),
        device_programs=tile._program_count(),
        programs=log.big(), compile=log.totals(), probes=probes,
    )
    say("kernel", device=dev, lanes=sz.max_lanes, mosaic_calls=mosaic)
    say("kernel", **res["compile"], tile_warm_s=res["tile_warm_s"],
        aot_recompile_s=res["aot_recompile_s"],
        hostpath_s=res["hostpath_s"], golden_s=res["golden_s"])
    for fname, p in res["programs"].items():
        say("kernel", program=fname, **p)
    say("kernel", **{k: (round(v, 3) if isinstance(v, float) else v)
                     for k, v in probes.items()})
    return res


# ---------------------------------------------------------------------------
# the corpus (signed on the device, by a child that exits)


def build_corpus(sz: Sizes, seed: int) -> dict:
    """The seeded traffic of phases b-d and its reference outcome.

    n_unique distinct signed system transfers among n_accounts funded
    accounts; n_dup of them re-sent byte for byte and n_bad re-sent with
    one bit of the signature's first 8 bytes flipped (the dedup tag is
    those 8 bytes: the corrupted copy has its own tag, so it is rejected
    by VERIFY and never mistaken for a duplicate).  Copies are spread
    over the stream, each after its original.  `expected` is what a
    plain execute_txn loop over the unique txns leaves in each account.
    """
    import numpy as np

    from firedancer_tpu.flamenco.accounts import Account, AccountMgr
    from firedancer_tpu.flamenco.runtime import Executor
    from firedancer_tpu.funk.funk import Funk
    from firedancer_tpu.tiles.bench import make_transfer_pool

    rows, pubs = make_transfer_pool(
        sz.n_unique, n_signers=sz.n_accounts, seed=seed,
        closed_accounts=True,
    )
    rng = np.random.default_rng(seed + 7)
    n = sz.n_unique
    dup_src = rng.choice(n, sz.n_dup, replace=False)
    bad_src = rng.choice(n, sz.n_bad, replace=False)
    src = np.concatenate([np.arange(n), dup_src, bad_src])
    kind = np.concatenate([
        np.zeros(n, np.uint8), np.ones(sz.n_dup, np.uint8),
        np.full(sz.n_bad, 2, np.uint8),
    ])
    # a copy lands at a seeded place strictly after its original
    extra = np.concatenate([dup_src, bad_src])
    key = np.concatenate([
        np.arange(n, dtype=np.float64),
        extra + 0.5 + np.floor(rng.random(len(extra)) * (n - extra)),
    ])
    order = np.argsort(key, kind="stable")
    send = rows[src[order]].copy()
    kind = kind[order]
    bad = np.flatnonzero(kind == 2)
    send[bad, 1 + rng.integers(0, 8, len(bad))] ^= (
        1 << rng.integers(0, 8, len(bad))
    ).astype(np.uint8)

    funk = Funk()
    mgr = AccountMgr(funk)
    for p in pubs:
        mgr.store(p, Account(START_LAMPORTS))
    ex = Executor(funk)
    ex.begin_slot(0)
    for i in range(n):
        r = ex.execute_txn(rows[i].tobytes())
        check(r.ok, f"reference executor failed txn {i}: {r}")
    expected = np.array([mgr.lamports(p) for p in pubs], np.uint64)
    return dict(
        send=send, kind=kind,
        pubs=np.stack([np.frombuffer(p, np.uint8) for p in pubs]),
        expected=expected,
    )


def phase_corpus(sz: Sizes, seed: int, rehearse: bool, workdir: str) -> dict:
    import hashlib

    import numpy as np

    log, dev = _start_jax_phase(rehearse)
    from firedancer_tpu.ballet import txn as T
    from firedancer_tpu.ops.ed25519 import hostpath

    t0 = time.perf_counter()
    c = build_corpus(sz, seed)
    build_s = time.perf_counter() - t0
    # the device signer against the host verifier, on a sample
    uniq = np.flatnonzero(c["kind"] == 0)
    pick = uniq[np.random.default_rng(seed).permutation(len(uniq))[:32]]
    for i in pick:
        raw = c["send"][i].tobytes()
        d = T.parse(raw)
        sig, pk = raw[1:65], bytes(d.acct_addr(raw, 0))
        dig = hashlib.sha512(sig[:32] + pk + d.message(raw)).digest()
        check(hostpath.verify_digest(dig, sig, pk),
              f"device-signed txn {i} does not verify on the host")
    np.savez(os.path.join(workdir, "corpus.npz"), **c)
    res = dict(
        device=dev, txns=len(c["send"]), unique=sz.n_unique,
        dup=sz.n_dup, bad=sz.n_bad, accounts=sz.n_accounts,
        txn_bytes=int(c["send"].shape[1]), build_s=round(build_s, 2),
        programs=log.big(), compile=log.totals(),
    )
    say("corpus", **{k: res[k] for k in
                     ("txns", "unique", "dup", "bad", "accounts",
                      "txn_bytes", "build_s")}, **res["compile"])
    return res


# ---------------------------------------------------------------------------
# phases (b), (c): the leader path


def _verify_toml(sz: Sizes) -> str:
    """The only keys a run may set beside its ports and runtime: FULL
    sets none (the shipped defaults ARE the upstream sizes); the
    rehearsal shrinks the lane count to what a CPU verifies in seconds."""
    if sz is FULL:
        return ""
    return f"[tiles.verify]\nmax_lanes = {sz.max_lanes}\n"


def _load_config(workdir: str, name: str, toml: str):
    """The config goes through a FILE, as it does for `fdtctl run
    --config`: written, read back, parsed."""
    from firedancer_tpu.app import config as C

    path = os.path.join(workdir, f"{name}.toml")
    with open(path, "w") as f:
        f.write(toml)
    with open(path) as f:
        return C.parse(f.read())


def _identity(seed: int) -> bytes:
    import numpy as np

    return np.random.default_rng(seed).integers(
        0, 256, 32, np.uint8).tobytes()


def _free_udp_port() -> int:
    """Under the process runtime the net tile's child binds the socket,
    so the port must be known to this parent beforehand.  (Probe->bind
    leaves a small window; a stolen port fails the child's bind loudly.)"""
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _udp_kernel_drops(port: int) -> int:
    """Datagrams the kernel dropped at the socket bound to `port` (the
    last column of /proc/net/udp): the one loss no tile can count."""
    drops = 0
    with open("/proc/net/udp") as f:
        next(f)
        for line in f:
            cols = line.split()
            if int(cols[1].rsplit(":", 1)[1], 16) == port:
                drops += int(cols[-1])
    return drops


class _Counters:
    """Named reads of the topology's shared metrics (valid from the
    parent under either runtime: the regions live in the workspace)."""

    def __init__(self, topo, n_banks: int):
        self.topo, self.n_banks = topo, n_banks

    def get(self, tile: str, name: str) -> int:
        return int(self.topo.metrics(tile).counter(name))

    def executed(self) -> int:
        return sum(self.get(f"bank{i}", "executed_txns")
                   for i in range(self.n_banks))

    def settled(self) -> int:
        """Txns that reached their end: landed, or dropped with a
        counter that says why."""
        return (
            self.executed()
            + self.get("verify0", "verify_fail_txns")
            + self.get("verify0", "dedup_drop_txns")
            + self.get("dedup", "dup_txns")
        )


def _socket_window() -> int:
    """Datagrams the receiving tile's socket can hold unread, with half
    its buffer to spare.  waltz/udpsock.py asks for 2 MiB; the kernel
    grants min(that, rmem_max), doubled; a datagram of a few hundred
    bytes is charged ~1280 bytes of it (skb truesize)."""
    with open("/proc/sys/net/core/rmem_max") as f:
        granted = 2 * min(1 << 21, int(f.read()))
    return max(granted // 1280 // 2, 64)


def _send_paced(sock, addr, rows, in_flight, rx_count, window, deadline,
                poll) -> int:
    """Blast `rows` at `addr`, never more than `window` txns inside the
    pipeline (in_flight()) nor more datagrams than the receiving tile's
    socket buffer holds that the tile has not read yet (rx_count()):
    UDP has no backpressure, and the socket buffer and pack's pool both
    drop what overflows them.  Returns the count sent — short of
    len(rows) only when the deadline cut it, which the caller's ledger
    then shows with every counter."""
    sent, n = 0, len(rows)
    unread_max = _socket_window()
    while sent < n and time.monotonic() < deadline:
        poll()
        room = min(window - in_flight(sent),
                   unread_max - (sent - rx_count()), n - sent)
        if room <= 0:
            # a long nap: under the thread runtime this loop shares one
            # GIL with every tile, and a sender that polls hard slows
            # the pipeline it is waiting for
            time.sleep(0.005)
            continue
        for i in range(sent, sent + room):
            sock.sendto(rows[i], addr)  # a row is one contiguous buffer
        sent += room
    return sent


def _contention_probe(sz: Sizes, workdir: str) -> dict:
    """How does a verify tile child that cannot get the chip show
    itself?  Boot a second, minimal process-runtime topology while the
    first one's verify child owns the chip, and RECORD the outcome: a
    boot crash with a readable error sidecar, or a hang (no RUN and no
    FAIL inside the patience).  On a CPU the device is not exclusive and
    the child simply boots."""
    import numpy as np

    from firedancer_tpu.disco import Topology
    from firedancer_tpu.tiles import wire
    from firedancer_tpu.tiles.sink import SinkTile
    from firedancer_tpu.tiles.synth import SynthTile
    from firedancer_tpu.tiles.verify import VerifyTile

    topo = Topology(name=f"smokeprobe{os.getpid()}", runtime="process")
    topo.link("s_v", depth=64, mtu=wire.LINK_MTU)
    topo.link("v_k", depth=64, mtu=wire.LINK_MTU)
    topo.tile(
        SynthTile(np.zeros((1, wire.LINK_MTU), np.uint8),
                  np.zeros(1, np.uint16), total=0),
        outs=["s_v"],
    )
    topo.tile(
        VerifyTile(max_lanes=sz.max_lanes, name="verify"),
        ins=[("s_v", True)], outs=["v_k"],
    )
    topo.tile(SinkTile(), ins=[("v_k", True)])
    # a second TOPOLOGY, so build's one-process-per-chip check (which
    # looks inside one topology) does not refuse it first
    topo.build()
    t0 = time.monotonic()
    try:
        topo.start(boot_timeout_s=90.0)
        out = dict(outcome="booted", detail="the device was not exclusive")
        topo.halt()
    except TimeoutError as e:
        out = dict(outcome="hang", detail=str(e))
    except RuntimeError as e:
        # the sidecar's traceback rides the exception: keep the lines
        # that say what failed, not the frames
        lines = [ln.strip() for ln in str(e).splitlines() if ln.strip()]
        errs = [ln for ln in lines[1:]
                if "Error" in ln and not ln.startswith("File ")]
        out = dict(outcome="boot crash with error sidecar",
                   detail=" | ".join([lines[0]] + errs[-3:])[:900])
    finally:
        topo.close()
    out["seconds"] = round(time.monotonic() - t0, 1)
    return out


def phase_leader(sz: Sizes, seed: int, rehearse: bool, workdir: str,
                 runtime: str = "thread") -> dict:
    """(b)/(c): config file -> parse -> build_validator_topology ->
    build -> start, exactly as `fdtctl run --full`; the corpus at the
    UDP txn port; RPC getTransactionCount as the landed count."""
    import numpy as np

    from firedancer_tpu.app import config as C
    from firedancer_tpu.flamenco.accounts import Account, AccountMgr
    from firedancer_tpu.flamenco.runtime import BankTable
    from firedancer_tpu.funk.funk import Funk
    from firedancer_tpu.tango import rings as R
    from firedancer_tpu.tiles.rpc import rpc_call
    from firedancer_tpu.utils import hostdev

    phase = f"leader-{runtime}"
    process = runtime == "process"
    log = dev = None
    if process:
        # this parent must never initialise a backend: config only
        hostdev.enable_compilation_cache()
    else:
        log, dev = _start_jax_phase(rehearse)
    c = np.load(os.path.join(workdir, "corpus.npz"))
    send, kind, expected = c["send"], c["kind"], c["expected"]
    pubs = [p.tobytes() for p in c["pubs"]]
    n_total = len(send)

    udp_port = _free_udp_port()
    cfg = _load_config(workdir, phase, (
        f'name = "smoke{os.getpid()}"\n'
        + ('[topo]\nruntime = "process"\nstem = "native"\n' if process else "")
        + f"[tiles.quic]\nudp_port = {udp_port}\n"
        + _verify_toml(sz)
    ))
    if sz is FULL:
        check(
            (cfg.dedup_depth, cfg.verify_msg_width, cfg.verify_max_lanes,
             cfg.bank_table_slots) == (4_194_302, 1232, 4096, 16_384),
            "the config's defaults are no longer the upstream sizes",
        )
    check(len(pubs) * 2 <= cfg.bank_table_slots,
          "the corpus accounts do not fit the bank table")

    funk = Funk()
    mgr = AccountMgr(funk)
    for p in pubs:
        mgr.store(p, Account(START_LAMPORTS))
    topo, handles = C.build_validator_topology(
        cfg, _identity(seed),
        os.path.join(workdir, f"{phase}.blockstore"), funk=funk,
    )
    topo.build()
    t0 = time.perf_counter()
    topo.start()
    boot_s = time.perf_counter() - t0
    ctr = _Counters(topo, cfg.bank_count)
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    probe = None
    try:
        rpc_addr = handles["rpc"].addr
        base = rpc_call(rpc_addr, "getTransactionCount")["result"]
        programs0 = ctr.get("verify0", "device_programs")
        compiles0 = log.n_backend if log else 0
        check(rehearse or programs0 >= 1,
              "the verify tile booted with no compiled device program")
        t_serve = time.perf_counter()
        deadline = time.monotonic() + 420.0
        sent = _send_paced(
            sock, ("127.0.0.1", udp_port), send,
            in_flight=lambda sent: sent - ctr.settled(),
            rx_count=lambda: ctr.get("net", "rx_dgrams"),
            window=sz.window, deadline=deadline, poll=topo.poll_failure,
        )
        while ctr.settled() < n_total and time.monotonic() < deadline:
            topo.poll_failure()
            time.sleep(0.01)
        serve_s = time.perf_counter() - t_serve
        landed = rpc_call(rpc_addr, "getTransactionCount")["result"] - base
        if process:
            probe = _contention_probe(sz, workdir)
        kdrops = _udp_kernel_drops(udp_port)
        topo.halt()

        v = {k: ctr.get("verify0", k) for k in (
            "verify_fail_txns", "dedup_drop_txns", "verified_sigs",
            "device_batches", "fallback_batches", "device_errors",
            "device_trips", "device_programs",
        )}
        q = {k: ctr.get("quic", k) for k in (
            "rx_txns_udp", "parse_fail_txns", "drop_txn_rate",
            "shed_unstaked", "shed_lowstake", "shed_backlog",
        )}
        led = dict(
            corpus=n_total, sent=sent, landed=landed,
            executed=ctr.executed(),
            verify_rejected=v["verify_fail_txns"],
            dup_pre_dedup=v["dedup_drop_txns"],
            dup_dedup_tile=ctr.get("dedup", "dup_txns"),
            udp_kernel_drops=kdrops,
            net_rx=ctr.get("net", "rx_dgrams"),
            net_oversize_drops=ctr.get("net", "oversize_drops"),
            quic_rx=q["rx_txns_udp"],
            quic_drops=sum(q.values()) - q["rx_txns_udp"],
            pack_inserted=ctr.get("pack", "inserted_txns"),
            pack_rejected=ctr.get("pack", "insert_rejected"),
            pack_microblocks=ctr.get("pack", "microblocks"),
            bank_failed=sum(ctr.get(f"bank{i}", "failed_txns")
                            for i in range(cfg.bank_count)),
        )
        failed_tiles = [
            name for name, cnc in topo._cncs.items()
            if cnc.signal_query() == R.CNC_FAIL
        ]
        # final balances out of the banks' shared table (authoritative
        # for these accounts under both runtimes; funk lags a commit,
        # and under the process runtime it is a copy in each bank child)
        tab = BankTable(topo.wksp.view("shared_banktab"),
                        cfg.bank_table_slots)
        got = np.zeros(len(pubs), np.uint64)
        for i, p in enumerate(pubs):
            st, lam = tab.get(p)
            check(st == BankTable.ST_TRIVIAL,
                  f"account {i} is not resident in the bank table "
                  f"(state {st})")
            got[i] = lam
        compiles_in_window = (log.n_backend - compiles0) if log else None
    finally:
        sock.close()
        topo.close()

    n_unique = int((kind == 0).sum())
    n_dup, n_bad = int((kind == 1).sum()), int((kind == 2).sum())
    res = dict(
        runtime=topo._runtime, stem=topo._loop_kw["stem"],
        boot_s=round(boot_s, 2), serve_s=round(serve_s, 2),
        landed_per_s=round(landed / serve_s, 1), ledger=led, verify=v,
        compiles_in_window=compiles_in_window,
        programs_at_boot=programs0, failed_tiles=failed_tiles,
        balances_equal=bool((got == expected).all()),
        parent_backend_initialized=hostdev.backend_initialized(),
    )
    if dev:
        res.update(device=dev, programs=log.big(), compile=log.totals())
    if probe:
        res["chip_contention"] = probe
    say(phase, runtime=res["runtime"], stem=res["stem"],
        boot_s=res["boot_s"], serve_s=res["serve_s"],
        landed_per_s=res["landed_per_s"])
    say(phase, **led)
    say(phase, **v, compiles_in_window=compiles_in_window)
    if dev:
        # every JAX program any tile of this process compiled: under the
        # process runtime only the verify tile's child may have one
        say(phase, **res["compile"], all_programs=sorted(log._compiled()))
        for fname, p in res["programs"].items():
            say(phase, program=fname, **p)
    if probe:
        say(phase, chip_contention=probe)

    check(not failed_tiles, f"tiles reached FAIL: {failed_tiles}")
    check(sent == n_total, f"the sender was cut at {sent}/{n_total}")
    check(landed == n_unique == led["executed"],
          f"landed {landed} (executed {led['executed']}), unique valid "
          f"{n_unique}")
    check(led["verify_rejected"] == n_bad,
          f"verify rejected {led['verify_rejected']}, corrupted {n_bad}")
    dups = led["dup_pre_dedup"] + led["dup_dedup_tile"]
    check(dups == n_dup, f"duplicates dropped {dups}, re-sent {n_dup}")
    # every missing or extra txn is explained by a counter: the ledger
    # closes, and every loss counter reads zero
    check(landed + led["verify_rejected"] + dups == n_total,
          f"the ledger does not close: {led}")
    for k in ("udp_kernel_drops", "net_oversize_drops", "quic_drops",
              "pack_rejected", "bank_failed"):
        check(led[k] == 0, f"{k} = {led[k]}")
    check(led["net_rx"] == led["quic_rx"] == n_total
          and led["pack_inserted"] == n_unique,
          f"a stage saw a different count: {led}")
    check(res["balances_equal"],
          f"{int((got != expected).sum())} account balances differ from "
          f"the plain execute_txn loop's")
    check(v["device_batches"] >= sz.min_device_batches,
          f"device_batches {v['device_batches']} < "
          f"{sz.min_device_batches}")
    check(v["fallback_batches"] == 0 and v["device_errors"] == 0,
          f"the device path degraded: fallback_batches "
          f"{v['fallback_batches']}, device_errors {v['device_errors']}")
    check(v["device_programs"] == programs0 and not compiles_in_window,
          f"a compile happened inside the serving window: device_programs "
          f"{programs0} -> {v['device_programs']}, backend compiles "
          f"{compiles_in_window}")
    if process:
        check(not res["parent_backend_initialized"],
              "the topology parent initialised a JAX backend")
        check(res["stem"] == "native" and res["runtime"] == "process",
              f"ran as {res['runtime']}/{res['stem']}")
    return res


# ---------------------------------------------------------------------------
# phase (d): the ingress entry under a trickle


def phase_ingress(sz: Sizes, seed: int, rehearse: bool, workdir: str) -> dict:
    """What `fdtctl run` without --full builds, under sub-batch bursts:
    every burst is its own partial batch, padded to the one shape the
    boot warmed: `device_programs` must not grow here."""
    import numpy as np

    from firedancer_tpu.app import config as C

    log, dev = _start_jax_phase(rehearse)
    c = np.load(os.path.join(workdir, "corpus.npz"))
    rows = c["send"][c["kind"] == 0][: sum(sz.trickle)]
    check(len(rows) == sum(sz.trickle), "corpus smaller than the trickle")
    cfg = _load_config(
        workdir, "ingress",
        f'name = "smokein{os.getpid()}"\n' + _verify_toml(sz),
    )
    topo, qt = C.build_ingress_topology(cfg, _identity(seed))
    topo.build()
    t0 = time.perf_counter()
    topo.start()
    boot_s = time.perf_counter() - t0
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        addr = ("127.0.0.1", qt.udp_addr[1])
        mv, mq = topo.metrics("verify0"), topo.metrics("quic")
        ms = topo.metrics("sink")
        programs0 = int(mv.counter("device_programs"))
        compiles0 = log.n_backend
        check(rehearse or programs0 >= 1,
              "the verify tile booted with no compiled device program")
        done, burst_s = 0, []
        deadline = time.monotonic() + 180.0
        for n in sz.trickle:
            t0 = time.perf_counter()
            _send_paced(
                sock, addr, rows[done : done + n],
                in_flight=lambda sent: 0,
                rx_count=lambda: int(mq.counter("rx_txns_udp")) - done,
                window=n, deadline=deadline, poll=topo.poll_failure,
            )
            done += n
            while (int(ms.counter("in_frags")) < done
                   and time.monotonic() < deadline):
                topo.poll_failure()
                time.sleep(0.002)
            burst_s.append(round(time.perf_counter() - t0, 3))
        sunk = int(ms.counter("in_frags"))
        topo.halt()
        v = {k: int(mv.counter(k)) for k in (
            "verify_fail_txns", "dedup_drop_txns", "device_batches",
            "fallback_batches", "device_errors", "device_programs",
        )}
        compiles_in_window = log.n_backend - compiles0
    finally:
        sock.close()
        topo.close()
    res = dict(
        device=dev, boot_s=round(boot_s, 2), bursts=list(sz.trickle),
        burst_s=burst_s, sunk=sunk, verify=v,
        compiles_in_window=compiles_in_window, programs_at_boot=programs0,
        programs=log.big(), compile=log.totals(),
    )
    say("ingress", boot_s=res["boot_s"], bursts=res["bursts"],
        burst_s=burst_s, sunk=sunk)
    say("ingress", **v, compiles_in_window=compiles_in_window,
        **res["compile"])
    check(sunk == done, f"sunk {sunk} of {done} sent")
    check(v["verify_fail_txns"] == 0 and v["dedup_drop_txns"] == 0,
          f"valid unique txns were dropped: {v}")
    check(v["device_batches"] >= len(sz.trickle),
          f"device_batches {v['device_batches']} < {len(sz.trickle)} bursts")
    check(v["fallback_batches"] == 0 and v["device_errors"] == 0,
          f"the device path degraded: {v}")
    check(compiles_in_window == 0 and v["device_programs"] == programs0,
          f"a compile happened inside the serving window: device_programs "
          f"{programs0} -> {v['device_programs']}, backend compiles "
          f"{compiles_in_window}")
    return res


# ---------------------------------------------------------------------------
# --chips 4: the verify pool at width four against width one


def _pool_run(sz: Sizes, pcap_path: str, total: int, devices) -> dict:
    """The verify-path topology (replay -> verify -> dedup -> sink) in
    this one process with `[tiles.verify] devices = devices`;
    returns the publish-order tag stream, the counters, and where each
    domain's arrays really lived."""
    import jax
    import numpy as np

    from firedancer_tpu.disco import Topology
    from firedancer_tpu.tiles import wire
    from firedancer_tpu.tiles.dedup import DedupTile
    from firedancer_tpu.tiles.replay import ReplayTile
    from firedancer_tpu.tiles.sink import SinkTile
    from firedancer_tpu.tiles.verify import VerifyTile

    verify = VerifyTile(
        max_lanes=sz.max_lanes, pre_dedup=False,
        devices=devices,
    )
    # placement is read off the result arrays, not off the ordinal
    # list: a pool that only ever ran on a virtual mesh could commit
    # every domain to device 0 and still list four ordinals
    t0 = time.perf_counter()
    fns = verify._make_device_fns()
    warm_s = time.perf_counter() - t0
    placed = [set() for _ in fns]

    def spy(i, f):
        def g(d, s, p, n):
            out = f(d, s, p, n)
            placed[i].update(dev.id for dev in out.devices())
            return out

        g.jitted = getattr(f, "jitted", f)
        return g

    verify._fns = [spy(i, f) for i, f in enumerate(fns)]
    sink = SinkTile(record=True)
    topo = Topology()
    depth = 1 << 13
    topo.link("replay_verify", depth=depth, mtu=wire.LINK_MTU)
    topo.link("verify_dedup", depth=depth, mtu=wire.LINK_MTU)
    topo.link("dedup_sink", depth=depth, mtu=wire.LINK_MTU)
    topo.tile(ReplayTile(pcap_path, total=total), outs=["replay_verify"])
    topo.tile(verify, ins=[("replay_verify", True)], outs=["verify_dedup"])
    topo.tile(DedupTile(depth=1 << 20), ins=[("verify_dedup", True)],
              outs=["dedup_sink"])
    topo.tile(sink, ins=[("dedup_sink", True)])
    topo.build()
    topo.start()
    try:
        mv, ms = topo.metrics("verify"), topo.metrics("sink")
        t0 = time.perf_counter()
        deadline = time.monotonic() + 300.0
        while time.monotonic() < deadline:
            topo.poll_failure()
            # done = every txn has its verdict AND every accepted one
            # has reached the sink.  halt() does not drain the rings: a
            # frag still in flight when it is called is simply dropped
            out = int(mv.counter("out_frags"))
            if (out + int(mv.counter("verify_fail_txns")) == total
                    and int(ms.counter("in_frags")) == out):
                break
            time.sleep(0.01)
        run_s = time.perf_counter() - t0
        topo.halt()
        counters = {k: int(mv.counter(k)) for k in (
            "verified_sigs", "verify_fail_txns", "device_batches",
            "fallback_batches", "device_errors", "pool_resubmits",
            "out_frags",
        )}
        counters["dedup_dup_txns"] = int(
            topo.metrics("dedup").counter("dup_txns"))
        counters["sink_in_frags"] = int(ms.counter("in_frags"))
        landed = [int(mv.counter(f"dev{i}_landed"))
                  for i in range(verify.n_devices)]
        tags = sink.all_sigs()
    finally:
        topo.close()
    local = jax.local_devices()
    return dict(
        tags=tags, counters=counters, landed=landed,
        placed=[sorted(s) for s in placed],
        want_ids=[local[d].id for d in verify.device_indices],
        warm_s=round(warm_s, 2), run_s=round(run_s, 2),
    )


def phase_pool4(sz: Sizes, seed: int, rehearse: bool, workdir: str) -> dict:
    import numpy as np

    log, dev = _start_jax_phase(rehearse)
    from firedancer_tpu.tiles.bench import make_transfer_pool
    from firedancer_tpu.waltz import pcap

    check(dev["count"] >= 4, f"--chips 4 needs four devices, found {dev}")
    rows, _ = make_transfer_pool(
        sz.pool_txns, n_signers=min(sz.n_accounts, sz.pool_txns), seed=seed,
    )
    rng = np.random.default_rng(seed + 3)
    bad = rng.choice(sz.pool_txns, sz.pool_bad, replace=False)
    rows[bad, 1 + rng.integers(0, 64, len(bad))] ^= 0x40
    path = os.path.join(workdir, "pool4.pcap")
    w = pcap.PcapWriter(path)
    for i in range(len(rows)):
        w.write(rows[i].tobytes(), ts_us=i)
    w.close()

    wide = _pool_run(sz, path, sz.pool_txns, 4)
    one = _pool_run(sz, path, sz.pool_txns, 1)
    res = dict(
        device=dev, txns=sz.pool_txns, corrupted=sz.pool_bad,
        width4={k: wide[k] for k in
                ("counters", "landed", "placed", "want_ids",
                 "warm_s", "run_s")},
        width1={k: one[k] for k in
                ("counters", "landed", "placed", "warm_s", "run_s")},
        published=len(wide["tags"]),
        order_equal=bool(np.array_equal(wide["tags"], one["tags"])),
        programs=log.big(), compile=log.totals(),
    )
    say("pool4", device=dev, txns=sz.pool_txns, corrupted=sz.pool_bad)
    say("pool4", width=4, **wide["counters"], landed=wide["landed"],
        placed=wide["placed"], warm_s=wide["warm_s"], run_s=wide["run_s"])
    say("pool4", width=1, **one["counters"], landed=one["landed"],
        placed=one["placed"], warm_s=one["warm_s"], run_s=one["run_s"])
    say("pool4", published=res["published"], order_equal=res["order_equal"],
        **res["compile"])
    for fname, p in res["programs"].items():
        say("pool4", program=fname, **p)

    ids = [p[0] for p in wide["placed"] if len(p) == 1]
    check(len(ids) == 4 and len(set(ids)) == 4,
          f"the four domains' arrays lived on devices {wide['placed']}")
    check(ids == wide["want_ids"],
          f"arrays on {ids}, ordinals name {wide['want_ids']}")
    check(all(n > 0 for n in wide["landed"]),
          f"a device landed nothing: dev_landed {wide['landed']}")
    for name, r in (("width 4", wide), ("width 1", one)):
        cnt = r["counters"]
        check(cnt["fallback_batches"] == 0 and cnt["device_errors"] == 0,
              f"{name}: the device path degraded: {cnt}")
        check(cnt["verify_fail_txns"] == sz.pool_bad,
              f"{name}: rejected {cnt['verify_fail_txns']}, corrupted "
              f"{sz.pool_bad}")
    check(res["published"] == sz.pool_txns - sz.pool_bad,
          f"published {res['published']}")
    check(res["order_equal"],
          "publish order or verdicts differ between width 4 and width 1")
    return res


# ---------------------------------------------------------------------------
# the runner

PHASES = {
    "kernel": phase_kernel,
    "corpus": phase_corpus,
    "leader-thread": functools.partial(phase_leader, runtime="thread"),
    "leader-process": functools.partial(phase_leader, runtime="process"),
    "ingress": phase_ingress,
    "pool4": phase_pool4,
}
DEFAULT_RUN = ("kernel", "corpus", "leader-thread", "leader-process",
               "ingress")
#: seconds a phase may take before its process group is killed
PHASE_CAP_S = {"kernel": 500, "corpus": 300, "leader-thread": 600,
               "leader-process": 500, "ingress": 300, "pool4": 900}
_RESULT_TAG = "PHASE_RESULT "


def run_phase_here(name: str, sz: Sizes, seed: int, rehearse: bool,
                   workdir: str) -> int:
    """Body of a phase child: run, print the result line, exit code."""
    try:
        res = PHASES[name](sz, seed, rehearse, workdir)
    except PhaseFailed as e:
        print(f"chip_smoke {name}: FAILED: {e}", flush=True)
        return EXIT_FAILED
    print(_RESULT_TAG + json.dumps(res, default=str), flush=True)
    return 0


def _run_child(name: str, args, workdir: str, cap_s: float):
    """One phase as a child process in its own process group; echoes its
    output; returns (exit code, result dict or None, seconds).  The
    group is killed when the phase ends, however it ends: nothing this
    script started outlives it."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name,
           "--workdir", workdir, "--seed", str(args.seed)]
    if args.rehearse:
        cmd.append("--rehearse")
    t0 = time.monotonic()
    p = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                         start_new_session=True)
    timer = threading.Timer(cap_s, os.killpg, (p.pid, signal.SIGKILL))
    timer.start()
    result = None
    try:
        for line in p.stdout:
            if line.startswith(_RESULT_TAG):
                result = json.loads(line[len(_RESULT_TAG):])
            else:
                print(line, end="", flush=True)
        rc = p.wait()
    finally:
        timer.cancel()
        try:
            os.killpg(p.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    return rc, result, time.monotonic() - t0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the four-chip verify pool and its width-1 "
                    "comparison, and no other phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny size; never a chip run")
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    ap.add_argument("--workdir", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    sz = TINY if args.rehearse else FULL
    if args.phase:
        return run_phase_here(args.phase, sz, args.seed, args.rehearse,
                              args.workdir)

    names = ("pool4",) if args.chips == 4 else DEFAULT_RUN
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    t_start = time.monotonic()
    results, device = {}, None
    try:
        for name in names:
            left = BUDGET_S - (time.monotonic() - t_start)
            rc, res, secs = _run_child(
                name, args, workdir, min(PHASE_CAP_S[name], max(left, 1.0))
            )
            # teardown is part of the result: a phase that printed its
            # result and then died in interpreter/runtime teardown has a
            # non-zero (or signal) exit code and fails here
            print(f"chip_smoke {name}: seconds={secs:.1f} exit={rc}",
                  flush=True)
            if rc != 0 or res is None:
                print(f"chip_smoke: FAILED in phase {name} (exit {rc})",
                      flush=True)
                return EXIT_FAILED if rc == EXIT_FAILED else EXIT_CRASHED
            results[name] = res
            if device is None and "device" in res:
                device = {k: res["device"][k]
                          for k in ("platform", "kind", "count")}
        _cross_phase_checks(results)
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", flush=True)
        return EXIT_FAILED
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(f"chip_smoke: total_seconds={time.monotonic() - t_start:.1f}",
          flush=True)
    if args.rehearse or device["platform"] != "tpu":
        print(f"chip_smoke: REHEARSAL PASSED on {device} - not a chip run",
              flush=True)
        return EXIT_REHEARSED
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def _cross_phase_checks(results: dict) -> None:
    """The compile cache really is shared: the verify program phase (a)
    compiled is a persistent-cache hit when the leader phase boots."""
    a, b = results.get("kernel"), results.get("leader-thread")
    if not (a and b):
        return
    name = "jit(verify_batch_digest)"
    pa, pb = a["programs"].get(name), b["programs"].get(name)
    check(pa and pb, f"{name} missing from a phase's compile log")
    say("cache", program=name,
        kernel=dict(cache=pa["cache"], compile_s=pa["compile_s"],
                    trace_lower_s=round(pa["trace_s"] + pa["lower_s"], 2)),
        leader_thread=dict(
            cache=pb["cache"], compile_s=pb["compile_s"],
            trace_lower_s=round(pb["trace_s"] + pb["lower_s"], 2)))
    check(pb["cache"] == "hit",
          f"the leader phase did not find {name} in the compile cache "
          f"phase (a) wrote ({pb})")
    if pa["cache"] == "miss":
        check(pb["compile_s"] < pa["compile_s"],
              f"cache hit ({pb['compile_s']} s) not faster than the cold "
              f"compile ({pa['compile_s']} s)")


if __name__ == "__main__":
    sys.exit(main())
