"""The TPU sig-verify bridge tile — this build's analog of the reference's
verify tile (src/app/fdctl/run/tiles/fd_verify.c) and of the wiredancer
FPGA offload (src/wiredancer/c/wd_f1.c).

Round-3 redesign: ASYNCHRONOUS push-request / push-result dispatch, the
defining wiredancer property (src/wiredancer/README.md "Pipeline Design":
the ring never waits on the accelerator).  The mux loop stages host-side
work (gather, trailer parse, lane expansion) and pushes prepared batches
to a device worker thread; the worker keeps several batches in flight
(dispatch N+1 while N computes — JAX dispatch is async; a batch lands
when its device-to-host copy returns) and lands results on a lock-free
deque; the mux loop publishes landed results downstream as
credits allow.  Upstream backpressure propagates through `in_budget`:
when no device can take another full batch the tile stops draining its
in-ring and the ring's credit model takes over — exactly the reference's
flow-control discipline, with the device behind the same tile/link
boundary.

`VerifyTile._submit_staged` is the one place a staged batch is handed
to a device, and holds the rule for it: a full batch may queue behind
others, a partial one waits staged for the land of the batch in flight.

Round-6 scale-out: the single worker became a DEVICE POOL (`_DevicePool`)
— one worker thread (with its own in-flight pipeline, i.e. the double
buffer) per local accelerator, a least-in-flight scheduler with
round-robin tie-break, an in-flight cap per device, and an in-order
landing buffer so results still publish in arrival-seq order across
devices.  Each device is its own FAULT DOMAIN (`DevicePolicy`): a device
that errors or stalls past its patience is quarantined with capped
backoff and its in-flight batches are resubmitted to healthy devices;
the strict host path (ops/ed25519/hostpath.py) remains the last resort
when every device is out.  The per-signature kernel is bound by the
chip's integer ALU throughput, so past one chip's ceiling the lever is
more chips: this layer is what turns N devices into an aggregate — the
same conclusion that drove the reference to scale sig-verify across
tiles and wiredancer FPGA lanes.

Batch discipline: every batch is padded to `max_lanes`, the one shape the
boot compiles and warms, and goes to the device with its real lane count:
the kernel runs only the 256-lane tiles that hold a real lane, so a small
batch costs a small batch's kernel time.  All per-frag work is vectorized
numpy; the Python loop body is O(1) per batch.
"""

from __future__ import annotations

import collections
import contextlib
import inspect
import queue
import threading
import time

import numpy as np

from firedancer_tpu.disco import trace as SPAN
from firedancer_tpu.disco.metrics import MetricsSchema, device_counters
from firedancer_tpu.disco.mux import MuxCtx, Tile, now_ts, ns_to_ts, ts_diff
from firedancer_tpu.ops.ed25519 import TILE as KERNEL_TILE
from firedancer_tpu.tango import rings as R
from firedancer_tpu.tango.tempo import tickcount

from . import wire

#: where a device batch's time goes, sampled once per batch when its last
#: verdict is published (wide log2 hists, compressed-us clock of now_ts):
#: t_first -> t_submit (staged: the submit rule's hold for a land, or for a
#: device below its depth), t_submit -> t_disp (the worker's request
#: queue), t_disp -> t_land (H2D, the batches ahead on the chip, the
#: kernel, D2H), t_land -> t_pub (the mux thread's turn, then out-link
#: credits)
BATCH_HISTS = (
    "batch_fill_us", "batch_queue_us", "batch_inflight_us", "batch_drain_us",
)
#: beside that chain, the part of t_land -> t_pub a batch spent parked in
#: the pool's reorder buffer: t_taken (the mux thread took it from its
#: worker) -> t_rel (the pool released it in pool_seq order).  One clock
#: read a poll that takes anything, so it is 0 for a batch released by the
#: poll that took it: every batch of a one-device pool.  Sampled as the
#: batch lands (_land_batch), where `device_batches` and
#: `reordered_batches` (batches some poll left parked behind an earlier
#: pool_seq) are counted
REORDER_HIST = "batch_reorder_us"
#: the mux thread's self-time by phase, wall ns (tango.tempo.tickcount):
#: on_frags up to staging; _submit_front; _land_results and _publish_ready
#: on the iterations that landed or published something; and the wall time
#: during which no device could take a full batch (_pool_open): the tile
#: then leaves its frags in the ring (in_budget)
PHASE_COUNTERS = (
    "expand_ns", "submit_ns", "results_ns", "publish_ns", "pool_full_ns",
)
#: how a device batch came to be submitted (_submit_staged), counted where
#: `device_batches` is, when it lands: `full_batches` went out full (the
#: throughput regime: queueing behind other batches is real work);
#: `held_batches` are partial batches whose submit waited for a batch in
#: flight to land; `spread_batches` are partial batches that went to an
#: idle device while another batch was in flight in the pool, because
#: they filled a kernel tile.  The rest of `device_batches` are partial
#: batches that found the whole pool idle and went at once (trickle), and
#: halt's flush.
SUBMIT_COUNTERS = ("held_batches", "full_batches", "spread_batches")
REORDER_COUNTER = "reordered_batches"
#: batches a device may have in flight for a PARTIAL batch still to be
#: submitted to it.  1: a partial batch never queues behind another; it
#: waits staged (and grows) for the land instead.  2 would hide the ~2 ms
#: of dispatch + H2D under the running batch and cost every txn one more
#: batch time; PERF.md (PR 26) has both readings on the chip.
#: A partial batch under one kernel tile also waits while any device of
#: the pool has a batch in flight (_submit_staged).
PARTIAL_AHEAD = 1
#: what of a batch's meta outlives its landing, until its last publish
_LIFE_KEYS = (
    "t_first", "t_submit", "t_disp", "t_land", "t_dev", "pool_seq", "lanes",
)

#: reference: VERIFY_TCACHE_DEPTH 16 (fd_verify.h:6) — a tiny per-tile
#: pre-dedup catching back-to-back duplicates before they burn device time
PRE_DEDUP_DEPTH = 16

_STOP = object()

_NULL_SPAN = contextlib.nullcontext()


def _tiles(lanes: int) -> int:
    """Kernel tiles a batch of `lanes` real lanes costs the device:
    pallas_kernel.verify_core runs whole tiles up to the last real lane."""
    return -(-lanes // KERNEL_TILE)


def _no_span(name: str, **kw):
    """The span factory of a process that holds no JAX backend: the
    per-batch `fdt.verify.*` spans (VerifyTile._span) cost one call."""
    return _NULL_SPAN


def _takes_count(fn) -> bool:
    """Whether a device fn can be handed a batch's lane count after its
    three arrays.  The tile's own fns can (verify_batch_digest's `n_lanes`),
    and so can the host verifier standing in for a device (`lanes`); a stub
    written for the three arrays is called with them alone, and computes
    every padded lane as it always did."""
    try:
        params = inspect.signature(fn).parameters.values()
    except (TypeError, ValueError):  # no signature to read (or no fn)
        return True
    return any(p.kind is p.VAR_POSITIONAL for p in params) or 4 <= sum(
        p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD) for p in params
    )


class FallbackPolicy:
    """Graceful degradation for the batched device-verify path.

    Wraps the device dispatch in a catch → host-retry → circuit-trip
    state machine: a TPU/Pallas dispatch (or D2H sync) error reroutes
    THAT batch through the strict host verifier
    (ops/ed25519/hostpath.py) instead of killing the tile; `trip_after`
    consecutive device failures latch host-only mode, and every
    `reprobe_every` batches one batch re-probes the device so a
    recovered accelerator is picked back up automatically.

    `fault_hook` is the faultinj device_error injection point — called
    once per device-batch attempt, raising a scripted DeviceFault that
    exercises exactly the production failure path.

    Counter attributes are mirrored into the tile's shared metrics
    (fallback_batches etc.) by VerifyTile so a monitor process sees the
    degradation state live.
    """

    #: set by the pool's stall watchdog while a device call is wedged
    #: past its patience (DevicePolicy only; the classic single-device
    #: policy never stalls — its worker's host fallback is in-line)
    stalled = False

    def __init__(
        self,
        device_fn,
        host_fn,
        *,
        trip_after: int = 3,
        reprobe_every: int = 64,
        fault_hook=None,
    ):
        self.device_fn = device_fn
        #: a batch's args are its three padded arrays and its real lane
        #: count (VerifyTile._submit_front); how many of them device_fn takes
        self._dev_argc = 4 if _takes_count(device_fn) else 3
        self.host_fn = host_fn
        self.trip_after = max(trip_after, 1)
        self.reprobe_every = max(reprobe_every, 1)
        self.fault_hook = fault_hook
        self.consec_failures = 0
        self.tripped = False  # latched host-only mode
        self._since_trip = 0
        # counters (mirrored into metrics by the owning tile)
        self.fallback_batches = 0
        self.device_errors = 0
        self.device_trips = 0
        self.host_reprobes = 0

    def healthy(self, now: float | None = None) -> bool:
        """Schedulable by the pool.  The classic policy always is — it
        degrades to the host path internally, per batch."""
        return True

    def _try_device(self) -> bool:
        if self.device_fn is None:
            return False
        if not self.tripped:
            return True
        self._since_trip += 1
        if self._since_trip >= self.reprobe_every:
            self._since_trip = 0
            self.host_reprobes += 1
            return True
        return False

    def _device_failed(self) -> None:
        self.device_errors += 1
        self.consec_failures += 1
        if (
            not self.tripped
            and self.consec_failures >= self.trip_after
        ):
            self.tripped = True
            self.device_trips += 1
            self._since_trip = 0

    def dispatch(self, args):
        """Start a batch.  Device dispatch is async (returns a future);
        the host path defers all work to land()."""
        if self._try_device():
            try:
                if self.fault_hook is not None:
                    self.fault_hook()
                return ("dev", self.device_fn(*args[: self._dev_argc]))
            except Exception:
                self._device_failed()
        return ("host", None)

    def land(self, fut, args, lanes: int | None = None) -> np.ndarray:
        """Finish a batch: sync the device future (where JAX's async
        dispatch surfaces runtime errors) or run the host verifier."""
        kind, val = fut
        if kind == "dev":
            try:
                out = np.asarray(val)
                self.consec_failures = 0
                if self.tripped:
                    self.tripped = False  # re-probe succeeded: recovered
                return out
            except Exception:
                self._device_failed()
        if self.device_fn is not None:
            # fallback_batches measures DEGRADATION — batches a
            # configured device failed to serve.  An intentional
            # host-only tile (device="off") is healthy, not degraded:
            # counting it would leave monitors alarming forever on
            # CPU-only deployments.
            self.fallback_batches += 1
        return self.host_fn(*args[:3], lanes=lanes)


class DevicePolicy(FallbackPolicy):
    """One device's FAULT DOMAIN inside a multi-device pool.

    Differs from the classic FallbackPolicy in who owns recovery: the
    classic policy reroutes a failed batch to the host path itself; a
    pool domain hands the batch BACK (dispatch/land return a failure)
    so the scheduler can resubmit it to a HEALTHY device first and only
    fall to the host when every device is out.  The breaker is
    time-based: `trip_after` consecutive failures quarantine the device
    for a capped-exponential backoff (`backoff_base_s`..`backoff_max_s`),
    after which the next scheduled batch re-probes it.

    `stall_patience_s` is how long one device call may stay wedged
    before the pool gives up on it: past the patience only ITS device
    degrades (the pool marks `stalled`, quarantines, and redistributes
    its in-flight batches); the other devices keep verifying.  The
    120 s default is not measured on this installation (ROADMAP D4).
    """

    def __init__(
        self,
        device_fn,
        host_fn,
        *,
        index: int = 0,
        trip_after: int = 3,
        backoff_base_s: float = 0.5,
        backoff_max_s: float = 30.0,
        stall_patience_s: float = 120.0,
        fault_hook=None,
    ):
        super().__init__(
            device_fn, host_fn, trip_after=trip_after, fault_hook=fault_hook
        )
        self.index = index
        self.backoff_base_s = backoff_base_s
        self.backoff_max_s = backoff_max_s
        self.stall_patience_s = stall_patience_s
        self.backoff_s = 0.0
        self.quarantined_until = 0.0
        self.stalled = False
        self.device_stalls = 0

    def healthy(self, now: float | None = None) -> bool:
        if self.stalled or self.device_fn is None:
            return False
        if not self.tripped:
            return True
        if now is None:
            now = time.monotonic()
        return now >= self.quarantined_until  # backoff expired: re-probe

    def _try_device(self) -> bool:
        if self.device_fn is None or self.stalled:
            return False
        if not self.tripped:
            return True
        if time.monotonic() >= self.quarantined_until:
            self.host_reprobes += 1  # (re-)probe of a quarantined device
            return True
        return False

    def _quarantine(self) -> None:
        """Trip the breaker with capped exponential backoff: each failed
        (re-)probe doubles the backoff, a success (in land) resets it."""
        if not self.tripped:
            self.device_trips += 1
        self.tripped = True
        self.backoff_s = (
            self.backoff_base_s
            if not self.backoff_s
            else min(self.backoff_s * 2.0, self.backoff_max_s)
        )
        self.quarantined_until = time.monotonic() + self.backoff_s

    def _device_failed(self) -> None:
        self.device_errors += 1
        self.consec_failures += 1
        if self.consec_failures >= self.trip_after:
            self._quarantine()

    def mark_stalled(self) -> None:
        """Pool stall watchdog: the device call is wedged past patience.
        Quarantine so the scheduler routes around it; the flag clears
        when the wedged call finally returns (the worker owns that)."""
        self.stalled = True
        self.device_stalls += 1
        self._quarantine()

    def dispatch(self, args):
        if self._try_device():
            try:
                if self.fault_hook is not None:
                    self.fault_hook(self.index)
                return ("dev", self.device_fn(*args[: self._dev_argc]))
            except Exception:
                self._device_failed()
                return ("fail", None)
        return ("fail", None)  # quarantined: the pool redistributes

    def land(self, fut, args, lanes: int | None = None):
        kind, val = fut
        if kind == "dev":
            try:
                out = np.asarray(val)
                self.consec_failures = 0
                self.tripped = False
                self.backoff_s = 0.0
                return out
            except Exception:
                self._device_failed()
                return None  # the pool resubmits elsewhere
        if kind == "host":
            if self.device_fn is not None:
                self.fallback_batches += 1
            return self.host_fn(*args[:3], lanes=lanes)
        return None  # "fail": never dispatched (quarantine raced)


class _DeviceWorker:
    """Push-request/push-result engine (the wd_f1.c interface shape).

    One dedicated thread owns all interaction with ONE device.  Up to
    `depth` batches ride in flight (the pool's cap on `inflight()`): the
    thread dispatches every queued request before it blocks on the
    oldest result's D2H copy, so transfer and compute of batch N+1
    overlap the sync of batch N (the double buffer).  All dispatch/land
    calls go through the policy, so a device failure degrades (classic)
    or surfaces to the pool (DevicePolicy) instead of killing this
    thread.

    Accounting contract: every submitted batch is exactly one of
    landed (a results entry), still queued/in flight (visible in
    `reqq`/`pending`), or drained back by `abort()` — never silently
    dropped.  `pending` entries are appended BEFORE dispatch and popped
    only AFTER their land completes, so a wedge inside a device call
    keeps that batch recoverable.
    """

    def __init__(self, policy: FallbackPolicy, depth: int = 3,
                 name: str = "verify-dev", span=_no_span):
        self.policy = policy
        self.depth = depth
        #: host-span factory (VerifyTile._span): `fdt.verify.dispatch` and
        #: `fdt.verify.land` wrap this thread's two device calls
        self.span = span
        self.reqq: queue.Queue = queue.Queue(maxsize=depth)
        self.results: collections.deque = collections.deque()
        self.pending: collections.deque = collections.deque()
        self.error: BaseException | None = None
        self.aborted = False
        #: single-writer counters: submitted_n by the submitting (mux)
        #: thread, completed_n by this worker thread; the difference is
        #: the in-flight load the scheduler balances on
        self.submitted_n = 0
        self.completed_n = 0
        #: landed batches accepted by the pool (pool/mux thread only)
        self.landed_n = 0
        #: monotonic timestamp while inside a device call — dispatch
        #: (its H2D put can block) or land (the D2H sync) — read by the
        #: pool's stall watchdog; 0.0 = not in a call
        self.land_t0 = 0.0
        self.thread = threading.Thread(
            target=self._main, name=name, daemon=True
        )
        self.thread.start()

    def inflight(self) -> int:
        return self.submitted_n - self.completed_n

    def alive(self) -> bool:
        return self.error is None and self.thread.is_alive()

    def submit(self, meta, args, mode: str = "auto") -> None:
        """Single-submitter (mux thread); the pool submits only while
        inflight() is below the depth, which keeps `reqq` (as deep)
        from ever being full, so this never blocks."""
        self.reqq.put_nowait((meta, args, mode))
        self.submitted_n += 1

    def stop(self, timeout_s: float | None = None) -> None:
        deadline = (
            None if timeout_s is None else time.monotonic() + timeout_s
        )
        while self.thread.is_alive():
            try:
                self.reqq.put(_STOP, timeout=0.1)
                break
            except queue.Full:
                # a dead worker never drains: is_alive re-checks.  A
                # WEDGED worker never drains either — the deadline must
                # bound this loop too, or a stop under a full queue
                # spins forever and the halt path never returns
                if deadline is not None and time.monotonic() >= deadline:
                    break
        self.thread.join(
            None if deadline is None
            else max(deadline - time.monotonic(), 0.0)
        )

    def abort(self, timeout_s: float = 10.0) -> list[tuple]:
        """Teardown that cannot orphan work: stop (or abandon, if
        wedged) the thread, then drain every batch it never landed —
        queued submissions AND the in-flight `pending` entries (a land
        wedged inside a device call keeps its batch there) — back to
        the caller for resubmission or deliberate discard."""
        self.aborted = True
        try:
            self.reqq.put_nowait(_STOP)
        except queue.Full:
            pass
        self.thread.join(timeout=timeout_s)
        drained: list[tuple] = []
        while True:
            try:
                item = self.reqq.get_nowait()
            except queue.Empty:
                break
            if item is not _STOP:
                drained.append(item)
        # liveness BEFORE the pending snapshot: a slow-but-not-wedged
        # worker can finish its in-flight land right after the join
        # timeout — snapshotting first would count that batch in both
        # completed_n and drained and fire the assert spuriously.  Once
        # dead here, counters and pending are final.  A still-alive
        # thread (wedged, or merely slower than the join timeout) can
        # popleft/append concurrently, so the snapshot retries on the
        # deque's mutated-during-iteration error rather than letting it
        # escape into the crash-recovery path.
        alive = self.thread.is_alive()
        while True:
            try:
                snap = [(m, a, md) for m, a, md, _ in self.pending]
                break
            except RuntimeError:
                continue
        drained.extend(snap)
        if not alive:
            # the thread exited: counters are final — prove no batch
            # was silently dropped (the pre-fix abort lost queued metas
            # when a land wedged)
            assert self.submitted_n == self.completed_n + len(drained), (
                f"device worker dropped batches: submitted "
                f"{self.submitted_n} != landed {self.completed_n} + "
                f"drained {len(drained)}"
            )
        return drained

    def _main(self) -> None:
        pending = self.pending
        stopped = False
        try:
            while not (stopped and not pending):
                if self.aborted:
                    return
                while not stopped and len(pending) < self.depth:
                    try:
                        item = self.reqq.get(
                            block=not pending, timeout=0.02
                        )
                    except queue.Empty:
                        break
                    if item is _STOP:
                        stopped = True
                        break
                    meta, args, mode = item
                    # enter the accounting BEFORE dispatch: a dispatch
                    # that wedges must leave the batch recoverable
                    slot = [meta, args, mode, None]
                    pending.append(slot)
                    # the batch's lifecycle stamps ride the meta dict
                    # (plain writes on this worker thread); the MUX
                    # thread samples them into the batch_*_us hists and
                    # turns them into DISPATCH/LAND span events — the
                    # metrics region and the span ring stay single-writer
                    meta["t_disp"] = now_ts()
                    meta["t_dev"] = getattr(self.policy, "index", 0)
                    if mode == "host":
                        slot[3] = ("host", None)
                    else:
                        # async dispatch returns at once when healthy,
                        # but the H2D put inside it can block on a sick
                        # device, so the watchdog window covers it too
                        self.land_t0 = time.monotonic()
                        # `tiles` is what the batch asks of the device
                        # (0 on a host-only policy); a dispatch that
                        # fails over to the host says 0 on its land
                        with self.span(
                            "fdt.verify.dispatch",
                            seq=meta.get("pool_seq", 0),
                            lanes=meta["lanes"],
                            txns=meta.get("txns", 0),
                            tiles=_tiles(meta["lanes"])
                            if self.policy.device_fn is not None else 0,
                            dev=meta["t_dev"],
                        ):
                            slot[3] = self.policy.dispatch(args)
                        self.land_t0 = 0.0
                if pending:
                    meta, args, mode, fut = pending[0]
                    if fut is None:  # pragma: no cover - abort raced
                        fut = ("fail", None)
                    # land = the D2H copy of the verdicts: it returns
                    # when the batch has run, and it is where an async
                    # dispatch surfaces a runtime error
                    self.land_t0 = time.monotonic()
                    # the tiles the device computed: none where the host
                    # path serves the batch, at dispatch or at land
                    tiles = _tiles(meta["lanes"]) if fut[0] == "dev" else 0
                    fallbacks = self.policy.fallback_batches
                    with self.span(
                        "fdt.verify.land",
                        seq=meta.get("pool_seq", 0),
                        lanes=meta["lanes"],
                        txns=meta.get("txns", 0),
                        tiles=tiles,
                        dev=meta["t_dev"],
                    ):
                        ok = self.policy.land(fut, args, meta["lanes"])
                    self.land_t0 = 0.0
                    if self.policy.fallback_batches != fallbacks:
                        tiles = 0
                    meta["tiles"] = tiles
                    meta["t_land"] = now_ts()
                    self.policy.stalled = False  # the call returned
                    self.completed_n += 1
                    pending.popleft()
                    self.results.append((meta, ok))
        except BaseException as e:  # noqa: BLE001 — surfaced by the tile
            self.error = e


class _DevicePool:
    """N per-device workers behind one submit/land facade.

    Scheduler: least-in-flight across healthy domains, ties broken
    round-robin.  A device is open while its `inflight()` (submitted and
    not yet landed: queued, dispatched or running) is below a cap: the
    pool's `depth` by default, or the caller's lower `ahead` (the tile's
    submit rule gives a partial batch PARTIAL_AHEAD, and holds one under
    a kernel tile while `inflight()` is above 0).  When no device is
    healthy, batches go out in `mode="host"` — the strict host path as
    last resort — on any responsive worker, under the same caps.

    Landing is IN ORDER: every batch gets a monotonically increasing
    `pool_seq` at first submit; completed batches park in a reorder
    buffer and `ready` hands them out strictly by seq, so downstream
    publish order is identical to a single serialized stream no matter
    how devices interleave.  The buffer keeps its own account in the
    batch's meta: `t_taken` / `t_rel` (taken from its worker, released
    in order; one clock read a poll, so equal unless it waited),
    `parked` (a poll left it behind an earlier seq) and `t_dev` (the
    domain whose result was accepted), which the tile samples as the
    batch lands (REORDER_HIST, REORDER_COUNTER, dev{i}_landed).

    Fault handling: a failed batch (device error) or a quarantined/
    stalled/dead domain's in-flight work is resubmitted — same seq —
    to another domain.  Late results from a domain a batch was moved
    away from are dropped by an assignment check, which is what makes
    "zero lost, zero duplicated" hold through stall recovery races.

    Thread model: submit/poll/abort run on the owning tile's mux
    thread only; workers touch only their own queues/results.
    """

    def __init__(self, policies: list, depth: int = 3, name: str = "verify",
                 span=_no_span):
        self.policies = policies
        self.depth = depth
        self.workers = [
            _DeviceWorker(p, depth, name=f"{name}-dev{i}", span=span)
            for i, p in enumerate(policies)
        ]
        self.aborted = False
        self.next_seq = 0
        self.landed_seq = 0
        self.reorder: dict[int, tuple] = {}
        #: seq -> [meta, args, mode, domain_idx]; the live assignment
        self.outstanding: dict[int, list] = {}
        #: evicted batches waiting for a domain with room
        self.retryq: collections.deque = collections.deque()
        #: in-order completed batches, consumed by the tile
        self.ready: collections.deque = collections.deque()
        self.rr = 0
        self.resubmits = 0
        self.late_results = 0
        self._evicted: set[int] = set()
        self._stopping = False

    # ---- scheduling -----------------------------------------------------

    def _domain_ok(self, i: int) -> bool:
        w = self.workers[i]
        return w.alive() and not self.policies[i].stalled

    def _pick(
        self, peek: bool = False, ahead: int | None = None
    ) -> tuple[int | None, str]:
        """The least-loaded schedulable domain with fewer than `ahead`
        batches in flight (None: the pool's depth), or None."""
        cap = self.depth if ahead is None else min(ahead, self.depth)
        now = time.monotonic()
        n = len(self.workers)
        cand = [
            i for i in range(n)
            if self._domain_ok(i) and self.policies[i].healthy(now)
        ]
        mode = "auto"
        if not cand:
            # every device quarantined/stalled/dead: strict host path on
            # any still-responsive worker is the last resort
            mode = "host"
            cand = [i for i in range(n) if self._domain_ok(i)]
        open_ = [i for i in cand if self.workers[i].inflight() < cap]
        if not open_:
            return None, mode
        best, best_load = None, None
        for j in range(len(open_)):
            i = open_[(self.rr + j) % len(open_)]
            load = self.workers[i].inflight()
            if best is None or load < best_load:
                best, best_load = i, load
        if not peek:
            self.rr = (self.rr + 1) % max(n, 1)
        return best, mode

    def can_accept(self, ahead: int | None = None) -> bool:
        """Room for NEW work behind fewer than `ahead` batches (None:
        for a full batch, anywhere below the depth): evicted batches
        retry first (publishing is seq-ordered, so head-of-line seqs
        must not starve)."""
        if self.retryq:
            return False
        return self._pick(peek=True, ahead=ahead)[0] is not None

    def inflight(self) -> int:
        """Batches in flight anywhere in the pool: the schedulable
        domains' `inflight()` and the evicted batches in `retryq`.  A
        dead or stalled domain is left out: its batches count where they
        were moved, so a domain that never lands again holds nothing."""
        return len(self.retryq) + sum(
            w.inflight() for i, w in enumerate(self.workers)
            if self._domain_ok(i)
        )

    def submit(self, meta, args) -> bool:
        """Schedule one new batch on the least-loaded open device (the
        one can_accept(ahead) found, if the caller asked it first);
        False = no capacity (caller holds it staged and retries — ring
        backpressure does the rest)."""
        self.pump()
        if self.retryq:
            return False
        tgt, mode = self._pick()
        if tgt is None:
            return False
        seq = self.next_seq
        self.next_seq += 1
        meta["pool_seq"] = seq
        # stamped BEFORE the worker can see the batch, so t_submit <=
        # t_disp holds; a resubmission keeps the first acceptance
        meta["t_submit"] = now_ts()
        self.outstanding[seq] = [meta, args, mode, tgt]
        self.workers[tgt].submit(meta, args, mode)
        return True

    def pump(self) -> None:
        """Re-place evicted batches as capacity frees up."""
        while self.retryq:
            tgt, mode = self._pick()
            if tgt is None:
                return
            seq = self.retryq.popleft()
            ent = self.outstanding.get(seq)
            if ent is None:  # pragma: no cover - landed while queued
                continue
            ent[2], ent[3] = mode, tgt
            self.workers[tgt].submit(ent[0], ent[1], mode)

    def _resubmit(self, seq: int) -> None:
        ent = self.outstanding[seq]
        self.resubmits += 1
        tgt, mode = self._pick()
        if tgt is None:
            ent[3] = -1  # unassigned: parked until capacity frees
            self.retryq.append(seq)
            return
        ent[2], ent[3] = mode, tgt
        self.workers[tgt].submit(ent[0], ent[1], mode)

    def _evict(self, i: int) -> None:
        """Move every batch assigned to domain i elsewhere (quarantine /
        dead worker).  Late results from i are dropped by the
        assignment check in poll()."""
        for seq, ent in list(self.outstanding.items()):
            if ent[3] == i:
                self._resubmit(seq)

    # ---- landing --------------------------------------------------------

    def _drain_results(self, i: int, w: _DeviceWorker, t_taken: int) -> None:
        while w.results:
            meta, ok = w.results.popleft()
            seq = meta["pool_seq"]
            ent = self.outstanding.get(seq)
            if ent is None or ent[3] != i:
                # a batch this domain lost to resubmission landed
                # anyway (stall recovered): first landing won
                self.late_results += 1
                continue
            if ok is None:
                self._resubmit(seq)  # device failed it: try elsewhere
                continue
            del self.outstanding[seq]
            w.landed_n += 1
            # the domain whose result the pool ACCEPTED (a batch moved
            # here from a stalled domain keeps no trace of that one)
            meta["t_dev"] = i
            meta["t_taken"] = t_taken
            meta["parked"] = False
            self.reorder[seq] = (meta, ok)

    def poll(self) -> None:
        """Drain worker results into the in-order ready queue; watchdog
        stalled/dead domains; resubmit failed batches.  Mux-thread only."""
        now = time.monotonic()
        # one stamp for all this poll takes and releases (REORDER_HIST)
        ts = None
        for i, w in enumerate(self.workers):
            p = self.policies[i]
            # drain completed results BEFORE any eviction below: a
            # worker that landed S1..Sk and then wedged/died on S(k+1)
            # must not have its finished batches reassigned and re-run
            # (eviction-first turned them into dropped late results)
            if w.results:
                if ts is None:
                    ts = now_ts()
                self._drain_results(i, w, ts)
            patience = getattr(p, "stall_patience_s", 0.0)
            t0 = w.land_t0
            if (
                patience
                and t0
                and now - t0 > patience
                and not p.stalled
            ):
                # wedged past its patience: only THIS device
                # degrades; its batches move on
                p.mark_stalled()
                self._evict(i)
            if (
                p.stalled
                and not w.land_t0
                and not w.pending
                and w.reqq.empty()
            ):
                # watchdog/return race: the wedged call came back (the
                # worker cleared the flag) and THEN a stale mark_stalled
                # re-set it.  Nothing is in flight on this worker, so no
                # land will ever clear it again — clear it here or the
                # domain is out of the pool forever.  The quarantine
                # backoff from the mark still gates the re-probe.
                p.stalled = False
            if (
                not self._stopping
                and i not in self._evicted
                and (w.error is not None or not w.thread.is_alive())
            ):
                self._evicted.add(i)
                self._evict(i)
        self.pump()
        # a release needs the take of `landed_seq`, so `ts` is set here
        while self.landed_seq in self.reorder:
            meta, ok = self.reorder.pop(self.landed_seq)
            meta["t_rel"] = ts
            self.ready.append((meta, ok))
            self.landed_seq += 1
        for meta, _ok in self.reorder.values():
            meta["parked"] = True  # behind an earlier seq on another device

    def idle(self) -> bool:
        return not self.outstanding and not self.ready

    def check_fatal(self) -> None:
        """Every domain dead -> surface the first error (the supervisor
        restarts the tile).  A partial failure is handled by eviction."""
        errs = [w.error for w in self.workers]
        if errs and all(e is not None for e in errs):
            raise errs[0]

    # ---- lifecycle ------------------------------------------------------

    def stop(self, timeout_s: float | None = 30.0) -> None:
        self._stopping = True
        for w in self.workers:
            w.stop(timeout_s)

    def abort(self, timeout_s: float = 10.0) -> tuple[list[int], int]:
        """Crash teardown: abort every worker, drain their unlanded
        batches (the caller deliberately discards them — the
        supervisor's ring replay re-delivers), and report which domains
        are wedged zombies (their policies must be detached)."""
        self.aborted = True
        self._stopping = True
        zombies: list[int] = []
        dropped = 0
        for i, w in enumerate(self.workers):
            dropped += len(w.abort(timeout_s))
            if w.thread.is_alive():
                zombies.append(i)
        return zombies, dropped


class VerifyTile(Tile):
    def __init__(
        self,
        *,
        msg_width: int = 1232,
        max_lanes: int = 4096,
        pre_dedup: bool = True,
        shard: tuple[int, int] | None = None,
        async_depth: int = 3,
        device: str = "auto",
        device_fn=None,
        devices: int | str | list | None = 1,
        device_universe: list | None = None,
        fallback_trip: int = 3,
        fallback_reprobe: int = 64,
        dev_backoff_base_s: float = 0.5,
        dev_backoff_max_s: float = 30.0,
        stall_patience_s: float = 120.0,
        name: str = "verify",
    ):
        """max_lanes: the one batch shape; every batch pads to it (see the
        module docstring).

        shard=(idx, cnt): horizontal scaling — this replica only processes
        frags with seq % cnt == idx (reference: round-robin seq sharding
        across verify tiles, fd_verify.c:46); the others are skipped
        without gathering payloads.

        async_depth: FULL batches in flight PER DEVICE — submitted and
        not yet landed, counted once (`_DeviceWorker.inflight()`), the
        wiredancer request pipe depth; 1 degenerates to synchronous
        dispatch.  A partial batch is allowed PARTIAL_AHEAD (a module
        constant) instead, and under one kernel tile only while the pool
        has nothing in flight (_submit_staged).

        device: "auto" jits the batched kernel; "off" never touches JAX
        and verifies every batch on the strict host path (CPU-only tests,
        chaos harnesses, degraded deploys).  device_fn overrides the
        jitted kernel outright (fault-injection stubs).  fallback_trip /
        fallback_reprobe parameterize the FallbackPolicy.

        devices: the pool width — 1 (default: today's single serialized
        stream, bit-identical), an int N (domains 0..N-1), an explicit
        list of local device ordinals, or "auto" (every jax local
        device; resolves to 1 off-device).  With N > 1 each domain is
        its own fault domain: dev_backoff_base_s/dev_backoff_max_s cap
        the quarantine backoff and stall_patience_s is the per-device
        stall patience (DevicePolicy).

        device_universe: elastic shard members only — the kind-wide
        device-ordinal list shared by EVERY member.  Instead of keeping
        a boot-time partition forever, the member recomputes its slice
        from the LIVE active mask at every epoch flip
        (elastic.device_partition): scale-out recruits the ordinals the
        smaller active set left spare, scale-in returns them to the
        survivors.  The pool is rebuilt only at a quiet boundary (no
        in-flight device batches), so repartition never strands work.
        Metrics rows are sized for the full universe (the region is
        fixed at build).  Overrides `devices` when set."""
        self.name = name
        self.msg_width = msg_width
        self.max_lanes = max_lanes
        self.pre_dedup = pre_dedup
        self.shard = shard
        self.async_depth = max(async_depth, 1)
        self.device = device
        self._device_fn_override = device_fn
        self.device_universe = (
            [int(d) for d in device_universe] if device_universe else None
        )
        if self.device_universe is not None:
            # boot with the full universe (metrics rows size to it);
            # on_boot / the first epoch flip narrows to the live slice
            self.device_indices = list(self.device_universe)
        else:
            self.device_indices = _resolve_devices(devices, device, device_fn)
        self.n_devices = len(self.device_indices)
        self._pending_devices: list[int] | None = None
        self._fault_hook = None
        self.fallback_trip = fallback_trip
        self.fallback_reprobe = fallback_reprobe
        self.dev_backoff_base_s = dev_backoff_base_s
        self.dev_backoff_max_s = dev_backoff_max_s
        self.stall_patience_s = stall_patience_s
        # per-instance schema: the per-device health/throughput rows are
        # sized by the pool width at declaration time (the topology
        # allocates the metrics region before boot)
        self.schema = MetricsSchema(
            counters=(
                "verify_fail_txns",
                "dedup_drop_txns",
                "verified_sigs",
                "device_batches",
                # lanes the kernel computed for the landed batches: each
                # batch's real lanes rounded up to whole kernel tiles
                "kernel_lanes",
                # the kernel tiles the DEVICE computed for them: as
                # kernel_lanes / 256, less the batches the host served
                "device_tiles",
                # FallbackPolicy state, mirrored each loop so monitors
                # see degradation live (sums across the pool's domains)
                "fallback_batches",
                "device_errors",
                "device_trips",
                "host_reprobes",
                "pool_resubmits",
                "pool_late_results",
                # gauge: verify programs this process has compiled for
                # the tile's device fns.  Set after the boot-time warm;
                # a rise while serving IS a compile inside the serving
                # window (an unseen batch shape), which stalls the pipe
                # for the length of a cold compile
                "device_programs",
            )
            + PHASE_COUNTERS
            + SUBMIT_COUNTERS
            + (REORDER_COUNTER,)
            + device_counters(self.n_devices),
            hists=("lane_batch",) + BATCH_HISTS + (REORDER_HIST,),
            wide_hists=BATCH_HISTS + (REORDER_HIST,),
        )
        self._tc: R.TCache | None = None
        self._fns: list | None = None
        self._policies: list[FallbackPolicy] | None = None
        self._pool: _DevicePool | None = None
        self._interrupt = None  # ctx.interrupt, bound at boot
        self._tracer = None  # ctx.tracer, bound at boot
        self._prev_fallback = 0  # FALLBACK span edge detector
        self._prev_degraded: dict[int, int] = {}  # QUARANTINE edges
        self._mirror_tick = 0
        #: host-span factory for the per-batch steps: jax.profiler's
        #: TraceAnnotation once THIS process has imported JAX for the
        #: device path (_make_device_fns) — no other tile imports it
        self._span = _no_span
        self._next_clock_ns = 0  # next `fdt.clock` tie (housekeeping)
        #: PHASE_COUNTERS accumulators: plain ints the mux thread adds to
        #: per burst, flushed to the metrics region every 16th iteration
        self._phase_ns = dict.fromkeys(PHASE_COUNTERS, 0)
        self._full_t0 = 0  # tickcount of the pool's first open refusal
        #: the front of staging has been refused by the submit rule since
        #: the last submit: its batch waited for a land (SUBMIT_COUNTERS)
        self._held = False
        #: staged host-prepared lanes not yet submitted (list of dicts)
        self._staged: collections.deque = collections.deque()
        self._staged_lanes = 0
        #: results processed into publish-ready arrays, awaiting credits
        self._outq: collections.deque = collections.deque()
        self._outq_txns = 0

    @property
    def _policy(self) -> FallbackPolicy | None:
        """Compat view for single-device callers/tests."""
        return self._policies[0] if self._policies else None

    def wksp_footprint(self) -> int:
        if not self.pre_dedup:
            return 0
        return R.TCache.footprint(
            PRE_DEDUP_DEPTH, R.TCache.map_cnt_for(PRE_DEDUP_DEPTH)
        )

    def _make_device_fns(self) -> list:
        """One verify executable per pool domain.  With real devices
        (device="auto", n>1) each is pinned to its own accelerator —
        inputs commit there, so one domain's H2D put overlaps the other
        domains' compute (round-3 measurement: a device_put progresses
        while an execution runs)."""
        n = self.n_devices
        if self._device_fn_override is not None:
            return [self._device_fn_override] * n
        if self.device != "auto":
            return [None] * n
        if self._fns is None:
            import jax

            self._span = jax.profiler.TraceAnnotation
            from firedancer_tpu.ops.ed25519 import verify as fver
            from firedancer_tpu.utils.hostdev import (
                enable_compilation_cache,
            )

            # this process is about to compile the verify program: a
            # process-runtime tile child starts with a fresh jax.config,
            # so the persistent cache is switched on here, through the
            # one function that decides where it lives
            enable_compilation_cache()
            # digest-input variant: host hashes SHA512(R||A||M) during
            # lane expansion, so each lane ships 160 device bytes
            # (digest+sig+pub) instead of msg_width+100 — less host->
            # device traffic per lane, and the device SHA prologue is
            # work the host's expand pass does anyway
            if self.device_indices == [0]:
                # the default single-stream tile: plain jit on the
                # default device — bit-identical to the pre-pool path
                self._fns = [jax.jit(fver.verify_batch_digest)]
            else:
                local = jax.local_devices()
                bad = [d for d in self.device_indices if d >= len(local)]
                if bad:
                    # aliasing d % len(local) would silently pin two
                    # pool domains to one chip and report N healthy
                    # independent devices — surface the misconfig instead
                    raise ValueError(
                        f"{self.name}: devices {bad} out of range — host "
                        f"has {len(local)} local device(s)"
                    )
                self._fns = [
                    fver.verify_batch_digest_on(local[d])
                    for d in self.device_indices
                ]
            # warm the one batch shape (per device) so serving never
            # compiles (`device_programs` would show it).  Each device
            # pays its own lowering and, cold, its own compile: the
            # persistent cache's key covers the device assignment.  Four
            # v5e devices warmed cold in 346.7 s (one: 56.2 s); with the
            # programs cached the whole boot of `leader4` took 112.4 s
            # (PERF.md section 6, my chip runs, PR 28): ROADMAP S6.
            # The lane count goes in exactly as _submit_front sends it
            # (an int32 array; a Python int is weakly typed and would
            # trace a program of its own): its VALUE picks no program.
            for f in self._fns:
                np.asarray(
                    f(
                        np.zeros((self.max_lanes, 64), dtype=np.uint8),
                        np.zeros((self.max_lanes, 64), np.uint8),
                        np.zeros((self.max_lanes, 32), np.uint8),
                        np.asarray(self.max_lanes, np.int32),
                    )
                )
        return self._fns

    def _program_count(self) -> int:
        """Compiled verify programs behind this tile's device fns, read
        from the jit objects' own caches (stubs and host-only tiles have
        none -> 0).  Pinned fns share one jit object, counted once."""
        jits = {getattr(f, "jitted", f) for f in self._fns or ()}
        return sum(
            j._cache_size() for j in jits if hasattr(j, "_cache_size")
        )

    def device_ordinals(self) -> tuple[int, ...]:
        if self.device != "auto" or self._device_fn_override is not None:
            return ()  # host-only or stubbed: no accelerator behind it
        return tuple(self.device_universe or self.device_indices)

    def on_boot(self, ctx: MuxCtx) -> None:
        from firedancer_tpu.ops.ed25519 import hostpath

        self._interrupt = ctx.interrupt
        self._tracer = ctx.tracer
        # warm the strict host path once per process: its first call
        # pays field-table setup (~100 ms on this host) that must not
        # land inside the first production batch's tail latency — the
        # device path warms its compiled shape the same way below, and
        # the host path is every fallback's last resort
        hostpath.verify_batch_digest_host(
            np.zeros((1, 64), np.uint8), np.zeros((1, 64), np.uint8),
            np.zeros((1, 32), np.uint8),
        )
        if self.pre_dedup:
            depth = PRE_DEDUP_DEPTH
            map_cnt = R.TCache.map_cnt_for(depth)
            fp = R.TCache.footprint(depth, map_cnt)
            # re-initialized (join=False) even on restart: a replayed
            # frag the dead incarnation consumed but never forwarded
            # must NOT be swallowed by a stale pre-dedup entry — the
            # real dedup tile downstream keeps the durable history
            self._tc = R.TCache(ctx.alloc("tcache", fp), depth, map_cnt)
        self._fault_hook = (
            ctx.faults.device_error if ctx.faults is not None else None
        )
        eb = self.elastic
        if (
            self.device_universe is not None
            and eb is not None
            and eb.role == "member"
        ):
            # shard-count-aware partition: this member's slice of the
            # kind's device universe under the LIVE mask, not the
            # boot-time ordinal list (repartition drops the cached
            # fns/policies; an elastic member's degradation counters
            # reset with its device set, deliberately)
            from firedancer_tpu.disco.elastic import device_partition

            part = device_partition(
                self.device_universe, eb.bind(ctx).mask(eb.slot), eb.index
            )
            if part and part != self.device_indices:
                self.device_indices = part
                self.n_devices = len(part)
                self._fns = None
                self._policies = None
        if self._policies is None:
            # policies (and their degradation counters) persist across
            # supervisor restarts; only the worker threads are per-life
            self._policies = self._build_policies()
        self._pool = _DevicePool(
            self._policies, self.async_depth, name=self.name,
            span=self._span,
        )

    def _build_policies(self) -> list:
        from firedancer_tpu.ops.ed25519 import hostpath

        fns = self._make_device_fns()
        hook = self._fault_hook
        if self.n_devices == 1:
            return [
                FallbackPolicy(
                    fns[0],
                    hostpath.verify_batch_digest_host,
                    trip_after=self.fallback_trip,
                    reprobe_every=self.fallback_reprobe,
                    fault_hook=hook,
                )
            ]
        return [
            DevicePolicy(
                fns[i],
                hostpath.verify_batch_digest_host,
                index=i,
                trip_after=self.fallback_trip,
                backoff_base_s=self.dev_backoff_base_s,
                backoff_max_s=self.dev_backoff_max_s,
                stall_patience_s=self.stall_patience_s,
                fault_hook=hook,
            )
            for i in range(self.n_devices)
        ]

    # ---- elastic device repartition (fdt_upgrade satellite) -------------

    def on_epoch(self, ctx: MuxCtx) -> None:
        super().on_epoch(ctx)
        eb = self.elastic
        if (
            self.device_universe is None
            or eb is None
            or eb.role != "member"
        ):
            return
        from firedancer_tpu.disco.elastic import device_partition

        part = device_partition(
            self.device_universe, eb.bind(ctx).mask(eb.slot), eb.index
        )
        if part and part != self.device_indices:
            self._pending_devices = part
            self._maybe_repartition()

    def _maybe_repartition(self) -> None:
        """Apply a pending device repartition at a QUIET boundary: the
        pool must be idle (submitted work lands on the devices it was
        scheduled to — a mid-flight swap would strand results), so a
        busy pool retries from after_credit until its pipelines drain."""
        part = self._pending_devices
        if part is None:
            return
        if part == self.device_indices:
            self._pending_devices = None
            return
        pool = self._pool
        if pool is not None:
            if not pool.idle():
                return
            pool.stop(timeout_s=30.0)
        self.device_indices = list(part)
        self.n_devices = len(part)
        self._pending_devices = None
        self._fns = None
        self._policies = self._build_policies()
        self._pool = _DevicePool(
            self._policies, self.async_depth, name=self.name,
            span=self._span,
        )

    # ---- ingress: host prep + staging -----------------------------------

    def on_frags(self, ctx: MuxCtx, in_idx: int, frags: np.ndarray) -> None:
        # two clock reads a burst: the first is also the burst's ingest
        # time — the t_first of a batch whose oldest frag it staged
        t0 = tickcount()
        self._stage(ctx, in_idx, frags, ns_to_ts(t0))
        self._phase_ns["expand_ns"] += tickcount() - t0
        # staged only: after_credit, which the run loop calls next in
        # the same turn, is the one place staged work is submitted

    def _pool_open(self) -> bool:
        """pool.can_accept() — some device can take a FULL batch — with
        the wall time from the first refusal to the next acceptance
        counted into pool_full_ns: a clock read at each edge, none on
        the turns between."""
        if self._pool.can_accept():
            if self._full_t0:
                self._phase_ns["pool_full_ns"] += tickcount() - self._full_t0
                self._full_t0 = 0
            return True
        if not self._full_t0:
            self._full_t0 = tickcount()
        return False

    def _stage(
        self, ctx: MuxCtx, in_idx: int, frags: np.ndarray, t_ingest: int
    ) -> None:
        il = ctx.ins[in_idx]
        if self.elastic is not None:
            # elastic seq sharding (disco/elastic.py): assignment is a
            # pure function of (seq, flip journal) — the producer's
            # flip entries are sequenced before the frags they govern,
            # so every member resolves the same owner for every seq
            # regardless of when it observed the epoch flip
            frags = frags[self.elastic.assign(ctx, frags["seq"])]
            if not len(frags):
                return
        elif self.shard is not None:
            idx, cnt = self.shard
            frags = frags[frags["seq"] % cnt == idx]
            if not len(frags):
                return
        if self._tc is not None:
            dup = self._tc.dedup(frags["sig"])
            if dup.any():
                ctx.metrics.inc("dedup_drop_txns", int(dup.sum()))
                frags = frags[~dup]
        if not len(frags):
            return
        # one GIL-released native call: dcache gather + trailer parse +
        # per-sig lane expansion + k-digests + dedup tags; the device
        # gets digests, so the message copy is skipped outright
        b = wire.expand_native(il.dcache, frags, self.msg_width,
                               with_digests=True, with_msgs=False)
        lanes = len(b["sigs"])
        b.pop("txn_idx")
        b["tsorigs"] = frags["tsorig"].copy()
        # ring seq per txn, carried through staging -> device -> publish
        # so ack_floor can hold the fseq at the oldest unflushed frag
        b["seqs"] = frags["seq"].copy()
        b["t_first"] = t_ingest
        self._staged.append(b)
        self._staged_lanes += lanes

    def elastic_drained(self, ctx: MuxCtx) -> bool:
        """Retirement drain contract (disco/elastic.py): beyond the
        ring-cursor checks the binding performs, this replica holds
        in-flight work in its staging deque, its device pool (dispatch
        pipelines + the in-order reorder buffer), and its credit-gated
        publish queue — ALL must land and publish before the drained
        marker may be written (zero-loss handover)."""
        p = self._pool
        return (
            self._staged_lanes == 0
            and not self._staged
            and self._outq_txns == 0
            and not self._outq
            and (p is None or p.idle())
        )

    def ack_floor(self, ctx: MuxCtx, in_idx: int) -> int | None:
        """Oldest in-ring frag seq still riding the async pipeline
        (staged -> device pool -> credit-gated publish queue).  The mux
        holds the fseq here so the producer cannot overwrite a consumed
        -but-unpublished frag — a crash anywhere in the pipeline is
        then recoverable by rejoin replay (the drop/landing of a txn
        releases its seq, so the floor only ever advances)."""
        floor = None
        batches = [b["seqs"] for q in (self._outq, self._staged) for b in q]
        pool = self._pool
        if pool is not None:
            batches += [ent[0]["seqs"] for ent in pool.outstanding.values()]
            batches += [meta["seqs"] for meta, _ok in pool.reorder.values()]
            batches += [meta["seqs"] for meta, _ok in pool.ready]
        for seqs in batches:
            s = int(seqs[0])
            # wrap-safe min (fdtmc finding, PR 3: plain-int min picks
            # the wrapped-to-tiny seq across a 2^64 crossing)
            floor = s if floor is None else R.seq_min(floor, s)
        return floor

    def in_budget(self, ctx: MuxCtx) -> int | None:
        # stop draining the ring when no device can take a full batch
        # or results are waiting on downstream credits — backpressure
        # flows upstream through the ring's credit model, not an
        # unbounded host buffer.  A partial batch HELD by the submit rule
        # does not close the ring: the tile keeps draining into staging
        # (up to 2 x max_lanes), so expand runs during the hold
        if self._pool is not None and not self._pool_open():
            return 0
        if self._staged_lanes >= 2 * self.max_lanes:
            return 0
        if self._outq_txns >= 4 * self.max_lanes:
            return 0
        return None

    # ---- device submit ---------------------------------------------------

    def _submit_staged(self) -> None:
        """THE submit rule — when a staged batch may go to a device.  It
        reads only what the tile observes: the lanes staged, each
        device's batches in flight and the pool's (`_DevicePool.inflight`).

        1. A full batch goes to the least-loaded healthy device with
           fewer than `async_depth` in flight (_pool_open): queueing
           there is real work.
        2. A partial batch goes only to one with fewer than
           PARTIAL_AHEAD in flight, and only if no batch is in flight
           anywhere in the pool or its lanes fill at least one kernel
           tile (KERNEL_TILE); otherwise it stays staged, and grows with
           every burst, until a batch lands, it fills a tile, or it is
           full (rule 1).  With nothing in flight it goes at once:
           trickle traffic pays no linger.  The hold waits on nothing
           but the land of a batch that IS in flight, so it cannot
           deadlock; halt, crash teardown and repartition flush or drop
           staging as before.

        A batch costs the kernel its own tiles, one (0.6 ms) for up to
        256 lanes whatever it carries (PERF.md section 6), and
        every dispatch costs the host its turn under the GIL, more when
        another worker dispatches beside it.  So a sub-tile batch sent to
        a second idle device buys almost no compute and slows every
        dispatch; it waits for the land instead and goes as one batch,
        as it does where the pool has one device (there "no device has
        one in flight" and "the pool has none" are the same test).  A
        batch that fills a tile is real work for an idle device, so load
        spreads over the devices long before batches are full."""
        pool = self._pool
        while self._staged_lanes:
            if self._staged_lanes >= self.max_lanes:
                go, rule = self._pool_open(), "full_batches"
            else:
                # the pool's count is read only when a device is open: at
                # width one a turn that holds for the land reads no more
                # than the device's own count
                go = pool.can_accept(PARTIAL_AHEAD)
                busy = go and pool.inflight() > 0
                if busy and self._staged_lanes < KERNEL_TILE:
                    go = False
                rule = (
                    "spread_batches" if busy
                    else "held_batches" if self._held
                    else None
                )
            if not go:
                self._held = True
                return
            self._held = False
            self._submit_front(self.max_lanes, rule)

    def _submit_front(self, lanes_cap: int, rule: str | None = None) -> None:
        """Concatenate staged chunks into one device batch of <= lanes_cap
        lanes (whole txns only) and push it to the pool; `rule` is the
        SUBMIT_COUNTERS name it lands under, if any."""
        t0 = tickcount()
        take, lanes = [], 0
        while self._staged:
            chunk = self._staged[0]
            n = len(chunk["sigs"])
            if lanes + n > lanes_cap:
                # split the chunk on a txn boundary
                cnt = chunk["sig_cnt"]
                ends = np.cumsum(cnt)
                k = int(np.searchsorted(ends, lanes_cap - lanes, "right"))
                if k == 0:
                    if lanes == 0:
                        # a single txn with more lanes than the cap: take
                        # it alone, in a batch of its own size (a second
                        # shape, only where max_lanes is under a txn's
                        # signature count) — never stall with zero progress
                        k = 1
                    else:
                        break
                head, tail = _split_chunk(chunk, k, int(ends[k - 1]))
                take.append(head)
                lanes += int(ends[k - 1])
                if len(tail["sigs"]):
                    self._staged[0] = tail
                else:
                    self._staged.popleft()
                break
            take.append(self._staged.popleft())
            lanes += n
        if not take:
            return
        self._staged_lanes -= lanes
        with self._span(
            "fdt.verify.submit", seq=self._pool.next_seq, lanes=lanes
        ):
            if len(take) == 1:
                b = take[0]
            else:
                b = {
                    k: np.concatenate([c[k] for c in take])
                    for k in take[0]
                    if k != "t_first"
                }
            pad = max(self.max_lanes, lanes)
            meta = dict(
                rows=b["rows"], szs=b["szs"], tsorigs=b["tsorigs"],
                sig_cnt=b["sig_cnt"], tags=b["tags"], seqs=b["seqs"],
                lanes=lanes, txns=len(b["sig_cnt"]), rule=rule,
                # the deque is FIFO: the first chunk holds the oldest frag
                t_first=take[0]["t_first"],
            )
            self._submit(
                meta,
                (
                    _pad2(b["digests"], pad),
                    _pad2(b["sigs"], pad),
                    _pad2(b["pubs"], pad),
                    # the kernel skips the tiles past it; same dtype and
                    # shape as the boot-time warm (_make_device_fns)
                    np.asarray(lanes, np.int32),
                ),
            )
        self._phase_ns["submit_ns"] += tickcount() - t0

    def _submit(self, meta, args) -> None:
        """Interruptible submit: a full pool behind a slow host path
        must not turn into an unbounded blocking put — the supervisor's
        interrupt (stall recovery) and dead workers both have to be
        able to unwedge the loop thread."""
        pool = self._pool
        while True:
            pool.check_fatal()
            if pool.aborted:
                return  # crash teardown: ring replay re-delivers
            if self._interrupt is not None and self._interrupt.is_set():
                from firedancer_tpu.disco.mux import TileInterrupted

                raise TileInterrupted(f"{self.name}: submit abandoned")
            if pool.submit(meta, args):
                if self._tracer is not None:
                    seq = meta["pool_seq"]
                    lanes16 = min(meta["lanes"], 0xFFFF)
                    self._tracer.point(
                        SPAN.STAGE, ts=meta["t_first"], seq=seq,
                        aux16=lanes16,
                    )
                    self._tracer.point(
                        SPAN.ENQUEUE, ts=meta["t_submit"], seq=seq,
                        aux16=lanes16,
                    )
                return
            # no capacity anywhere: poll (stall watchdog + retry pump
            # may free a lane) and wait for a worker to make progress
            pool.poll()
            time.sleep(1e-3)

    # ---- egress: results -> publish --------------------------------------

    def _land_results(self, ctx: MuxCtx) -> None:
        t0 = tickcount()
        pool = self._pool
        pool.check_fatal()
        pool.poll()
        if not pool.ready:
            return
        while pool.ready:
            meta, ok = pool.ready.popleft()
            with self._span(
                "fdt.verify.results", seq=meta["pool_seq"],
                lanes=meta["lanes"],
            ):
                self._land_batch(ctx, meta, ok)
        self._phase_ns["results_ns"] += tickcount() - t0

    def _land_batch(self, ctx: MuxCtx, meta: dict, ok: np.ndarray) -> None:
        """One landed batch: lane verdicts -> per-txn verdicts -> the
        credit-gated publish queue."""
        lanes = meta["lanes"]
        ok = ok[:lanes]
        if self._tracer is not None:
            # dispatch/land timestamps were stamped into the meta by
            # the worker thread; emitted here so the span ring keeps
            # its single writer (this mux thread)
            dev = int(meta["t_dev"]) & 0xFF
            seq = meta["pool_seq"]
            self._tracer.point(
                SPAN.DISPATCH, ts=meta["t_disp"], seq=seq, aux16=dev,
            )
            self._tracer.point(
                SPAN.LAND, ts=meta["t_land"], seq=seq, aux16=dev,
                aux64=lanes,
            )
        ctx.metrics.inc("verified_sigs", lanes)
        ctx.metrics.inc("device_batches")
        # what the batch cost the kernel, which runs whole tiles up to the
        # last real lane (pallas_kernel.verify_core).  A batch the host
        # path served is counted alike: `fallback_batches` counts those.
        # `device_tiles` counts what the device itself computed (the
        # worker's land stamps it), 0 for a batch the host served
        ctx.metrics.inc("kernel_lanes", _tiles(lanes) * KERNEL_TILE)
        ctx.metrics.inc("device_tiles", meta["tiles"])
        # counted here, beside device_batches, under the domain the pool
        # accepted the result from: a window's deltas of the dev{i}_landed
        # sum to device_batches' delta
        ctx.metrics.inc(f"dev{meta['t_dev']}_landed")
        if meta["rule"]:
            ctx.metrics.inc(meta["rule"])
        if meta["parked"]:
            ctx.metrics.inc(REORDER_COUNTER)
        ctx.metrics.hist_sample(
            REORDER_HIST, max(ts_diff(meta["t_rel"], meta["t_taken"]), 0)
        )
        ctx.metrics.hist_sample("lane_batch", lanes)
        cnt = meta["sig_cnt"]
        starts = np.concatenate(([0], np.cumsum(cnt)[:-1]))
        txn_ok = (
            np.logical_and.reduceat(ok, starts)
            if lanes
            else np.zeros(0, bool)
        )
        n_fail = int((~txn_ok).sum())
        if n_fail:
            ctx.metrics.inc("verify_fail_txns", n_fail)
        if not txn_ok.any():
            self._batch_published(ctx, meta)  # nothing of it to publish
            return
        # dedup tag: first 8 bytes of the first signature, LE u64
        # (reference: fd_dedup keys the tango sig field, fd_dedup.c:125)
        # — computed by fdt_verify_expand at staging time
        self._outq.append(
            dict(
                tags=meta["tags"][txn_ok],
                rows=meta["rows"][txn_ok],
                szs=meta["szs"][txn_ok].astype(np.uint16),
                tsorigs=meta["tsorigs"][txn_ok],
                seqs=meta["seqs"][txn_ok],
                # the stamps alone: the batch's arrays are not kept alive
                # for as long as its verdicts wait for credits
                life={k: meta[k] for k in _LIFE_KEYS},
            )
        )
        self._outq_txns += int(txn_ok.sum())

    def _batch_published(self, ctx: MuxCtx, meta: dict) -> None:
        """The batch's last verdict has left: take t_pub and sample its
        lifecycle (BATCH_HISTS) from the five stamps (`meta` holds at
        least _LIFE_KEYS)."""
        t_pub = now_ts()
        m = ctx.metrics
        stamps = (
            meta["t_first"], meta["t_submit"], meta["t_disp"],
            meta["t_land"], t_pub,
        )
        for name, a, b in zip(BATCH_HISTS, stamps, stamps[1:]):
            m.hist_sample(name, max(ts_diff(b, a), 0))
        if self._tracer is not None:
            self._tracer.point(
                SPAN.PUBLISHED, ts=t_pub, seq=meta["pool_seq"],
                aux16=int(meta["t_dev"]) & 0xFF, aux64=meta["lanes"],
            )

    def _publish_ready(self, ctx: MuxCtx) -> None:
        if not self._outq:
            return
        t0 = tickcount()
        while self._outq and ctx.credits > 0:
            b = self._outq[0]
            n = len(b["tags"])
            if n <= ctx.credits:
                self._outq.popleft()
                ctx.publish(b["tags"], b["rows"], b["szs"], tsorigs=b["tsorigs"])
                ctx.credits -= n
                self._outq_txns -= n
                self._batch_published(ctx, b["life"])
            else:
                m = ctx.credits
                ctx.publish(
                    b["tags"][:m], b["rows"][:m], b["szs"][:m],
                    tsorigs=b["tsorigs"][:m],
                )
                for k in ("tags", "rows", "szs", "tsorigs", "seqs"):
                    b[k] = b[k][m:]
                ctx.credits = 0
                self._outq_txns -= m
        self._phase_ns["publish_ns"] += tickcount() - t0

    def after_credit(self, ctx: MuxCtx) -> None:
        self._land_results(ctx)
        self._publish_ready(ctx)
        if self._pending_devices is not None:
            self._maybe_repartition()
        # after the landings, so a batch held for a land goes in the
        # same turn that sees it
        self._submit_staged()
        self._mirror_policy_metrics(ctx)

    def during_housekeeping(self, ctx: MuxCtx) -> None:
        """Once a second, in the process that holds the chip: a
        zero-length `fdt.clock` host span whose `mono_ns` argument is
        time.monotonic_ns() at its start — a reader of the profiler's
        trace gets that clock's offset to the span ring's and the
        metrics' (monotonic) clock from any one of them."""
        if self._span is _no_span:
            return
        now = tickcount()
        if now >= self._next_clock_ns:
            self._next_clock_ns = now + 1_000_000_000
            with self._span("fdt.clock", mono_ns=now):
                pass

    def _mirror_policy_metrics(self, ctx: MuxCtx) -> None:
        """Expose the pool's degradation state in the shared metrics
        region (monitors read it live).  Aggregates every iteration;
        per-device rows every 16th (they are O(devices) set calls)."""
        pool = self._pool
        ps = self._policies
        m = ctx.metrics
        fb = sum(p.fallback_batches for p in ps)
        if self._tracer is not None and fb > self._prev_fallback:
            self._tracer.point(
                SPAN.FALLBACK, aux64=fb - self._prev_fallback
            )
        self._prev_fallback = fb
        m.set("fallback_batches", fb)
        m.set("device_errors", sum(p.device_errors for p in ps))
        m.set("device_trips", sum(p.device_trips for p in ps))
        m.set("host_reprobes", sum(p.host_reprobes for p in ps))
        m.set("pool_resubmits", pool.resubmits)
        m.set("pool_late_results", pool.late_results)
        self._mirror_tick += 1
        if (self._mirror_tick & 0xF) != 1:
            return
        if self._full_t0:  # a refusal still open: count it up to now
            now = tickcount()
            self._phase_ns["pool_full_ns"] += now - self._full_t0
            self._full_t0 = now
        for name, ns in self._phase_ns.items():
            if ns:
                m.inc(name, ns)  # inc, not set: monotone across restarts
                self._phase_ns[name] = 0
        m.set("device_programs", self._program_count())
        now = time.monotonic()
        for i, w in enumerate(pool.workers):
            p = ps[i]
            m.set(f"dev{i}_depth", w.reqq.qsize())
            m.set(f"dev{i}_inflight", max(w.inflight(), 0))
            m.set(f"dev{i}_failed", p.device_errors + getattr(
                p, "device_stalls", 0))
            degraded = (
                # a cleanly stopped worker (halt) is not a fault; a
                # dead/errored one mid-run is
                (not w.alive() and not pool._stopping)
                or w.error is not None
                or p.stalled
                or (p.tripped and not p.healthy(now))
            )
            if (
                self._tracer is not None
                and degraded
                and not self._prev_degraded.get(i)
            ):
                self._tracer.point(SPAN.QUARANTINE, aux16=i)
            self._prev_degraded[i] = int(degraded)
            m.set(f"dev{i}_degraded", int(degraded))

    def on_crash(self, ctx: MuxCtx) -> None:
        # drop in-flight host state: the supervisor's ring replay
        # re-delivers anything the dead incarnation consumed but never
        # forwarded, and the downstream dedup collapses re-delivery of
        # what it DID forward.  The policy objects (device fns + trip
        # state) survive into the next incarnation.
        if self._pool is not None:
            zombies, _dropped = self._pool.abort()
            for i in zombies:
                # the zombie worker (stuck mid device/host call; threads
                # are unkillable) still holds its old policy — detach a
                # fresh copy so its late dispatch/land calls can't
                # corrupt the live incarnation's degradation state
                self._policies[i] = _clone_policy(
                    self._policies[i],
                    trip_after=self.fallback_trip,
                    reprobe_every=self.fallback_reprobe,
                )
            self._pool = None
        self._staged.clear()
        self._staged_lanes = 0
        self._outq.clear()
        self._outq_txns = 0
        self._full_t0 = 0
        self._held = False

    def on_halt(self, ctx: MuxCtx) -> None:
        # drain everything: staged -> devices -> results -> downstream.
        # consumers are still running (topology halts upstream-first,
        # disco/topo.py halt order), so credits keep freeing.
        while self._staged_lanes:
            self._submit_front(self.max_lanes)
        pool = self._pool
        deadline = time.monotonic() + 60.0
        while not pool.idle() and time.monotonic() < deadline:
            self._land_results(ctx)
            if pool.outstanding:
                time.sleep(1e-3)
        pool.stop()
        self._land_results(ctx)
        deadline = time.monotonic() + 30.0
        while self._outq and time.monotonic() < deadline:
            cr = min(o.cr_avail() for o in ctx.outs) if ctx.outs else 0
            if cr <= 0:
                time.sleep(100e-6)
                continue
            ctx.credits = cr
            self._publish_ready(ctx)
        self._mirror_tick = 0  # force the per-device rows one last time
        self._mirror_policy_metrics(ctx)


def _resolve_devices(devices, device: str, device_fn) -> list[int]:
    """`devices` spec -> local device ordinals (pool domains).

    "auto" asks for the local inventory ONLY for a real device="auto"
    kernel (a host-only or stubbed tile must never pull the backend
    in), and hostdev.local_device_count takes it in a child that exits
    — this runs in the topology parent, which under the process runtime
    must leave the chip to the tile's own process; int N = ordinals
    0..N-1 (logical domains when stubbed); an explicit list is taken
    verbatim (disjoint ordinal sets across seq-sharded replicas — see
    disco.topo.device_assignments)."""
    if devices in (None, 1, "off"):
        return [0]  # "off" mirrors disco.topo.device_assignments
    if devices == "auto":
        if device == "auto" and device_fn is None:
            from firedancer_tpu.utils.hostdev import local_device_count

            return list(range(local_device_count()))
        return [0]
    if isinstance(devices, int):
        return list(range(max(devices, 1)))
    out = [int(d) for d in devices]
    return out or [0]


def _clone_policy(
    old: FallbackPolicy, *, trip_after: int, reprobe_every: int
) -> FallbackPolicy:
    """Fresh policy object carrying over the old one's degradation
    state (a wedged zombie thread keeps a dead reference instead)."""
    if isinstance(old, DevicePolicy):
        p: FallbackPolicy = DevicePolicy(
            old.device_fn, old.host_fn,
            index=old.index,
            trip_after=old.trip_after,
            backoff_base_s=old.backoff_base_s,
            backoff_max_s=old.backoff_max_s,
            stall_patience_s=old.stall_patience_s,
            fault_hook=old.fault_hook,
        )
        for attr in ("backoff_s", "quarantined_until", "device_stalls"):
            setattr(p, attr, getattr(old, attr))
        # NOT `stalled`: only the wedged call's return clears that flag,
        # and the zombie holds the OLD object — a copied flag would
        # quarantine the clone forever.  The carried-over backoff still
        # delays the re-probe, and a still-wedged device just re-trips
        # the patience watchdog.
    else:
        p = FallbackPolicy(
            old.device_fn, old.host_fn,
            trip_after=trip_after,
            reprobe_every=reprobe_every,
            fault_hook=old.fault_hook,
        )
    for attr in (
        "consec_failures", "tripped", "fallback_batches",
        "device_errors", "device_trips", "host_reprobes",
    ):
        setattr(p, attr, getattr(old, attr))
    return p


def _split_chunk(chunk: dict, k_txns: int, k_lanes: int) -> tuple[dict, dict]:
    """Split a staged chunk after k_txns txns / k_lanes lanes."""
    head, tail = {}, {}
    for key in ("rows", "szs", "tsorigs", "sig_cnt", "tags", "seqs"):
        head[key], tail[key] = chunk[key][:k_txns], chunk[key][k_txns:]
    for key in ("digests", "sigs", "pubs"):
        head[key], tail[key] = chunk[key][:k_lanes], chunk[key][k_lanes:]
    # both halves were ingested by the same burst
    head["t_first"] = tail["t_first"] = chunk["t_first"]
    return head, tail


def _pad2(a: np.ndarray, n: int) -> np.ndarray:
    if len(a) == n:
        return a
    out = np.zeros((n,) + a.shape[1:], dtype=a.dtype)
    out[: len(a)] = a
    return out


def _pad1(a: np.ndarray, n: int) -> np.ndarray:
    if len(a) == n:
        return a
    out = np.zeros(n, dtype=a.dtype)
    out[: len(a)] = a
    return out
