"""Service-agnostic device-offload runtime (ISSUE 20).

The LaunchAggregator / guard / launch-scheduler / mempool stack grew up
inside codec/matrix_codec.py serving exactly one client: EC coding
launches.  Nothing in it is EC-specific — a "launch" is any batched
per-byte transform with a device plan and a byte-identical host oracle
— so this module hoists the machinery out of the codec and fronts it
with a small service registry:

- **LaunchAggregator** (and its moving parts: AggTicket, _AggGroup) is
  the generic aggregation engine.  A service subclasses it and supplies
  the device plan builder (`_dispatch`) and the byte-identical host
  oracle (`_dispatch_host`); the engine owns windowing, padding,
  pipelining, QoS lane submission (SCHED_CLASS), guard fallback and
  mempool accounting.
- **register_service / service_aggregator** is the registry: a service
  registers its aggregator factory, QoS lane and host-oracle
  description once; callers reach the shared process-wide instance by
  name.  The EC encode/decode/verify aggregators (still defined in
  codec/matrix_codec.py, now as plain subclasses of this module's
  engine) are the first three entries — zero behavior change, their
  perf names, knobs and import paths are untouched.  The device
  crc32c service (ops/checksum_offload.py) and the batched device
  compressor (compressor/device.py) are the first post-EC riders.
- **offload_perf_dump** flattens every registered service's aggregator
  counters into the `offload.*` slice of the OSD perf report — the
  `ceph_tpu_offload_*` Prometheus families.

Nothing here imports the codec package at module scope (the codec
imports THIS module); the one EC-flavored seam left is that a failed
launch surfaces as `EcError(EIO, ...)` at the reap, imported lazily —
every existing reap path catches exactly that type.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import OrderedDict

import numpy as np

from ceph_tpu.common.lockdep import make_lock as _lockdep_make_lock
from ceph_tpu.common.lockdep import make_rlock as _lockdep_make_rlock
from ceph_tpu.common.mempool import ledger as _hbm_ledger
from ceph_tpu.common.tracer import section


def _next_pow2(n: int) -> int:
    return 1 << (max(1, n) - 1).bit_length()


class AggTicket:
    """One submitted stripe-batch coding launch awaiting aggregation.

    Resolves to this submission's (stripes, rows, L) output — parity for
    an encode submission, reconstructed chunks for a decode submission.
    Duck-types the surface PendingEncode/PendingDecode expect of a live
    device array: `is_ready()` for non-blocking polls and `__array__` for
    materialization (np.asarray on a ticket forces its group's launch and
    blocks until it finishes)."""

    __slots__ = ("_agg", "_group", "_start", "_stripes", "_value")

    def __init__(self, agg: "LaunchAggregator", group: "_AggGroup", start: int, stripes: int):
        self._agg = agg
        self._group = group
        self._start = start
        self._stripes = stripes
        self._value: np.ndarray | None = None

    @property
    def launched(self) -> bool:
        if self._value is not None:
            return True
        g = self._group
        return g.host is not None or g.parity is not None or g.error is not None

    def is_ready(self) -> bool:
        if self._value is not None:
            return True
        g = self._group
        if g.host is not None or g.error is not None:
            return True  # a failed launch is "ready": the reap reports it
        if g.parity is None:
            return False  # still windowed; a flush will launch it
        ready = getattr(g.parity, "is_ready", None)
        return True if ready is None else bool(ready())

    def result(self) -> np.ndarray:
        if self._value is None:
            self._agg._materialize(self)
        return self._value

    def __array__(self, dtype=None, copy=None):
        out = self.result()
        return out if dtype is None else out.astype(dtype)


class _AggGroup:
    """Pending submissions sharing one (matrix, chunk-length) geometry —
    the unit that concatenates into a single padded device launch."""

    __slots__ = (
        "key", "ec", "ctx", "arrays", "tickets", "stripes", "nbytes",
        "parity", "host", "pad", "error", "lock", "input", "credit",
        "flight", "submit_ts", "stalled", "mem",
    )

    def __init__(self, key, ec, ctx=None):
        self.key = key
        self.ec = ec
        self.ctx = ctx  # per-kind dispatch context (decode: erasure tuple)
        self.arrays: list[np.ndarray] = []
        self.tickets: list[AggTicket] = []
        self.stripes = 0
        self.nbytes = 0
        self.parity = None  # live device array once launched
        self.host: np.ndarray | None = None  # materialized parity
        self.pad = 0
        self.error: BaseException | None = None  # a failed launch, sticky
        # HBM ledger handle for that in-flight output (ISSUE 13):
        # alloc'd at dispatch, freed at settle on every outcome —
        # host-fallback and sticky-error settles included
        self.mem = None
        # concatenated padded launch input, retained from launch until
        # settle so a device that wedges AFTER dispatch can still be
        # recomputed on the host oracle
        self.input: np.ndarray | None = None
        self.credit = 0  # inflight-byte throttle credit held by this group
        # flight-recorder state (ISSUE 8): the launch's record, the
        # window-open timestamp queue-wait anchors on, and whether any
        # submitter hit the backpressure bound getting in
        self.flight: dict | None = None
        self.submit_ts = time.monotonic()
        self.stalled = False
        # serializes THIS group's launch/materialization (the encode
        # dispatch + blocking device wait) without stalling the
        # aggregator-wide lock; RLock because a reap-forced launch runs
        # inside the reap's own hold
        self.lock = threading.RLock()


class LaunchAggregator:
    """Cross-op launch aggregation: coalesce concurrent small stripe-batch
    coding calls (different ops, PGs, objects) into one padded device
    launch.  Shared machinery of every offload service's aggregator;
    the subclasses supply the group key and the device dispatch.

    The storage-side analog of a training stack's bucketed all-reduce:
    per-op launches under ~1 MiB are dominated by dispatch overhead, so
    submissions queue in per-geometry groups and launch together when the
    window fills, the byte budget trips, or a barrier drains the window
    (ECBackend.flush_encodes / flush_decodes — or any ticket reap).

    One launch path, two modes.  window <= 1 (the encode and decode
    windows' default) launches each submission at once as its own
    group: nothing is concatenated or padded.  window > 1 concatenates a group's
    submissions and pads the stripe count to a static bucket (power of
    two up to 64, then multiples of 64 — capped waste, unlike pure pow2)
    so the jit cache sees few geometries; a window that trips launches
    at once.  Tickets slice their own stripes back out, in submission
    order.  No device buffer outlives its launch's settle.

    Occupancy and launch-size distributions are PerfHistograms on
    `self.perf`, exportable through the PR-1 prometheus layer
    (PerfCountersCollection.add(agg.perf))."""

    PERF_NAME = "ec_aggregator"
    WHAT = "encode"  # used in error reports
    # QoS lane every launch of this aggregator dispatches under (ISSUE 9
    # launch scheduler): client encodes preempt queued background work;
    # the decode/verify subclasses override with their own lane.
    SCHED_CLASS = "client"
    # HBM ledger pool this aggregator's in-flight launch outputs charge
    # (ISSUE 13); the verify subclass charges its own pool so the leak
    # gate can drain-check the EC data path and scrub independently.
    MEM_POOL = "ec_pipeline_inflight"

    def __init__(self, window: int = 0, max_bytes: int = 64 << 20,
                 inflight_max_bytes: int | None = None,
                 pipeline_depth: int | None = None):
        from ceph_tpu.common.perf_counters import PerfCountersBuilder
        from ceph_tpu.common.throttle import Throttle

        self.window = int(window)
        self.max_bytes = int(max_bytes)
        # depth-N asynchronous launch pipeline (ISSUE 11): how many
        # launched-but-unsettled groups may be in flight before a new
        # launch first settles the oldest — the settle happens AFTER the
        # new dispatch, so window N+1's H2D overlaps window N's kernel.
        # <= 0 disables the ring (in-flight bounded only by the byte
        # throttle, the pre-ISSUE-11 behavior).
        if pipeline_depth is None:
            from ceph_tpu.common.options import OPTIONS

            pipeline_depth = int(OPTIONS["ec_tpu_pipeline_depth"].default)
        self.pipeline_depth = int(pipeline_depth)
        from ceph_tpu.ops.dispatch import PIPELINE

        PIPELINE.set_depth(self.pipeline_depth)
        # RLock: a reap (`_materialize`) forces its group's launch from
        # inside the lock (make_rlock: per-instance reentrant, ordering
        # still validated on the outermost acquire)
        self._lock = _lockdep_make_rlock(self.PERF_NAME)
        self._groups: "OrderedDict[tuple, _AggGroup]" = OrderedDict()
        # end-to-end backpressure (ec_tpu_inflight_max_bytes): byte credit
        # over everything admitted but not yet settled — windowed groups
        # AND launched-but-unreaped ones.  Over the bound, _admit makes
        # the SUBMITTER settle older launches first.
        if inflight_max_bytes is None:
            from ceph_tpu.common.options import OPTIONS

            inflight_max_bytes = int(OPTIONS["ec_tpu_inflight_max_bytes"].default)
        self.inflight = Throttle(
            f"{self.PERF_NAME}.inflight", int(inflight_max_bytes)
        )
        self._live: list[_AggGroup] = []  # launched, not yet settled (FIFO)
        b = PerfCountersBuilder(self.PERF_NAME)
        for c in ("submits", "launches", "flush_window", "flush_bytes",
                  "flush_explicit", "flush_immediate", "flush_reap",
                  "flush_backpressure", "pad_stripes", "host_fallbacks",
                  "throttle_stalls"):
            b.add_u64_counter(c)
        b.add_histogram("stripes_per_launch",
                        "stripe-batch occupancy of each device launch",
                        lowest=1, buckets=14)
        b.add_histogram("tickets_per_launch",
                        "submissions coalesced into each device launch",
                        lowest=1, buckets=8)
        b.add_histogram("launch_bytes",
                        "input bytes per device launch",
                        lowest=4096, buckets=18)
        self.perf = b.create_perf_counters()
        # live-aggregator registry (ISSUE 13): the leak-gate and chaos
        # drains reach every instance through it
        _AGGREGATORS.add(self)

    def configure(self, window: int | None = None, max_bytes: int | None = None,
                  inflight_max_bytes: int | None = None,
                  pipeline_depth: int | None = None) -> None:
        """Apply live config (the OSD wires its Config + runtime observers
        here, so the aggregate_* settings reach the shared instance)."""
        if window is not None:
            self.window = int(window)
        if max_bytes is not None:
            self.max_bytes = int(max_bytes)
        if inflight_max_bytes is not None:
            self.inflight.limit = int(inflight_max_bytes)
        if pipeline_depth is not None:
            self.pipeline_depth = int(pipeline_depth)
            from ceph_tpu.ops.dispatch import PIPELINE

            PIPELINE.set_depth(self.pipeline_depth)

    # -- subclass hooks ------------------------------------------------------

    def _dispatch(self, g: _AggGroup, data: np.ndarray):
        raise NotImplementedError

    def _dispatch_host(self, g: _AggGroup, data: np.ndarray) -> np.ndarray:
        """Byte-identical host-oracle recompute of `_dispatch` (pure
        numpy): the DEGRADED-mode path a wedged device cannot hang."""
        raise NotImplementedError

    # -- submission ----------------------------------------------------------

    def _submit(self, key, ec, ctx, shaped: np.ndarray) -> AggTicket:
        """Queue one (stripes, k, L) uint8 batch under `key`; returns its
        ticket.  May launch (this or earlier submissions) when a threshold
        trips.  Admission is throttled: past ec_tpu_inflight_max_bytes of
        unsettled work, this call settles older launches first."""
        with section("offload.submit", shaped.nbytes):
            return self._enqueue(key, ec, ctx, shaped)

    def _enqueue(self, key, ec, ctx, shaped: np.ndarray) -> AggTicket:
        stripes = shaped.shape[0]
        # HBM pressure hook (ISSUE 13): time-throttled, no locks held —
        # under a target, sustained submission pressure trims the cache
        # / clamps depth without waiting for the next status beacon
        _hbm_ledger().maybe_check_pressure()
        stalled = self._admit(shaped.nbytes)
        reason = None
        with self._lock:
            self.perf.inc("submits")
            g = self._groups.get(key)
            if g is None:
                g = self._groups[key] = _AggGroup(key, ec, ctx)
            if stalled:
                g.stalled = True  # flight record flags the stall
            ticket = AggTicket(self, g, g.stripes, stripes)
            g.arrays.append(shaped)
            g.tickets.append(ticket)
            g.stripes += stripes
            g.nbytes += shaped.nbytes
            g.credit += shaped.nbytes
            if self.window <= 1:
                reason = "flush_immediate"
            elif g.nbytes >= self.max_bytes:
                reason = "flush_bytes"
            elif len(g.tickets) >= self.window:
                reason = "flush_window"
            if reason is not None:
                self._groups.pop(key, None)  # detach under the lock...
        if reason is not None:
            try:
                self._launch(g, reason)  # ...dispatch/compile outside it
            except Exception:
                # sticky on the group: every co-rider's reap reports it
                # (raising here would blame an arbitrary submitter and
                # tear down its unrelated write)
                pass
            # pipeline ring (ISSUE 11): AFTER the new launch dispatched,
            # settle down to the depth bound — the new window's H2D ran
            # before the oldest's blocking wait, which is the overlap
            self._drain_pipeline()
        return ticket

    def _drain_pipeline(self) -> None:
        """Bound the in-flight launch set at `ec_tpu_pipeline_depth` by
        settling the oldest launches.  Runs with NO locks held (a settle
        takes the victim group's lock; holding another group's lock here
        would deadlock two submitters draining each other)."""
        depth = self.pipeline_depth
        if depth <= 0:
            return
        if _hbm_ledger().depth_clamped:
            # HBM pressure stage 2: one launch's output in flight at a
            # time — overlap traded for bounded residency until relief
            depth = 1
        from ceph_tpu.ops.dispatch import PIPELINE

        while True:
            with self._lock:
                if len(self._live) <= depth:
                    return
                g = self._live[0]
            PIPELINE.record_drain()
            self._settle(g)
            with self._lock:
                if g in self._live:  # defensive: settle always removes
                    return

    def _admit(self, nbytes: int) -> bool:
        """Backpressure admission (the byte Throttle): take credit for a
        submission; over the bound, the SUBMITTER settles the oldest
        outstanding launches — paying the drain latency itself — until
        credit frees.  Pushing back on the producer is the point: a
        degraded/slow backend must stall its writers, not queue device
        work unboundedly.  A single submission larger than the whole
        bound is admitted once nothing older remains (the reference
        Throttle's oversized-request semantics: the dispatch path must
        not wedge).  Returns True when the submitter stalled (the flight
        record of the launch it rides flags `throttle_stall`)."""
        if self.inflight.get_or_fail(nbytes):
            return False
        self.perf.inc("throttle_stalls")
        while not self.inflight.get_or_fail(nbytes):
            if not self._settle_oldest():
                self.inflight.take(nbytes)  # oversized: admit anyway
                break
        return True

    def _settle_oldest(self) -> bool:
        """Settle one outstanding group, oldest first — launched groups
        before windowed ones (their credit frees on a blocking wait;
        windowed groups must be launched first).  False when nothing is
        outstanding."""
        with self._lock:
            if self._live:
                g = self._live[0]
            elif self._groups:
                g = next(iter(self._groups.values()))
            else:
                return False
        if g.parity is None and g.host is None and g.error is None:
            with self._lock:
                if self._groups.get(g.key) is g:
                    del self._groups[g.key]
            try:
                self._launch(g, "flush_backpressure")
            except Exception:
                pass  # sticky on the group; settle releases its credit
        self._settle(g)
        return True

    def pending(self) -> int:
        """Submissions queued but not yet launched."""
        with self._lock:
            return sum(len(g.tickets) for g in self._groups.values())

    def drain(self) -> None:
        """Settle EVERYTHING: flush the windowed groups, then settle
        every launched group oldest-first.  The HBM leak gate's
        teardown hook — after a drain the in-flight ledger pool must
        read zero (sticky errors settle too; they just stay sticky for
        their tickets' reaps)."""
        self.flush()
        while True:
            with self._lock:
                g = self._live[0] if self._live else None
            if g is None:
                return
            self._settle(g)

    def flush(self) -> None:
        """Launch every windowed group, FIFO (the commit barrier)."""
        with self._lock:
            detached = list(self._groups.values())
            self._groups.clear()
        for g in detached:
            try:
                self._launch(g, "flush_explicit")
            except Exception:
                continue  # sticky on the group; other groups still launch
        if detached:
            # the flushed groups joined the ring: re-bound the in-flight
            # set at the depth budget
            self._drain_pipeline()

    # -- launch + reap -------------------------------------------------------

    def _pad_target(self, stripes: int) -> int:
        """Launch-size bucket: pow2 up to 64 stripes, then multiples of 64.
        Bounds both the jit-cache geometry count AND the padding waste
        (pure pow2 would pad up to 2x on exactly the biggest launches the
        byte budget exists to bound)."""
        if stripes <= 64:
            return _next_pow2(stripes)
        return -(-stripes // 64) * 64

    def _launch(self, g: _AggGroup, reason: str) -> None:
        """Concatenate a (detached) group's submissions into one padded
        device launch.  Runs OUTSIDE the aggregator-wide lock: the encode
        dispatch — including a first-time jit compile, seconds on a
        remote-compile TPU path — must not stall other geometries'
        submits.  The group lock serializes against same-group reaps."""
        with g.lock:
            if g.parity is not None or g.host is not None or g.error is not None:
                return
            data = g.arrays[0] if len(g.arrays) == 1 else np.concatenate(g.arrays)
            # pad only in aggregating mode: with the window off, every
            # write would pay a concatenate copy + dead-stripe encode the
            # direct path never did
            pad = 0
            if self.window > 1:
                pad = self._pad_target(g.stripes) - g.stripes
            if pad:
                data = np.concatenate(
                    [data, np.zeros((pad, *data.shape[1:]), dtype=np.uint8)]
                )
            # retained until settle: a device that wedges AFTER this
            # dispatch is recomputed from these exact bytes on the host
            g.input = data
            # flight record (ISSUE 8): the launch's timeline entry.
            # queue_wait anchors on the group's window-open timestamp;
            # the guarded dispatch runs inside the record's scope so
            # ops/dispatch.py annotates devices and ops/guard.py flags
            # deadline hits on THIS record.
            from ceph_tpu.ops.flight_recorder import flight_recorder, new_record

            fr = flight_recorder()
            rec = g.flight = new_record(
                self.WHAT,
                group=self._group_label(g),
                tickets=len(g.tickets),
                stripes=g.stripes,
                batch=data.shape[0],
                nbytes=data.nbytes,
                submit_ts=g.submit_ts,
                reason=reason,
                sched_class=self.SCHED_CLASS,
            )
            rec["pad_stripes"] = pad
            if g.stalled:
                rec["flags"]["throttle_stall"] = True
            # QoS arbitration (ISSUE 9): the ready launch enters the
            # shared device queue tagged with this aggregator's lane and
            # leaves it in dmClock tag order — a queued client encode
            # dequeues ahead of a queued background verify.  The
            # scheduler runs the dispatch under THIS context (captured
            # at submit), so the active flight record and tracer scope
            # survive even when another submitter's drain executes it.
            # Timing anchors live INSIDE the scheduled callable: time
            # spent queued behind other classes' launches (or spent
            # cooperatively executing them) is queue wait, not h2d —
            # banking it as busy would double-count wall clock across
            # concurrent records and overstate occupancy under exactly
            # the contention the scheduler creates.
            from ceph_tpu.ops.launch_scheduler import (
                CLASS_BY_LANE,
                launch_scheduler,
            )

            t_enqueue = time.monotonic()
            timing: dict[str, float] = {}

            def _dispatch_scheduled():
                timing["t_dispatch"] = time.monotonic()
                out = self._guarded_dispatch(g, data)
                timing["t_done"] = time.monotonic()
                return out

            from ceph_tpu.ops.guard import device_guard

            try:
                with fr.active_scope(rec):
                    if device_guard().degraded:
                        # DEGRADED bypass: this launch re-runs on the
                        # host oracle (or at most a rate-limited compile
                        # probe), so there is no device to arbitrate —
                        # routing it through the device turn would
                        # serialize every lane's numpy recompute behind
                        # one lock, head-of-line-blocking client encodes
                        # exactly when the backend is already hurting
                        parity = _dispatch_scheduled()
                    else:
                        parity = launch_scheduler().submit(
                            CLASS_BY_LANE[self.SCHED_CLASS],
                            _dispatch_scheduled,
                            cost=data.nbytes,
                        )
            except BaseException as e:
                # sticky: every co-rider's reap reports the launch failure
                # instead of crashing on a half-torn group.  The group
                # still enters the live list so its backpressure credit
                # releases at settle.
                # same dead-time rule as the success path, stricter: a
                # launch that RAISED (deadline wait, device error with a
                # failed host recompute, bad geometry) produced nothing
                # — none of its elapsed time banks as busy
                rec["dispatch_ts"] = timing.get("t_dispatch", t_enqueue)
                g.error = e
                g.pad = pad
                with self._lock:
                    self._live.append(g)
                    rec["inflight_depth"] = len(self._live)
                from ceph_tpu.ops.dispatch import PIPELINE

                PIPELINE.launch()
                raise
            # dispatch_ts anchors where the launch LEFT the queue and
            # actually began dispatching (queue-wait — window AND
            # scheduler — ends here); h2d_s is the synchronous slice of
            # the dispatch — H2D staging + launch enqueue (JAX dispatch
            # is async, kernel time shows up at settle).  A fallback
            # launch gets h2d_s = 0: its host compute is already banked
            # in kernel_s, and the remainder of the elapsed time is the
            # watchdog DEADLINE wait on a wedged device — dead time that
            # must not inflate device_busy_seconds/occupancy.
            t_dispatch = timing.get("t_dispatch", t_enqueue)
            rec["dispatch_ts"] = t_dispatch
            if rec["flags"]["fallback"]:
                rec["h2d_s"] = 0.0
            else:
                rec["h2d_s"] = max(
                    0.0,
                    timing.get("t_done", t_dispatch)
                    - t_dispatch
                    - rec["kernel_s"],
                )
            g.arrays = []
            g.pad = pad
            g.parity = parity
            # HBM ledger (ISSUE 13): the in-flight device output is
            # resident from this dispatch until settle.  The handle is
            # buffer-finalized too, so even an abandoned group cannot
            # leak ledger bytes past the output's death.
            if not isinstance(parity, np.ndarray):
                out_nbytes = int(getattr(parity, "nbytes", 0) or 0)
                if out_nbytes:
                    g.mem = _hbm_ledger().alloc(
                        self.MEM_POOL, out_nbytes, buf=parity
                    )
            rec["hbm_bytes"] = _hbm_ledger().total_device_bytes()
            # inside g.lock, like the error path above: appending after
            # release races a reaper that settles (and _live-removes) the
            # group first, which would pin a settled group in _live
            with self._lock:
                self._live.append(g)
                rec["inflight_depth"] = len(self._live)
            from ceph_tpu.ops.dispatch import PIPELINE

            PIPELINE.launch()
        self.perf.inc("launches")
        self.perf.inc(reason)
        self.perf.inc("pad_stripes", pad)
        self.perf.hinc("stripes_per_launch", g.stripes)
        self.perf.hinc("tickets_per_launch", len(g.tickets))
        self.perf.hinc("launch_bytes", data.nbytes)

    def _group_label(self, g: _AggGroup) -> str:
        """Stable human-readable lane name for a group's flight records
        and trace-export lanes: aggregator kind + a short key digest +
        the chunk length (the key's raw bytes are not JSON-safe).
        crc32 over the key's repr, NOT hash(): the built-in is salted
        per process, which would break cross-run lane correlation."""
        import zlib

        chunk = g.key[-1] if g.key and isinstance(g.key[-1], int) else 0
        digest = zlib.crc32(repr(g.key).encode())
        return f"{self.PERF_NAME}/{digest:08x}/L{chunk}"

    # -- device guard / host fallback ---------------------------------------

    def _guarded_dispatch(self, g: _AggGroup, data: np.ndarray):
        """Dispatch one launch under the device guard: the `codec.launch`
        faultpoint and the per-launch deadline apply here; a device error
        or timeout re-runs the group on the byte-identical host oracle
        and marks the backend DEGRADED.  While degraded, the device is
        bypassed entirely until a probe heals it."""
        from ceph_tpu.common.fault_injector import faultpoint
        from ceph_tpu.ops.guard import device_guard

        guard = device_guard()
        if not guard.maybe_probe():
            # DEGRADED, probe not due (or failed): straight to the host
            return self._host_fallback(g, data, None)
        try:
            faultpoint("codec.launch")
            return guard.call(
                lambda: self._timed_dispatch(g, data),
                what=f"{self.WHAT} dispatch",
            )
        except BaseException as e:
            return self._host_fallback(g, data, e)

    def _timed_dispatch(self, g: _AggGroup, data: np.ndarray):
        """The guarded callable: the launch as the `device.dispatch`
        section of whichever thread runs it (a launch worker, or the
        caller with the watchdog off)."""
        with section("device.dispatch", data.nbytes):
            return self._dispatch(g, data)

    def _host_fallback(self, g: _AggGroup, data: np.ndarray, cause):
        """Re-run a launch on the host oracle.  `cause` is the device
        failure that sent us here (None = degraded-mode bypass); the
        backend is marked DEGRADED only when the host recompute SUCCEEDS
        after a device failure — a recompute that fails identically
        (singular matrix, bad geometry) is a data error, not a backend
        verdict, and raises sticky like any launch failure."""
        t0 = time.monotonic()
        host = self._dispatch_host(g, data)
        if g.flight is not None:
            # flight-record verdict: this launch completed on the host.
            # The host compute banks as kernel_s (it IS the kernel, just
            # not on the device); degraded_bypass marks launches that
            # never tried the device at all.
            g.flight["flags"]["fallback"] = True
            if cause is None:
                g.flight["flags"]["degraded_bypass"] = True
            g.flight["kernel_s"] += time.monotonic() - t0
        if cause is not None:
            from ceph_tpu.ops.guard import device_guard

            device_guard().mark_degraded(
                f"{self.WHAT} launch failed: {cause!r}"
            )
        from ceph_tpu.ops.dispatch import record_fallback

        record_fallback(data.shape[0], data.nbytes)
        self.perf.inc("host_fallbacks")
        return host

    # -- settle / reap -------------------------------------------------------

    def _settle(self, g: _AggGroup) -> None:
        """Resolve a group to host bytes (or a sticky error), releasing
        its backpressure credit exactly once.  Lock order: group lock ->
        aggregator lock (nothing acquires the other way); the blocking
        device wait runs outside the aggregator-wide lock so other
        geometries never stall behind a kernel.  The wait itself is
        deadline-guarded: a device that wedges AFTER dispatch triggers
        the same host recompute as a failed dispatch."""
        from ceph_tpu.ops.guard import device_guard

        with g.lock:
            if g.host is None and g.error is None and g.parity is None:
                # still windowed: detach and launch it ourselves (a reap
                # must never deadlock behind its own window).  Identity
                # check: a newer group may have reused our key after we
                # were detached by a concurrent flush — popping IT would
                # orphan its window.
                with self._lock:
                    if self._groups.get(g.key) is g:
                        del self._groups[g.key]
                try:
                    self._launch(g, "flush_reap")
                except Exception:
                    pass  # reported as EcError via g.error at the reap
            if g.host is None and g.error is None:
                parity = g.parity
                device_side = not isinstance(parity, np.ndarray)
                host = parity
                if device_side:
                    # completion-ordered readiness probe (ISSUE 11): at
                    # pipeline depth > 1 a launch often finished under a
                    # LATER launch's dispatch — was_ready marks perfect
                    # overlap on the record, and a DEGRADED backend with
                    # an UNREADY buffer goes straight to the host oracle
                    # so one wedged launch costs one deadline, not one
                    # per in-flight group
                    ready_fn = getattr(parity, "is_ready", None)
                    try:
                        was_ready = bool(ready_fn()) if ready_fn else False
                    except Exception:
                        was_ready = False
                    if device_guard().degraded and not was_ready:
                        try:
                            host = self._host_fallback(g, g.input, None)
                        except BaseException as e2:
                            g.error = e2
                        device_side = False  # suspect buffer: skip the wait
                if device_side:
                    rec = g.flight
                    # the worker writes spans into a side dict, folded
                    # into the record only on SUCCESS: a materialize
                    # that times out leaves an abandoned worker holding
                    # this closure, and if the device later unwedges it
                    # would otherwise rewrite an already-committed
                    # record with a minutes-long bogus kernel span
                    spans: dict[str, float] = {}

                    def _materialize():
                        # flight sub-spans: kernel_s is how long THIS
                        # reap blocked waiting for the device (0 = the
                        # kernel finished under other work — perfect
                        # overlap); d2h_s is the device->host copy.
                        # complete_ts anchors the record's spans in
                        # completion order: under async dispatch the
                        # wall clock around the (non-blocking) dispatch
                        # no longer brackets the kernel.
                        t0 = time.monotonic()
                        wait = getattr(parity, "block_until_ready", None)
                        if wait is not None:
                            with section("device.block"):
                                wait()
                        t1 = time.monotonic()
                        with section("device.d2h") as d2h:
                            out = np.asarray(parity)
                            d2h.nbytes = out.nbytes
                        t2 = time.monotonic()
                        spans["kernel_s"] = t1 - t0
                        spans["complete_ts"] = t1
                        spans["d2h_s"] = t2 - t1
                        return out

                    from ceph_tpu.ops.flight_recorder import flight_recorder

                    try:
                        with flight_recorder().active_scope(rec):
                            host = device_guard().call(
                                _materialize,
                                what=f"{self.WHAT} materialize",
                            )
                        if rec is not None:
                            rec["kernel_s"] += spans.get("kernel_s", 0.0)
                            rec["d2h_s"] += spans.get("d2h_s", 0.0)
                            rec["complete_ts"] = spans.get(
                                "complete_ts", 0.0
                            )
                            if was_ready:
                                rec["flags"]["overlap"] = True
                    except BaseException as e:
                        try:
                            host = self._host_fallback(g, g.input, e)
                        except BaseException as e2:
                            g.error = e2
                if g.error is None:
                    g.host = host[: g.stripes] if g.pad else host
                    g.parity = None
            # settled (host bytes or sticky error): release the
            # backpressure credit, the retained launch input, and the
            # HBM ledger hold — the release is unconditional, so the
            # host-fallback and sticky-error paths (the historical leak
            # shape) cannot keep the in-flight pool charged
            if g.mem is not None:
                g.mem.free()
                g.mem = None
            if g.credit:
                self.inflight.put(g.credit)
                g.credit = 0
            g.input = None
            # commit the flight record exactly once (g.flight nulls out;
            # later reaps of the same group skip this)
            if g.flight is not None:
                rec, g.flight = g.flight, None
                rec["flags"]["error"] = g.error is not None
                rec["settle_ts"] = time.monotonic()
                from ceph_tpu.ops.flight_recorder import flight_recorder

                flight_recorder().commit(rec)
        with self._lock:
            removed = g in self._live
            if removed:
                self._live.remove(g)
        if removed:
            from ceph_tpu.ops.dispatch import PIPELINE

            PIPELINE.settle()

    def _materialize(self, ticket: AggTicket) -> None:
        g = ticket._group
        self._settle(g)
        if g.error is not None:
            # lazy: the codec imports this module, not the reverse; every
            # reap path (EC and non-EC riders alike) catches EcError
            from ceph_tpu.codec.base import EIO
            from ceph_tpu.codec.interface import EcError

            raise EcError(
                EIO, f"aggregated {self.WHAT} launch failed: {g.error!r}"
            )
        ticket._value = g.host[ticket._start : ticket._start + ticket._stripes]


# every live aggregator, weakly held (ISSUE 13): the tier-1 leak gate's
# teardown drain and the chaos harness's end-of-run drain reach all
# instances — the process-wide defaults AND test-local ones
_AGGREGATORS: "weakref.WeakSet[LaunchAggregator]" = weakref.WeakSet()


def drain_all_aggregators() -> None:
    """Flush + settle every live aggregator (the tier-1 leak gate and
    the chaos harness's end-of-run drain)."""
    for agg in list(_AGGREGATORS):
        agg.drain()


class OffloadService:
    """One registered device-offload service: a name, the aggregator
    factory that builds (or returns) its process-wide instance, the QoS
    lane its launches ride (ops/launch_scheduler lanes: client /
    recovery / background) and a one-line description of its
    byte-identical host oracle.  The aggregator subclass IS the plan
    builder + oracle pair; the registry names them so generic code
    (perf export, drains, tools) can reach every service uniformly."""

    __slots__ = ("name", "factory", "lane", "oracle", "doc", "_instance")

    def __init__(self, name, factory, lane, oracle, doc):
        self.name = name
        self.factory = factory
        self.lane = lane
        self.oracle = oracle
        self.doc = doc
        self._instance: LaunchAggregator | None = None

    def aggregator(self) -> "LaunchAggregator":
        if self._instance is None:
            self._instance = self.factory()
        return self._instance


_SERVICES: "OrderedDict[str, OffloadService]" = OrderedDict()
_SERVICES_LOCK = _lockdep_make_lock("offload_services")


def register_service(
    name: str,
    factory,
    *,
    lane: str = "client",
    oracle: str = "",
    doc: str = "",
) -> OffloadService:
    """Register (or re-register, idempotently) an offload service.
    `factory` returns the service's process-wide LaunchAggregator;
    factories managing their own singleton (the EC default_*_aggregator
    trio) are called at most once per registry entry anyway."""
    with _SERVICES_LOCK:
        svc = _SERVICES.get(name)
        if svc is None:
            svc = _SERVICES[name] = OffloadService(
                name, factory, lane, oracle, doc
            )
        return svc


def service(name: str) -> OffloadService:
    """The registered service record, importing the module that
    registers it on first miss (the registry is populated by the
    service modules' import side effects)."""
    with _SERVICES_LOCK:
        svc = _SERVICES.get(name)
    if svc is None:
        _import_builtin_services()
        with _SERVICES_LOCK:
            svc = _SERVICES.get(name)
    if svc is None:
        raise KeyError(f"no offload service {name!r}")
    return svc


def service_aggregator(name: str) -> "LaunchAggregator":
    """The named service's shared process-wide aggregator."""
    return service(name).aggregator()


def offload_services() -> tuple[str, ...]:
    """Names of every registered service, registration-ordered."""
    _import_builtin_services()
    with _SERVICES_LOCK:
        return tuple(_SERVICES)


def _import_builtin_services() -> None:
    """Import the modules whose import side effects register the
    built-in services (EC trio, device crc32c, device compressor)."""
    import ceph_tpu.codec.matrix_codec  # noqa: F401  (encode/decode/verify)
    import ceph_tpu.compressor.device  # noqa: F401  (compress)
    import ceph_tpu.ops.checksum_offload  # noqa: F401  (csum)


def offload_perf_dump() -> dict[str, object]:
    """Flat JSON-safe per-service counter export — the `offload.*`
    slice of the OSD perf report, re-exported by the mgr Prometheus
    scrape as the ceph_tpu_offload_* families.  Services whose
    aggregator was never built contribute zeros (a family that appears
    only after first traffic would flap the metrics lint)."""
    _import_builtin_services()
    out: dict[str, object] = {}
    with _SERVICES_LOCK:
        entries = list(_SERVICES.items())
    for name, svc in entries:
        agg = svc.aggregator()
        for counter, val in agg.perf.dump().items():
            out[f"{name}.{counter}"] = val
        out[f"{name}.pending"] = agg.pending()
    out["services"] = len(entries)
    return out
