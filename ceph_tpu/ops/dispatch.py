"""Device-launch accounting for the coding hot path.

Three counters, incremented exactly once per host->device kernel dispatch
by the lowest-level python wrapper of each coding path (PackedPlan, the
Pallas CodingPlan, the jnp bitsliced fallback, xor_reduce, the sharded
shard_map dispatch): `LAUNCHES` totals every coding dispatch,
`DECODE_LAUNCHES` additionally totals the dispatches issued on behalf of
a decode (recovery / degraded read), and `SHARDED_LAUNCHES` additionally
totals the dispatches that spanned more than one device of the mesh
(parallel/dispatch.py data-parallel fan-out).  Tests assert batching
invariants against them — "encoding N stripes cost 1 dispatch",
"recovering N same-pattern objects cost O(1) decode dispatches", "a
bulk batch crossed the shard threshold and spanned the mesh" — so a
regression back to per-stripe launches (or silently single-device
launches) fails tier-1 instead of only showing up as a bench number
(ISSUE 3 / ISSUE 5 / ISSUE 6 launch-counter contracts).

Caveat: counting happens at python dispatch time.  A coding call traced
inside an OUTER jax.jit (bench.py's serial chain) runs the wrapper once
at trace time, so executions of the compiled program are not re-counted.
That is the correct reading for the batching invariant — the outer
program still contains one fused encode — but it means the counter is a
dispatch-shape witness, not an execution profiler.
"""

from __future__ import annotations

import threading

from ceph_tpu.common.lockdep import make_lock


class LaunchCounter:
    """Monotonic totals: device dispatches, stripes and bytes they carried."""

    __slots__ = ("_lock", "launches", "stripes", "bytes")

    def __init__(self) -> None:
        self._lock = make_lock("launch_counter")
        self.launches = 0
        self.stripes = 0
        self.bytes = 0

    def record(self, stripes: int, nbytes: int) -> None:
        with self._lock:
            self.launches += 1
            self.stripes += int(stripes)
            self.bytes += int(nbytes)

    def snapshot(self) -> dict[str, int]:
        with self._lock:
            return {
                "launches": self.launches,
                "stripes": self.stripes,
                "bytes": self.bytes,
            }

    def reset(self) -> None:
        with self._lock:
            self.launches = 0
            self.stripes = 0
            self.bytes = 0


LAUNCHES = LaunchCounter()

# Decode-only dispatches (recovery / degraded reads).  Every decode
# dispatch is counted here AND in LAUNCHES: LAUNCHES stays the
# process-wide total every existing invariant is written against, while
# this counter isolates the read/recovery half so "N objects recovered
# in one window = O(1) decode launches" is assertable on its own.
DECODE_LAUNCHES = LaunchCounter()

# Multi-device dispatches (parallel/dispatch.py shard_map fan-out over
# the stripe axis).  Counted here AND in LAUNCHES (and DECODE_LAUNCHES
# when it is a decode): by construction SHARDED_LAUNCHES.launches <=
# LAUNCHES.launches, and a 1-device process records zero here — the
# consistency contract tests/test_perf_smoke.py pins.
SHARDED_LAUNCHES = LaunchCounter()

# Verify-only dispatches (ISSUE 9: the deep-scrub compare-only kernel,
# ops/packed_gf.PackedVerifyPlan).  Counted here AND in LAUNCHES, like
# the decode counter: LAUNCHES stays the process-wide total, while this
# isolates the integrity-check traffic so "a whole scrub chunk verified
# in one launch" is assertable on its own (the acceptance criterion's
# VERIFY_LAUNCHES > 0 witness).
VERIFY_LAUNCHES = LaunchCounter()


class DeviceOccupancy:
    """Devices-per-launch distribution: how wide each coding dispatch
    ran.  Exact per-count buckets (device counts are tiny integers, a
    log2 histogram would blur 6 vs 8 chips) plus a device-launch total so
    mean occupancy is derivable from two scalars."""

    __slots__ = ("_lock", "counts", "device_launches")

    def __init__(self) -> None:
        self._lock = make_lock("device_occupancy")
        self.counts: dict[int, int] = {}
        self.device_launches = 0  # sum(devices) over every dispatch

    def record(self, devices: int) -> None:
        with self._lock:
            self.counts[devices] = self.counts.get(devices, 0) + 1
            self.device_launches += devices

    def snapshot(self) -> dict[int, int]:
        with self._lock:
            return dict(self.counts)

    def reset(self) -> None:
        with self._lock:
            self.counts.clear()
            self.device_launches = 0


DEVICES_PER_LAUNCH = DeviceOccupancy()

class PipelineGauges:
    """Process-wide accounting for the depth-N async launch ring
    (ISSUE 11, ops/offload_runtime.LaunchAggregator):

    - ``depth``: the configured ``ec_tpu_pipeline_depth`` (gauge),
    - ``inflight`` / ``inflight_peak``: launches dispatched but not yet
      settled, now and at peak,
    - ``drains``: ring-full settles (the submitter paid the oldest
      launch's wait so the new one could overlap it).
    """

    __slots__ = ("_lock", "depth", "inflight", "inflight_peak", "drains")

    def __init__(self) -> None:
        self._lock = make_lock("pipeline_gauges")
        self.depth = 0
        self.inflight = 0
        self.inflight_peak = 0
        self.drains = 0

    def set_depth(self, depth: int) -> None:
        with self._lock:
            self.depth = int(depth)

    def launch(self) -> None:
        with self._lock:
            self.inflight += 1
            self.inflight_peak = max(self.inflight_peak, self.inflight)

    def settle(self) -> None:
        with self._lock:
            self.inflight = max(0, self.inflight - 1)

    def record_drain(self) -> None:
        with self._lock:
            self.drains += 1

    def snapshot(self) -> dict[str, int]:
        with self._lock:
            return {
                "depth": self.depth,
                "inflight": self.inflight,
                "inflight_peak": self.inflight_peak,
                "drains": self.drains,
            }


PIPELINE = PipelineGauges()


# Launches that completed on the HOST ORACLE instead of the device
# (ops/guard.py DeviceGuard fallback: launch deadline exceeded, device
# error, or degraded-mode bypass).  NOT counted in LAUNCHES — these never
# reached the device — so LAUNCHES keeps meaning "device dispatches" for
# every existing batching invariant, and FALLBACK_LAUNCHES isolates the
# degraded-mode traffic the TPU_BACKEND_DEGRADED health check describes.
FALLBACK_LAUNCHES = LaunchCounter()


def record_fallback(stripes: int, nbytes: int) -> None:
    """Record one host-oracle fallback carrying `stripes` stripes /
    `nbytes` input bytes (the aggregator's degraded-path accounting)."""
    FALLBACK_LAUNCHES.record(stripes, nbytes)


class ClayCounters:
    """CLAY coupled-layer coding (ops/clay_gf.py): device programs
    launched for encode and for decode (layered decode and single-chunk
    repair), the stripes and the layered rounds they carried, and the
    stripes coupled on the host instead (the codec's chunk-level oracle
    and the programs' numpy executor).  `host_stripes` stays 0 on the
    served path; a launch that fell back to the host is also counted on
    FALLBACK_LAUNCHES by the offload runtime."""

    FIELDS = ("encode_launches", "decode_launches", "stripes", "rounds",
              "host_stripes")

    __slots__ = ("_lock", "_vals")

    def __init__(self) -> None:
        self._lock = make_lock("clay_counters")
        self._vals = dict.fromkeys(self.FIELDS, 0)

    def record_launch(self, decode: bool, stripes: int, rounds: int) -> None:
        with self._lock:
            self._vals["decode_launches" if decode else "encode_launches"] += 1
            self._vals["stripes"] += int(stripes)
            self._vals["rounds"] += int(rounds)

    def record_host(self, stripes: int) -> None:
        with self._lock:
            self._vals["host_stripes"] += int(stripes)

    def snapshot(self) -> dict[str, int]:
        with self._lock:
            return dict(self._vals)

    def reset(self) -> None:
        with self._lock:
            self._vals = dict.fromkeys(self.FIELDS, 0)


CLAY = ClayCounters()


def record_launch(
    stripes: int, nbytes: int, decode: bool = False, devices: int = 1,
    verify: bool = False,
) -> None:
    """Record one device dispatch carrying `stripes` stripes / `nbytes`
    input bytes on the global counter(s).  `decode=True` marks a dispatch
    issued on behalf of a decode (the coder's kind, threaded down from
    PLAN_CACHE.decode_coder) so it also lands on DECODE_LAUNCHES;
    `verify=True` marks a compare-only scrub dispatch
    (PLAN_CACHE.verify_coder) landing on VERIFY_LAUNCHES the same way.
    `devices` is how many mesh devices the dispatch spanned (the sharded
    dispatcher passes its stripe-shard count); > 1 additionally lands on
    SHARDED_LAUNCHES and every value feeds the occupancy distribution.

    Flight recorder hook (ISSUE 8): a dispatch running under an
    aggregator launch annotates devices/kind onto the ACTIVE flight
    record; a dispatch with no active record (eager bulk paths, bench
    loops) appends a lightweight span-less record so `dump_flight` and
    the trace export still show it on the timeline."""
    LAUNCHES.record(stripes, nbytes)
    if decode:
        DECODE_LAUNCHES.record(stripes, nbytes)
    if verify:
        VERIFY_LAUNCHES.record(stripes, nbytes)
    if devices > 1:
        SHARDED_LAUNCHES.record(stripes, nbytes)
    DEVICES_PER_LAUNCH.record(devices)
    from .flight_recorder import flight_recorder

    fr = flight_recorder()
    rec = fr.active()
    kind = "verify" if verify else ("decode" if decode else "encode")
    if rec is not None:
        # skip records that already settled: an abandoned watchdog
        # worker whose device unwedges minutes later still holds this
        # record through its contextvars copy, and a post-commit
        # rewrite would corrupt the ring under readers
        if not rec["settle_ts"]:
            rec["devices"] = max(rec["devices"], int(devices))
            rec["flags"]["sharded"] = rec["flags"]["sharded"] or devices > 1
            if decode or verify:
                rec["kind"] = kind
    else:
        fr.record_raw(kind, stripes, nbytes, devices)


def perf_dump() -> dict[str, object]:
    """JSON-safe export of every dispatch counter — the `ec_dispatch`
    section of the OSD's asok `perf dump` and (flattened) of the
    MMgrReport payload the mgr Prometheus scrape re-exports.  The
    devices-per-launch distribution rides as `devices_per_launch.<n>`
    scalars so the scrape renders one labeled-by-dot series per width."""
    out: dict[str, object] = {}
    for prefix, counter in (
        ("", LAUNCHES),
        ("decode_", DECODE_LAUNCHES),
        ("verify_", VERIFY_LAUNCHES),
        ("sharded_", SHARDED_LAUNCHES),
        ("fallback_", FALLBACK_LAUNCHES),
    ):
        for name, val in counter.snapshot().items():
            out[f"{prefix}{name}"] = val
    for name, val in CLAY.snapshot().items():
        out[f"clay.{name}"] = val
    out["device_launches"] = DEVICES_PER_LAUNCH.device_launches
    for devices, launches in sorted(DEVICES_PER_LAUNCH.snapshot().items()):
        out[f"devices_per_launch.{devices}"] = launches
    # degraded-backend state (ops/guard.py): `backend_degraded` is the
    # gauge the prometheus scrape exports next to the fallback counters
    from .guard import device_guard

    snap = device_guard().snapshot()
    out["backend_degraded"] = snap["degraded"]
    out["backend_degraded_total"] = snap["degraded_total"]
    out["backend_probes"] = snap["probes"]
    out["backend_probe_failures"] = snap["probe_failures"]
    # device-utilization accounting derived from the flight recorder
    # (ISSUE 8): busy-seconds weighted by launch width, occupancy % of
    # the observation window, and the flight-ring health scalars.  The
    # OSD's MMgrReport re-exports the first two under their canonical
    # prometheus names (ceph_tpu_ec_device_busy_seconds /
    # ceph_tpu_ec_device_occupancy).
    from .flight_recorder import flight_recorder

    util = flight_recorder().utilization()
    out["device_busy_seconds"] = round(util["device_busy_seconds"], 6)
    out["device_occupancy"] = round(util["occupancy"], 6)
    out["flight_records"] = int(util["span_records"])
    out["flight_mean_queue_wait_ms"] = round(
        util["mean_queue_wait_s"] * 1e3, 3
    )
    # launch-scheduler QoS counters (ISSUE 9): per-class enqueue/dequeue
    # totals, accumulated queue wait, and the current queue-depth gauge,
    # as `sched.<class>.<counter>` scalars — the prometheus scrape
    # renders one labeled-by-dot series per class/counter pair
    from .launch_scheduler import launch_scheduler

    for name, val in launch_scheduler().perf_dump().items():
        out[f"sched.{name}"] = val
    # pipelined-dispatch ring (ISSUE 11): configured depth,
    # current/peak in-flight launches, ring-full drains
    for name, val in PIPELINE.snapshot().items():
        out[f"pipeline.{name}"] = val
    # device-resident chunk cache (ISSUE 11): hit/miss/evict counters
    # plus the resident-bytes/entries gauges, as `cache.<counter>`
    # scalars -> ceph_tpu_ec_dispatch_cache_* prometheus families
    from .device_cache import device_chunk_cache

    for name, val in device_chunk_cache().perf_dump().items():
        out[f"cache.{name}"] = val
    return out
