"""Device-backend watchdog — deadline-bounded launches + degraded state.

The failure mode this contains showed up in the bench trajectory twice: a
TPU backend that wedges (hung compile, dead runtime) does not error, it
BLOCKS — and every EC write and recovery in the process then stalls
forever behind the aggregators.  bench.py grew a stage watchdog for its
own runs; this is the data-path version:

- `call()` runs a device dispatch (or its blocking materialization)
  under the `ec_tpu_launch_timeout_ms` deadline on a watchdog thread and
  raises DeviceTimeout instead of hanging the caller.
- A timeout (or a device error with a healthy host recompute) marks the
  backend DEGRADED: subsequent launches bypass the device entirely and
  run on the byte-identical host oracle (gf/bitslice.py) until a probe
  heals the state.  The degraded flag feeds the `TPU_BACKEND_DEGRADED`
  health check through the OSD status -> mgr digest -> mon pipeline.
- While degraded, `maybe_probe()` re-tries the device at most every
  `ec_tpu_probe_interval_ms` with a tiny compile probe under the same
  deadline — completing it self-heals dispatch back to the TPU path.

The guard is process-wide (like the plan cache and the aggregators): one
wedged runtime affects every PG in the process, so one state machine
owns the verdict.
"""

from __future__ import annotations

import queue
import threading
import time

from ceph_tpu.common.lockdep import make_lock
from ceph_tpu.common.tracer import section


class DeviceTimeout(RuntimeError):
    """A guarded device call exceeded its per-launch deadline."""


class _LaunchWorkers:
    """A few long-lived daemon threads that run every guarded device call.

    Device work stays on the same OS threads for the life of the process
    instead of a fresh thread per launch; with a thread per launch, one
    v5e chip run of the 11-OSD smoke cluster died with SIGSEGV during
    degraded reads (PR 21; cause not isolated).  A worker stuck past its
    deadline is abandoned and a replacement is started, so a wedged
    device never starves the pool."""

    WORKERS = 4

    def __init__(self) -> None:
        self._jobs: queue.SimpleQueue = queue.SimpleQueue()
        for _ in range(self.WORKERS):
            self.spawn()

    def spawn(self) -> None:
        threading.Thread(
            target=self._run, daemon=True, name="ec-launch-worker"
        ).start()

    def _run(self) -> None:
        _ON_WORKER.active = True
        while True:
            self._jobs.get()()

    def submit(self, job) -> None:
        self._jobs.put(job)


_ON_WORKER = threading.local()
_WORKERS: _LaunchWorkers | None = None
_WORKERS_LOCK = make_lock("launch_workers")


def _launch_workers() -> _LaunchWorkers:
    global _WORKERS
    with _WORKERS_LOCK:
        if _WORKERS is None:
            _WORKERS = _LaunchWorkers()
        return _WORKERS


# the caller's layer section for each kind of guarded call, by the last
# word of the `what` it gives: the time the caller sat blocked on the
# device (or on a launch worker running device work for it)
_WAIT_SECTIONS = {
    "dispatch": "offload.dispatch_wait",
    "probe": "offload.dispatch_wait",
    "materialize": "offload.settle_wait",
    "put": "offload.cache_wait",
    "fetch": "offload.cache_wait",
}


def _wait_section(what: str) -> str:
    return _WAIT_SECTIONS.get(what.rsplit(" ", 1)[-1], "offload.dispatch_wait")


def _default_probe() -> None:
    """Tiny compile probe: one shared-kernel dispatch + materialization.
    Cheap (the (8,8)x(1,128) xor_matmul shape is compiled once per
    process) but it exercises exactly the path real launches take:
    dispatch, device execute, D2H."""
    import numpy as np

    import jax.numpy as jnp

    from ceph_tpu.ops.xor_mm import xor_matmul

    bm = jnp.asarray(np.eye(8, dtype=np.uint8))
    x = jnp.asarray(np.arange(128, dtype=np.uint8).reshape(1, 128))
    np.asarray(xor_matmul(bm, x))


class DeviceGuard:
    """Per-process launch deadline + DEGRADED/healthy state machine."""

    def __init__(self, timeout_ms: int | None = None,
                 probe_interval_ms: int | None = None):
        if timeout_ms is None or probe_interval_ms is None:
            from ceph_tpu.common.options import OPTIONS

            if timeout_ms is None:
                timeout_ms = int(OPTIONS["ec_tpu_launch_timeout_ms"].default)
            if probe_interval_ms is None:
                probe_interval_ms = int(
                    OPTIONS["ec_tpu_probe_interval_ms"].default
                )
        self._lock = make_lock("device_guard")
        self.timeout_ms = int(timeout_ms)
        self.probe_interval_ms = int(probe_interval_ms)
        self.degraded = False
        self.degraded_since = 0.0
        self.reason = ""
        self.degraded_total = 0  # transitions into DEGRADED
        self.probes = 0
        self.probe_failures = 0
        self._last_probe = 0.0
        self._probe_cold = True  # first probe of a degrade episode

    def configure(self, timeout_ms: int | None = None,
                  probe_interval_ms: int | None = None) -> None:
        """Apply live config (the OSD wires its runtime observers here)."""
        if timeout_ms is not None:
            self.timeout_ms = int(timeout_ms)
        if probe_interval_ms is not None:
            self.probe_interval_ms = int(probe_interval_ms)

    # -- deadline-bounded execution ------------------------------------------

    def call(self, fn, what: str = "launch", timeout_ms: int | None = None):
        """Run `fn` on a launch worker under the per-launch deadline (or
        an explicit `timeout_ms` override).  Deadline <= 0 runs inline
        (watchdog off), as does a call made from a worker, which is
        already under its caller's deadline.  On timeout the worker is
        abandoned (daemon; its eventual result is discarded), a
        replacement starts, and DeviceTimeout raises — the caller falls
        back to the host oracle, which never touches the wedged
        runtime.  The caller's wait is the layer section
        `_wait_section(what)`."""
        with section(_wait_section(what)):
            return self._call(fn, what, timeout_ms)

    def _call(self, fn, what: str, timeout_ms: int | None):
        t_ms = self.timeout_ms if timeout_ms is None else timeout_ms
        if t_ms <= 0 or getattr(_ON_WORKER, "active", False):
            return fn()
        box: list = []
        err: list[BaseException] = []
        done = threading.Event()
        abandoned = threading.Event()
        # carry contextvars (the tracing span scope) onto the worker so a
        # guarded dispatch records its codec spans in the caller's trace
        import contextvars

        ctx = contextvars.copy_context()

        def run() -> None:
            if abandoned.is_set():
                return  # timed out while queued: the caller fell back
            try:
                box.append(ctx.run(fn))
            except BaseException as e:  # re-raised on the calling thread
                err.append(e)
            finally:
                done.set()

        workers = _launch_workers()
        workers.submit(run)
        if not done.wait(t_ms / 1000.0):
            abandoned.set()
            workers.spawn()
            # annotate the launch's flight record (ISSUE 8): the deadline
            # verdict belongs to THIS launch's timeline, not just the
            # process-wide degraded gauge
            from ceph_tpu.ops.flight_recorder import flight_recorder

            flight_recorder().flag_active("timeout")
            raise DeviceTimeout(f"device {what} exceeded {t_ms} ms deadline")
        if err:
            raise err[0]
        return box[0]

    # -- state machine --------------------------------------------------------

    def mark_degraded(self, reason: str) -> None:
        with self._lock:
            entered = not self.degraded
            if entered:
                self.degraded = True
                self.degraded_since = time.monotonic()
                self.degraded_total += 1
                # next launch may probe immediately: a transient error
                # (one bad compile) should not cost a full interval.
                # -inf, not 0.0 — monotonic() starts at boot, so on a
                # freshly booted host 0.0 is less than one interval ago
                # and would gate the heal probe
                self._last_probe = float("-inf")
                self._probe_cold = True
            self.reason = reason
        if entered:
            # the device-resident chunk cache (ops/device_cache.py) holds
            # buffers a wedged runtime can no longer serve — drop them on
            # the transition so the host-fallback path never consults a
            # cache it cannot materialize (puts are gated while degraded)
            from .device_cache import device_chunk_cache

            device_chunk_cache().clear()

    def mark_healthy(self) -> None:
        with self._lock:
            self.degraded = False
            self.degraded_since = 0.0
            self.reason = ""

    def maybe_probe(self, probe_fn=None) -> bool:
        """While DEGRADED, re-probe the device at most every probe
        interval; returns True when the probe healed the backend (the
        caller should dispatch to the device again).  Healthy state
        returns True without probing."""
        with self._lock:
            if not self.degraded:
                return True
            if self.probe_interval_ms <= 0:
                return False
            now = time.monotonic()
            if (now - self._last_probe) * 1000.0 < self.probe_interval_ms:
                return False
            self._last_probe = now
            self.probes += 1
            cold = self._probe_cold
            self._probe_cold = False
        try:
            # the probe runs on a SUBMITTER'S data path, so after the
            # first attempt of an episode it gets a deadline much
            # shorter than real launches: a still-wedged device costs
            # that submitter ~the probe interval, not the full launch
            # timeout, and leaks at most one abandoned thread per
            # interval instead of stacking them.  The FIRST probe keeps
            # the full deadline — it may carry the probe kernel's
            # compile, and even a timed-out attempt warms the compile
            # cache in its abandoned thread so later probes fit the
            # short window.
            probe_ms = self.timeout_ms
            if probe_ms > 0 and not cold:
                probe_ms = min(probe_ms, max(250, self.probe_interval_ms))
            self.call(probe_fn or _default_probe, what="probe",
                      timeout_ms=probe_ms)
        except BaseException:
            with self._lock:
                self.probe_failures += 1
            return False
        self.mark_healthy()
        return True

    def snapshot(self) -> dict[str, object]:
        with self._lock:
            return {
                "degraded": int(self.degraded),
                "degraded_for_sec": (
                    time.monotonic() - self.degraded_since
                    if self.degraded
                    else 0.0
                ),
                "degraded_total": self.degraded_total,
                "reason": self.reason,
                "probes": self.probes,
                "probe_failures": self.probe_failures,
            }


_GUARD: DeviceGuard | None = None


def device_guard() -> DeviceGuard:
    """The process-wide guard (built lazily from option defaults, like
    the default aggregators; daemons with a live Config re-configure it
    through their runtime observers)."""
    global _GUARD
    if _GUARD is None:
        _GUARD = DeviceGuard()
    return _GUARD
