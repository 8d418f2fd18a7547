"""HBM mempool ledger (ISSUE 13): accounting, reconciliation, pressure
staging, and the health pipeline.

Acceptance shape: every HBM holder (pipeline in-flight ring, device
chunk cache, sharded placements) accounts its bytes in the process-wide
ledger; ledger totals reconcile against the sum of live tracked-buffer
nbytes under the 8-concurrent-submitter harness at pipeline depth 4
with faults armed (host-fallback and sticky-error settles release their
holds); the pressure layer trims cache → pipeline depth in order and
raises/clears TPU_HBM_PRESSURE through the mon; and the device cache's cap-shrink observer recomputes
resident bytes from the entry index instead of trusting a drifted
counter."""

import asyncio
import gc
import threading

import numpy as np
import pytest

from ceph_tpu.codec import ErasureCodeTpuRs
from ceph_tpu.codec.matrix_codec import (
    DecodeAggregator,
    EncodeAggregator,
    VerifyAggregator,
)
from ceph_tpu.common.fault_injector import global_injector
from ceph_tpu.common.mempool import (
    POOLS,
    MempoolLedger,
    ledger,
    track_buffer,
)
from ceph_tpu.ops.device_cache import DeviceChunkCache, device_chunk_cache
from ceph_tpu.ops.guard import device_guard


@pytest.fixture(autouse=True)
def _clean_state():
    yield
    global_injector().clear()
    device_guard().mark_healthy()
    led = ledger()
    led.configure(target_bytes=0)
    led.check_pressure()  # releases any caps a pressure test armed


def make_rs(k=4, m=2):
    ec = ErasureCodeTpuRs()
    ec.init({"k": str(k), "m": str(m)})
    return ec


class TestLedgerCore:
    def test_alloc_resize_free_and_peaks(self):
        led = MempoolLedger()
        h = led.alloc("ec_pipeline_inflight", 1000)
        assert led.current_bytes("ec_pipeline_inflight") == 1000
        h.resize(4000)
        assert led.current_bytes("ec_pipeline_inflight") == 4000
        snap = led.snapshot()["ec_pipeline_inflight"]
        assert snap["peak_bytes"] == 4000 and snap["buffers"] == 1
        h.free()
        h.free()  # idempotent: the finalizer double-release shape
        assert led.current_bytes("ec_pipeline_inflight") == 0
        assert led.snapshot()["ec_pipeline_inflight"]["peak_bytes"] == 4000
        led.reset_peaks()
        assert led.snapshot()["ec_pipeline_inflight"]["peak_bytes"] == 0

    def test_predeclared_pools_and_dynamic_creation(self):
        led = MempoolLedger()
        assert set(led.snapshot()) == set(POOLS)
        led.alloc("experimental", 10)
        assert led.snapshot()["experimental"]["bytes"] == 10

    def test_track_buffer_frees_on_gc(self):
        import jax.numpy as jnp

        led = ledger()
        base = led.current_bytes("scratch")
        buf = track_buffer(jnp.zeros(2048, dtype=jnp.uint8), "scratch")
        assert led.current_bytes("scratch") == base + 2048
        del buf
        gc.collect()
        assert led.current_bytes("scratch") == base

    def test_track_buffer_skips_host_arrays(self):
        led = ledger()
        base = led.current_bytes("scratch")
        arr = np.zeros(4096, dtype=np.uint8)
        assert track_buffer(arr, "scratch") is arr
        assert led.current_bytes("scratch") == base

    def test_debug_mode_shards_by_call_site(self):
        led = MempoolLedger(debug=True)
        h = led.alloc("scratch", 512)
        dump = led.dump()
        assert dump["debug"]
        (site,) = [s for s in dump["by_site"] if s.startswith("scratch@")]
        assert "test_mempool.py" in site
        assert dump["by_site"][site]["bytes"] == 512
        h.free()
        assert not led.dump()["by_site"]

    def test_finalizer_reentrancy_under_lock(self):
        """A cyclic-GC pass can fire a tracked buffer's finalizer (which
        frees its handle through the ledger lock) INSIDE an accounting
        call that already holds the lock — the free must re-enter, not
        self-deadlock (the tier-1 hang this pins down)."""
        led = MempoolLedger()
        h = led.alloc("scratch", 10)
        with led._lock:  # what alloc/_resize hold when GC strikes
            h.free()
        assert led.current_bytes("scratch") == 0

    def test_gc_finalizers_defer_instead_of_locking(self):
        """Buffer finalizers fire in GC context, where acquiring ANY
        lock can self-deadlock the interrupted thread (under lockdep
        every instrumented acquire shares one plain registry mutex
        whose critical sections allocate).  The finalizer must only
        enqueue; the books close on the next accounting call."""
        import jax.numpy as jnp

        led = MempoolLedger()
        buf = jnp.zeros(512, dtype=jnp.uint8)
        led.alloc("scratch", 512, buf=buf)
        del buf
        gc.collect()
        # the finalizer ran but took no lock: the handle is parked on
        # the deferred queue, counters untouched
        assert led._deferred, "finalizer freed inline (GC-context lock)"
        assert led._pools["scratch"].bytes == 512
        # first accounting read drains it
        assert led.current_bytes("scratch") == 0
        assert not led._deferred

    def test_alloc_drains_deferred_so_peaks_track_concurrency(self):
        """Transient tracked buffers in an allocate-only loop (the
        bench hbm_peak_bytes shape: no accounting READ between
        iterations) must not pile up as deferred dead bytes — alloc
        drains first, so peaks reflect true concurrent residency."""
        import jax.numpy as jnp

        led = MempoolLedger()
        for i in range(20):
            buf = jnp.zeros(1024, dtype=jnp.uint8) + i
            led.alloc("scratch", 1024, buf=buf)
            del buf
            gc.collect()
        # at most the newest allocation is still counted (its buffer
        # just died; the NEXT accounting call collects it)
        assert led.total_device_bytes() <= 1024
        assert led.peak_total_bytes() <= 3 * 1024, led.peak_total_bytes()

    def test_explicit_free_detaches_the_finalizer(self):
        """A buffer accounted more than once gets a fresh handle per
        cycle; the explicit free must detach the old finalizer or the
        buffer pins one dead handle per cycle for its lifetime."""
        import jax.numpy as jnp

        led = MempoolLedger()
        buf = jnp.zeros(256, dtype=jnp.uint8)
        for _ in range(5):
            led.alloc("scratch", 256, buf=buf).free()
        del buf
        gc.collect()
        # every finalizer was detached at free: nothing enqueued
        assert not led._deferred
        assert led.current_bytes("scratch") == 0

    def test_reconcile_exposes_counter_drift(self):
        led = MempoolLedger()
        led.alloc("device_cache", 1000)
        assert led.reconcile()["device_cache"]["drift"] == 0
        # simulate a subsystem decrementing its counter wrongly (the
        # drift class the device-cache fix addresses)
        with led._lock:
            led._pools["device_cache"].bytes -= 400
        assert led.reconcile()["device_cache"]["drift"] == -400

    def test_per_device_breakdown(self):
        import jax.numpy as jnp

        led = MempoolLedger()
        buf = jnp.zeros(4096, dtype=jnp.uint8)  # held: alive through the dump
        led.alloc("scratch", 4096, buf=buf)
        led.alloc("scratch", 100)  # no placement known
        per = led.per_device()
        assert sum(per.values()) == 4196
        assert per.get("unplaced") == 100
        del buf
        # a byte count that does not divide the device set still sums
        # exactly (the remainder lands on the first device)
        led2 = MempoolLedger()
        h = led2.alloc("scratch", 100)
        h.devices = ("a", "b", "c")
        assert sum(led2.per_device().values()) == 100


class TestDeviceCacheAccounting:
    def test_ledger_tracks_entry_lifecycle(self):
        led = ledger()
        base = led.current_bytes("device_cache")
        cc = DeviceChunkCache(max_bytes=1 << 20)
        cc.put("a", 0, 1, np.zeros(4096, dtype=np.uint8))
        cc.put("b", 0, 1, np.zeros(8192, dtype=np.uint8))
        assert led.current_bytes("device_cache") == base + 12288
        assert cc.perf_dump()["resident_bytes"] == 12288
        cc.invalidate_object("a")
        assert led.current_bytes("device_cache") == base + 8192
        cc.clear()
        assert led.current_bytes("device_cache") == base

    def test_cap_shrink_recomputes_resident_bytes(self):
        """The ISSUE 13 satellite fix: the runtime cap-shrink observer
        must recompute resident bytes from the entry index — a drifted
        (stale-low) counter would otherwise evict too little and leave
        the cache over its new cap forever."""
        led = ledger()
        base = led.current_bytes("device_cache")
        cc = DeviceChunkCache(max_bytes=1 << 20)
        for i in range(4):
            cc.put(f"o{i}", 0, 1, np.full(65536, i, dtype=np.uint8))
        # inject historical counter drift: the counter reads 100000 low
        with cc._lock:
            cc._bytes -= 100000
        cc.configure(max_bytes=128 << 10)
        dump = cc.perf_dump()
        with cc._lock:
            index_bytes = sum(e.nbytes for e in cc._entries.values())
        assert dump["resident_bytes"] == index_bytes
        assert dump["resident_bytes"] <= 128 << 10, (
            "cap shrink trusted the drifted counter and under-evicted"
        )
        # the ledger agreed with the index all along (per-entry handles)
        assert led.current_bytes("device_cache") == base + index_bytes
        cc.clear()

    def test_trim_for_pressure_evicts_lru_first(self):
        cc = DeviceChunkCache(max_bytes=1 << 20)
        cc.put("old", 0, 1, np.zeros(4096, dtype=np.uint8))
        cc.put("new", 0, 1, np.zeros(4096, dtype=np.uint8))
        assert cc.get("new", 0, 1) is not None  # refresh LRU position
        freed = cc.trim_for_pressure(1)
        assert freed == 4096
        assert cc.get("old", 0, 1) is None
        assert cc.get("new", 0, 1) is not None
        cc.clear()


class TestReconciliationUnderLoad:
    def test_8_submitters_depth4_with_faults(self):
        """The acceptance harness: 8 concurrent submitters driving
        encode+decode+verify through depth-4 pipelines WITH launch
        faults armed (1-in-3 dispatches fail to the host oracle).  After
        drain the in-flight pools read zero — the host-fallback path
        released its holds — the handle registry reconciles against the
        counters with zero drift."""
        led = ledger()
        ec = make_rs(4, 2)
        agg = EncodeAggregator(window=4, pipeline_depth=4)
        dagg = DecodeAggregator(window=4, pipeline_depth=4)
        vagg = VerifyAggregator(window=4, pipeline_depth=4)
        inj = global_injector()
        inj.inject_probabilistic("codec.launch", 3)
        errors: list[BaseException] = []

        def submitter(tid: int) -> None:
            rng = np.random.default_rng(1000 + tid)
            try:
                for i in range(5):
                    # >= PACKED_MIN_BYTES so the coalesced launches take
                    # the packed bulk kernel
                    data = rng.integers(0, 256, (4, 4, 4096), dtype=np.uint8)
                    par = np.asarray(agg.submit(ec, data))
                    assert np.array_equal(
                        par, np.asarray(ec.encode_array_host(data))
                    )
                    full = np.concatenate([data, par], axis=1)
                    erasures = [int(rng.integers(0, 6))]
                    idx = ec.decode_index(erasures)
                    rec = np.asarray(
                        dagg.submit(ec, erasures, full[:, idx, :])
                    )
                    assert np.array_equal(rec, full[:, erasures, :])
                    bitmap = np.asarray(vagg.submit(ec, full))
                    assert not bitmap.any()
            except BaseException as e:  # surfaced after join
                errors.append(e)

        threads = [
            threading.Thread(target=submitter, args=(i,)) for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        inj.clear()
        for a in (agg, dagg, vagg):
            a.drain()
        assert not errors, errors
        assert led.current_bytes("ec_pipeline_inflight") == 0
        assert led.current_bytes("verify") == 0
        drift = {
            k: v["drift"] for k, v in led.reconcile().items() if v["drift"]
        }
        assert not drift, drift

    def test_sticky_error_settle_releases_hold(self, monkeypatch):
        """A launch that fails on the device AND on the host recompute
        goes sticky — the historical leak shape.  Its settle must still
        zero the in-flight pool."""
        led = ledger()
        base = led.current_bytes("ec_pipeline_inflight")
        ec = make_rs(4, 2)
        agg = EncodeAggregator(window=1, pipeline_depth=2)

        def broken_host(self, data):
            raise RuntimeError("host oracle down too")

        monkeypatch.setattr(
            type(ec), "encode_array_host", broken_host
        )
        global_injector().inject("codec.launch", 5, hits=1)
        t = agg.submit(
            ec, np.zeros((2, 4, 512), dtype=np.uint8)
        )
        global_injector().clear()
        with pytest.raises(Exception):
            np.asarray(t)
        agg.drain()
        assert led.current_bytes("ec_pipeline_inflight") == base


class TestPressureStaging:
    def test_trim_order_cache_then_depth(self):
        """One evaluation with an un-trimmable hold big enough that no
        stage relieves it: the cache gives its bytes back first, then
        the effective depth clamps — and relief releases everything."""
        led = ledger()
        cc = device_chunk_cache()
        old_max = cc.max_bytes
        pin = None
        try:
            cc.configure(max_bytes=1 << 20)
            cc.put("press/obj", 0, 1, np.zeros(64 << 10, dtype=np.uint8))
            cache_before = cc.perf_dump()["resident_bytes"]
            assert cache_before >= 64 << 10
            # the pin dwarfs any residual residency earlier suites left
            # in the process-wide ledger, so freeing it guarantees the
            # post-relief ratio lands under the clear threshold
            pin = led.alloc(
                "scratch", max(64 << 20, 50 * led.total_device_bytes())
            )
            untrimmable = (
                led.total_device_bytes()
                - led.current_bytes("device_cache")
            )
            led.configure(target_bytes=max(1, untrimmable // 2))
            st = led.check_pressure()
            assert st["pressure"] and st["stage"] == 2, st
            assert st["stage_name"] == "depth-clamp", st
            # stage 1 ran: the cache was trimmed to relieve first
            assert st["actions"]["cache_trimmed_bytes"] >= cache_before
            assert cc.perf_dump()["resident_bytes"] == 0
            # stage 2: depth clamped
            assert led.depth_clamped
            # relief: free the hold, next evaluation clears everything
            pin.free()
            pin = None
            st = led.check_pressure()
            assert not st["pressure"] and st["stage"] == 0, st
            assert not led.depth_clamped
        finally:
            if pin is not None:
                pin.free()
            led.configure(target_bytes=0)
            led.check_pressure()
            cc.configure(max_bytes=old_max)

    def test_depth_clamp_bounds_inflight_ring(self):
        """With the clamp armed, a depth-4 aggregator behaves like
        depth 1: at most one launch stays unsettled after a submit."""
        led = ledger()
        ec = make_rs(4, 2)
        agg = EncodeAggregator(window=1, pipeline_depth=4)
        try:
            led.depth_clamped = True
            for i in range(4):
                agg.submit(ec, np.zeros((2, 4, 512), dtype=np.uint8))
            with agg._lock:
                assert len(agg._live) <= 1
        finally:
            led.depth_clamped = False
            agg.drain()

    def test_concurrent_evaluations_never_strand_the_caps(self):
        """Racing check_pressure calls must serialize: an evaluation
        arming the clamp interleaved with one clearing the raised state
        would leave the pipeline depth silently clamped with no health
        check raised.  Invariant on every snapshot: clamp armed implies
        pressure raised."""
        led = MempoolLedger(target_bytes=1000)
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                led.check_pressure()

        t = threading.Thread(target=hammer)
        t.start()
        try:
            for _ in range(300):
                h = led.alloc("scratch", 2000)  # ratio 2.0: raise
                led.check_pressure()
                h.free()                        # ratio 0.0: clear
                led.check_pressure()
                st = led.pressure_status()
                assert st["pressure"] or not st["depth_clamped"], st
        finally:
            stop.set()
            t.join()

    def test_pressure_status_is_json_safe(self):
        import json

        json.dumps(ledger().check_pressure())
        json.dumps(ledger().dump())


class TestPressureHealthPipeline:
    def test_raise_and_clear_through_mon_health(self):
        """The integration gate: an un-trimmable HBM hold over the
        runtime-set target raises TPU_HBM_PRESSURE at the mon (with
        per-daemon detail) and on the mgr healthcheck surface; freeing
        the hold clears both."""

        async def run():
            from ceph_tpu.mgr import Mgr

            from test_cluster import start_cluster, stop_cluster, wait_until

            led = ledger()
            monmap, mons, osds = await start_cluster(1, 2)
            mgr = Mgr("x", monmap)
            mgr.beacon_interval = 0.1
            await mgr.start()
            await mgr.wait_for_active()
            pin = None
            try:
                # dwarf any residual residency from earlier suites (the
                # ledger is process-wide) so freeing the pin guarantees
                # relief under the clear threshold
                pin = led.alloc(
                    "scratch", max(64 << 20, 50 * led.total_device_bytes())
                )
                untrimmable = (
                    led.total_device_bytes()
                    - led.current_bytes("device_cache")
                )
                # the runtime-observer path IS under test: the config
                # set must reach the live ledger through the OSD's
                # ec_tpu_hbm_target_bytes observer
                osds[0].conf.set(
                    "ec_tpu_hbm_target_bytes", max(1, untrimmable // 2)
                )
                assert led.target_bytes == max(1, untrimmable // 2)

                def raised():
                    checks, _ = mons[0].health_checks()
                    return "TPU_HBM_PRESSURE" in checks
                await wait_until(raised, 10.0, "TPU_HBM_PRESSURE raised")
                checks, details = mons[0].health_checks()
                assert "HBM memory pressure" in checks["TPU_HBM_PRESSURE"]
                assert any(
                    "bytes resident vs" in line
                    for line in details["TPU_HBM_PRESSURE"]
                )
                assert "TPU_HBM_PRESSURE" in mgr.health_checks()
                # the staged response engaged all the way (the hold is
                # un-trimmable, so the cache trim could not relieve it)
                assert led.depth_clamped
                # relief: free the hold; the next beacons re-evaluate
                # and both surfaces clear
                pin.free()
                pin = None

                def cleared():
                    checks, _ = mons[0].health_checks()
                    return "TPU_HBM_PRESSURE" not in checks
                await wait_until(cleared, 10.0, "TPU_HBM_PRESSURE cleared")
                assert "TPU_HBM_PRESSURE" not in mgr.health_checks()
                assert not led.depth_clamped
            finally:
                if pin is not None:
                    pin.free()
                led.configure(target_bytes=0)
                led.check_pressure()
                await mgr.stop()
                await stop_cluster(mons, osds)

        asyncio.run(run())


class TestSurfacing:
    def test_flight_records_carry_resident_bytes(self):
        from ceph_tpu.ops.flight_recorder import flight_recorder

        led = ledger()
        ec = make_rs(4, 2)
        agg = EncodeAggregator(window=1, pipeline_depth=1)
        h = led.alloc("scratch", 12345)
        try:
            fr = flight_recorder()
            fr.reset()  # the ring is bounded: slicing by index misleads
            np.asarray(agg.submit(ec, np.zeros((2, 4, 512), np.uint8)))
            recs = fr.records()
            assert recs and all(
                r.get("hbm_bytes", 0) >= 12345 for r in recs
            ), recs
        finally:
            h.free()
            agg.drain()
            flight_recorder().reset()

    def test_trace_export_emits_hbm_counter_track(self):
        from ceph_tpu.ops.flight_recorder import flight_recorder
        from ceph_tpu.tools.trace_export import (
            export_chrome_trace,
            validate_chrome_trace,
        )

        ec = make_rs(4, 2)
        agg = EncodeAggregator(window=1, pipeline_depth=1)
        flight_recorder().reset()
        np.asarray(agg.submit(ec, np.zeros((2, 4, 512), np.uint8)))
        trace = export_chrome_trace(flight_recorder().records())
        validate_chrome_trace(trace)
        counters = [
            e for e in trace["traceEvents"] if e.get("ph") == "C"
        ]
        assert counters and all(
            e["name"] == "hbm_resident_bytes" and "bytes" in e["args"]
            for e in counters
        ), counters
        # pre-ledger records (old dumps) must not fabricate a counter
        legacy = [dict(r) for r in flight_recorder().records()]
        for r in legacy:
            r.pop("hbm_bytes", None)
        trace = export_chrome_trace(legacy)
        validate_chrome_trace(trace)
        assert not [e for e in trace["traceEvents"] if e.get("ph") == "C"]
        flight_recorder().reset()

    def test_dump_mempools_reconciles_with_holders(self):
        """The acceptance equality on the asok payload: with cache and
        scratch holders live, dump_mempools pool totals
        equal the holders' own live-buffer nbytes."""
        import jax.numpy as jnp

        led = ledger()
        base_cache = led.current_bytes("device_cache")
        base_scratch = led.current_bytes("scratch")
        cc = DeviceChunkCache(max_bytes=1 << 20)
        cc.put("x", 0, 1, np.zeros(4096, dtype=np.uint8))
        buf = track_buffer(jnp.zeros(2048, dtype=jnp.uint8), "scratch")
        try:
            pools = led.dump()["pools"]
            assert pools["device_cache"]["bytes"] - base_cache == \
                cc.perf_dump()["resident_bytes"]
            assert pools["scratch"]["bytes"] - base_scratch == buf.nbytes
            rec = led.reconcile()
            assert all(v["drift"] == 0 for v in rec.values()), rec
        finally:
            cc.clear()
            del buf
