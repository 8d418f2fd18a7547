"""ISSUE 11 tentpole contracts: the depth-N asynchronous launch
pipeline and the device-resident chunk cache.

Acceptance shape: launches dispatch into a bounded in-flight ring whose
records witness depth > 1; a wedge at depth > 1 host-fallbacks every
ticket byte-identically without losing the other in-flight groups; and
a device-cache hit serves a degraded read with NO decode launch and a
flight record whose only span is the D2H copy (h2d_s == 0)."""

import time

import numpy as np
import pytest

from ceph_tpu.codec import ErasureCodeTpuRs
from ceph_tpu.codec.matrix_codec import EncodeAggregator
from ceph_tpu.common.fault_injector import global_injector
from ceph_tpu.ops import dispatch as ec_dispatch
from ceph_tpu.ops.device_cache import DeviceChunkCache, device_chunk_cache
from ceph_tpu.ops.flight_recorder import flight_recorder
from ceph_tpu.ops.guard import device_guard
from ceph_tpu.stripe import StripeInfo
from ceph_tpu.stripe import stripe as stripe_mod


@pytest.fixture(autouse=True)
def _clean_state():
    flight_recorder().reset()
    yield
    global_injector().clear()
    device_guard().mark_healthy()
    device_guard().configure(timeout_ms=20000, probe_interval_ms=2000)
    flight_recorder().reset()


def make_rs(k=4, m=2):
    ec = ErasureCodeTpuRs()
    ec.init({"k": str(k), "m": str(m)})
    return ec


def batches(n, shape=(2, 4, 512), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, shape, dtype=np.uint8) for _ in range(n)]


class TestPipelineRing:
    def test_inflight_bounded_and_depth_witnessed(self):
        """window=2 / depth=2: the third launch drains the first, the
        ring never grows past depth+1, and the records carry the
        inflight_depth the pipeline actually reached."""
        ec = make_rs()
        agg = EncodeAggregator(window=2, pipeline_depth=2)
        pipe0 = ec_dispatch.PIPELINE.snapshot()
        data = batches(8)
        tickets = [agg.submit(ec, d) for d in data]
        agg.flush()
        for t, d in zip(tickets, data):
            assert np.array_equal(
                np.asarray(t), np.asarray(ec.encode_array_host(d))
            )
        pipe1 = ec_dispatch.PIPELINE.snapshot()
        assert pipe1["drains"] > pipe0["drains"], "ring never drained"
        recs = [
            r for r in flight_recorder().records()
            if r["kind"] == "encode" and r["group"] != "#raw"
        ]
        assert max(r["inflight_depth"] for r in recs) >= 2, recs
        # everything settled: nothing left in flight from this suite
        assert not agg._live

    def test_depth_zero_disables_ring(self):
        ec = make_rs()
        agg = EncodeAggregator(window=2, pipeline_depth=0)
        pipe0 = ec_dispatch.PIPELINE.snapshot()["drains"]
        tickets = [agg.submit(ec, d) for d in batches(8, seed=1)]
        agg.flush()
        for t in tickets:
            t.result()
        assert ec_dispatch.PIPELINE.snapshot()["drains"] == pipe0

    def test_configure_updates_depth(self):
        agg = EncodeAggregator(window=2, pipeline_depth=2)
        agg.configure(pipeline_depth=5)
        assert agg.pipeline_depth == 5
        assert ec_dispatch.PIPELINE.snapshot()["depth"] == 5

    def test_overlap_flag_on_already_finished_launch(self):
        """A launch whose device work completed before its reap is
        flagged `overlap` with a completion timestamp — the per-launch
        witness the bench overlap fraction aggregates."""
        ec = make_rs()
        agg = EncodeAggregator(window=0, pipeline_depth=0)
        pend = agg.submit(
            ec, batches(1, shape=(4, 4, 4096), seed=2)[0]
        )
        time.sleep(0.05)  # CPU backend: the async dispatch finishes
        np.asarray(pend)
        rec = [
            r for r in flight_recorder().records() if r["group"] != "#raw"
        ][-1]
        assert rec["complete_ts"] >= rec["dispatch_ts"], rec
        assert rec["flags"]["overlap"], rec


class TestWedgeAtDepth:
    def test_wedged_launches_at_depth_pay_one_deadline(self):
        """Every in-flight launch wedges AFTER dispatch (the runtime
        died under a full ring): the FIRST reap pays the deadline and
        marks DEGRADED; every other in-flight group's settle sees
        degraded + not-ready and goes straight to the host oracle — one
        deadline total, no ticket lost."""
        ec = make_rs()
        agg = EncodeAggregator(window=1, pipeline_depth=8)
        device_guard().configure(timeout_ms=100, probe_interval_ms=10_000_000)
        real = ec.encode_array
        data = batches(4, shape=(2, 4, 512), seed=3)

        class _Wedged:
            """A device-array stand-in that never becomes ready."""

            def __init__(self, shape):
                self.shape = shape
                self.dtype = np.uint8

            def is_ready(self):
                return False

            def block_until_ready(self):
                time.sleep(3600)

        def wedge(arr):
            return _Wedged((arr.shape[0], 2, arr.shape[2]))

        ec.encode_array = wedge
        try:
            tickets = [agg.submit(ec, d) for d in data]
            # all four dispatched (depth 8 ring never forces a settle);
            # reaping the first trips the deadline -> DEGRADED
            t0 = time.monotonic()
            for t, d in zip(tickets, data):
                assert np.array_equal(
                    np.asarray(t), np.asarray(ec.encode_array_host(d))
                )
            elapsed = time.monotonic() - t0
        finally:
            ec.encode_array = real
        # one deadline for the first wedge, near-zero for the rest
        assert elapsed < 2.0, elapsed
        assert device_guard().degraded
        recs = [
            r for r in flight_recorder().records() if r["group"] != "#raw"
        ]
        timeouts = [r for r in recs if r["flags"]["timeout"]]
        assert len(timeouts) == 1, recs
        fallbacks = [r for r in recs if r["flags"]["fallback"]]
        assert len(fallbacks) == len(data), recs
        # the spared groups are marked degraded_bypass, not timeout
        assert sum(
            1 for r in recs if r["flags"]["degraded_bypass"]
        ) == len(data) - 1, recs
        assert not agg._live

    def test_finished_coriders_keep_their_device_results(self):
        """A wedge that degrades the backend must NOT discard other
        in-flight launches whose device work already completed: a ready
        buffer settles from the device (no fallback flag), because
        re-running finished work on the host would only add latency."""
        ec = make_rs()
        agg = EncodeAggregator(window=1, pipeline_depth=8)
        data = batches(2, shape=(2, 4, 512), seed=4)
        tickets = [agg.submit(ec, d) for d in data]
        time.sleep(0.05)  # CPU backend: both launches finish
        device_guard().mark_degraded("test wedge elsewhere")
        try:
            for t, d in zip(tickets, data):
                assert np.array_equal(
                    np.asarray(t), np.asarray(ec.encode_array_host(d))
                )
        finally:
            device_guard().mark_healthy()
        recs = [
            r for r in flight_recorder().records() if r["group"] != "#raw"
        ]
        assert not any(r["flags"]["fallback"] for r in recs), recs


class TestDeviceChunkCache:
    def test_put_get_generation_and_eviction(self):
        cc = DeviceChunkCache(max_bytes=4096)
        a = np.arange(1024, dtype=np.uint8)
        assert cc.put("o1", 0, 1, a)
        assert np.array_equal(np.asarray(cc.get("o1", 0, 1)), a)
        assert cc.get("o1", 0, 2) is None  # generation mismatch
        assert cc.get("o1", 1, 1) is None  # shard mismatch
        # eviction: four 1 KiB entries fill the 4 KiB bound; the fifth
        # evicts the LRU (o1 was touched most recently by the get above)
        for i in range(2, 7):
            assert cc.put(f"o{i}", 0, 1, a)
        dump = cc.perf_dump()
        assert dump["evictions"] >= 1
        assert dump["resident_bytes"] <= 4096

    def test_disabled_and_oversized_put_refused(self):
        cc = DeviceChunkCache(max_bytes=0)
        assert not cc.enabled
        assert not cc.put("o", 0, 1, np.zeros(16, np.uint8))
        cc2 = DeviceChunkCache(max_bytes=64)
        assert not cc2.put("o", 0, 1, np.zeros(128, np.uint8))

    def test_invalidate_object_drops_all_shards(self):
        cc = DeviceChunkCache(max_bytes=1 << 20)
        for s in range(3):
            cc.put("obj", s, 1, np.zeros(64, np.uint8))
        cc.put("other", 0, 1, np.zeros(64, np.uint8))
        assert cc.invalidate_object("obj") == 3
        assert cc.get("obj", 0, 1) is None
        assert cc.get("other", 0, 1) is not None

    def test_degraded_transition_clears_and_gates_put(self):
        cc = device_chunk_cache()
        old_max = cc.max_bytes
        cc.configure(max_bytes=1 << 20)
        try:
            assert cc.put("deg-obj", 0, 1, np.zeros(64, np.uint8))
            device_guard().mark_degraded("test wedge")
            assert cc.get("deg-obj", 0, 1) is None, "clear on DEGRADED"
            assert not cc.put("deg-obj", 0, 1, np.zeros(64, np.uint8))
            device_guard().mark_healthy()
            assert cc.put("deg-obj", 0, 1, np.zeros(64, np.uint8))
        finally:
            cc.invalidate_object("deg-obj")
            cc.configure(max_bytes=old_max)

    def test_fetch_many_hit_record_skips_h2d(self):
        """The acceptance criterion, at the flight-record level: a
        cache-served read's record is flagged cache_hit with ZERO h2d
        and kernel spans — only the D2H copy."""
        cc = DeviceChunkCache(max_bytes=1 << 20)
        a = np.arange(2048, dtype=np.uint8)
        cc.put("obj", 1, 7, a)
        cc.put("obj", 3, 7, a[::-1].copy())
        got = cc.fetch_many("obj", [1, 3], 7, length=2048)
        assert got is not None
        assert np.array_equal(got[1], a)
        rec = [
            r for r in flight_recorder().records()
            if r["flags"].get("cache_hit")
        ][-1]
        assert rec["h2d_s"] == 0.0 and rec["kernel_s"] == 0.0, rec
        assert rec["d2h_s"] >= 0.0
        assert cc.fetch_many("obj", [1, 2], 7) is None  # partial -> miss

    def test_degraded_read_hit_skips_decode_launch(self):
        """End to end through the stripe decode launcher: the second
        same-generation degraded read serves from HBM — no new decode
        launch, byte-identical logical bytes, cache_hit record."""
        ec = make_rs()
        sinfo = StripeInfo(4 * 512, 512)
        rng = np.random.default_rng(9)
        data = rng.integers(0, 256, 2 * sinfo.stripe_width, dtype=np.uint8)
        shards = stripe_mod.encode(sinfo, ec, data)
        have = {i: shards[i] for i in range(6) if i != 1}
        cc = DeviceChunkCache(max_bytes=1 << 20)
        key = (("t", "obj"), 5)
        first = stripe_mod.decode_concat_launch(
            sinfo, ec, have, chunk_cache=cc, cache_key=key
        ).result()
        d0 = ec_dispatch.DECODE_LAUNCHES.snapshot()["launches"]
        second = stripe_mod.decode_concat_launch(
            sinfo, ec, have, chunk_cache=cc, cache_key=key
        ).result()
        assert ec_dispatch.DECODE_LAUNCHES.snapshot()["launches"] == d0
        assert np.array_equal(first, data)
        assert np.array_equal(second, data)
        assert any(
            r["flags"].get("cache_hit") for r in flight_recorder().records()
        )
        # a generation bump (overwrite) misses again
        third = stripe_mod.decode_concat_launch(
            sinfo, ec, have, chunk_cache=cc, cache_key=(key[0], 6)
        ).result()
        assert ec_dispatch.DECODE_LAUNCHES.snapshot()["launches"] == d0 + 1
        assert np.array_equal(third, data)

    def test_recovery_hit_through_decode_shards(self):
        ec = make_rs()
        sinfo = StripeInfo(4 * 512, 512)
        rng = np.random.default_rng(10)
        data = rng.integers(0, 256, 2 * sinfo.stripe_width, dtype=np.uint8)
        shards = stripe_mod.encode(sinfo, ec, data)
        have = {i: shards[i] for i in range(6) if i not in (1, 5)}
        cc = DeviceChunkCache(max_bytes=1 << 20)
        key = (("t", "obj2"), 3)
        first = stripe_mod.decode_shards_launch(
            sinfo, ec, have, {1, 5}, chunk_cache=cc, cache_key=key
        ).result()
        d0 = ec_dispatch.DECODE_LAUNCHES.snapshot()["launches"]
        second = stripe_mod.decode_shards_launch(
            sinfo, ec, have, {1, 5}, chunk_cache=cc, cache_key=key
        ).result()
        assert ec_dispatch.DECODE_LAUNCHES.snapshot()["launches"] == d0
        for s in (1, 5):
            assert np.array_equal(first[s], shards[s].reshape(-1))
            assert np.array_equal(second[s], first[s])


class TestRmwCacheConsult:
    def test_degraded_rmw_read_leg_hits_cache(self):
        """The RMW read leg reads exactly the committed pre-write bytes,
        so a prior degraded read's cached reconstruction must serve it
        from HBM.  Regression: submit_transaction used to project (and
        eagerly invalidate) BEFORE the read leg ran, making the
        advertised RMW consult unreachable — the submit-time generation
        capture plus encode-time invalidation make it real."""
        from test_ec_backend import (
            FLAG_EC_OVERWRITES,
            PG_NONE,
            Cluster,
            ec_pool,
            payload,
        )

        cc = device_chunk_cache()
        cc.configure(max_bytes=1 << 22)
        cc.clear()
        # disarm the on-device RMW delta path (ISSUE 18): with it on, a
        # warm cache makes the RMW bump generations IN PLACE instead of
        # invalidating — this test pins the MATERIALIZE path's
        # generation-capture + encode-time-invalidation contract
        from ceph_tpu.osd import ec_backend as ec_backend_mod

        ec_backend_mod.configure_rmw_delta(False)
        try:
            pool, profiles = ec_pool(4, 2, flags=FLAG_EC_OVERWRITES)
            c = Cluster(pool, profiles)
            base = payload(2 * pool.stripe_width)
            c.write("obj", 0, base)
            # a data shard goes dark: every read of obj now reconstructs
            c.acting[1] = PG_NONE
            assert c.read("obj", 0, len(base)) == base  # fills the cache
            assert cc.perf_dump()["entries"] >= 1
            h0 = cc.perf_dump()["hits"]
            d0 = ec_dispatch.DECODE_LAUNCHES.snapshot()["launches"]
            # partial-stripe overwrite: the RMW read leg reconstructs the
            # modified stripe — from the cache, not a decode launch
            patch = payload(300, seed=9)
            c.write("obj", 1000, patch)
            assert cc.perf_dump()["hits"] > h0, (
                "RMW read leg never consulted the device cache"
            )
            assert ec_dispatch.DECODE_LAUNCHES.snapshot()["launches"] == d0
            # encode-time invalidation dropped the now-stale entries
            assert cc.perf_dump()["entries"] == 0
            expect = bytearray(base)
            expect[1000:1300] = patch
            assert c.read("obj", 0, len(base)) == bytes(expect)
        finally:
            from ceph_tpu.common.options import OPTIONS

            ec_backend_mod.configure_rmw_delta(
                bool(OPTIONS["ec_tpu_rmw_delta"].default)
            )
            cc.clear()
            cc.configure(
                max_bytes=int(OPTIONS["ec_tpu_device_cache_bytes"].default)
            )


class TestPerfDumpFamilies:
    def test_pipeline_and_cache_keys_on_perf_dump(self):
        dump = ec_dispatch.perf_dump()
        for key in (
            "pipeline.depth", "pipeline.inflight", "pipeline.inflight_peak",
            "pipeline.drains",
            "cache.hits", "cache.misses", "cache.insertions",
            "cache.evictions", "cache.invalidations", "cache.served_bytes",
            "cache.resident_bytes", "cache.entries",
        ):
            assert key in dump, key
        # one launch path: no fusion, pad-learner or donation families
        assert not [
            key for key in dump
            if "fused" in key or "waste" in key or "donation" in key
        ], sorted(dump)
