"""ISSUE 8 tentpole contracts: the launch flight recorder and its
Chrome-trace export.

Acceptance shape: aggregated encode AND decode launches leave ring
records carrying queue-wait + h2d/kernel/d2h sub-spans; a DeviceGuard
timeout flags its launch's record (fallback + timeout) and the
degraded-bypass launches that follow are flagged too; the ring stays
bounded under concurrent submitters; and `tools/trace_export.py` emits
valid Chrome trace-event JSON (complete-event keys, monotonic
non-overlapping same-lane slices) from a live run."""

import threading
import time

import numpy as np
import pytest

from ceph_tpu.codec import ErasureCodeTpuRs
from ceph_tpu.codec.matrix_codec import DecodeAggregator, EncodeAggregator
from ceph_tpu.common.fault_injector import global_injector
from ceph_tpu.ops import dispatch as ec_dispatch
from ceph_tpu.ops.flight_recorder import FlightRecorder, flight_recorder
from ceph_tpu.ops.guard import device_guard
from ceph_tpu.stripe import StripeInfo
from ceph_tpu.stripe import stripe as stripe_mod
from ceph_tpu.tools.trace_export import (
    export_chrome_trace,
    validate_chrome_trace,
)


@pytest.fixture(autouse=True)
def _clean_state():
    """Recorder, guard, and injector state must not leak across tests."""
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


def payload(sinfo, stripes, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, stripes * sinfo.stripe_width, dtype=np.uint8)


class TestRingBuffer:
    def test_capacity_bound_under_concurrent_submitters(self):
        """8 threads hammering raw records: the ring never exceeds its
        bound, drops are oldest-first, and seq stays unique."""
        fr = FlightRecorder(capacity=64)
        threads = [
            threading.Thread(
                target=lambda: [
                    fr.record_raw("encode", 1, 4096) for _ in range(100)
                ]
            )
            for _ in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        recs = fr.records()
        assert len(recs) == 64
        seqs = [r["seq"] for r in recs]
        assert len(set(seqs)) == len(seqs), "duplicate seq in ring"
        assert fr.summary()["launches"] == 800
        # newest records survive: the max seq committed is retained
        assert max(seqs) == max(r["seq"] for r in recs)

    def test_configure_resize_keeps_newest(self):
        fr = FlightRecorder(capacity=16)
        for _ in range(16):
            fr.record_raw("encode", 1, 1)
        oldest_before = fr.records()[0]["seq"]
        fr.configure(capacity=4)
        recs = fr.records()
        assert len(recs) == 4
        assert recs[0]["seq"] > oldest_before, "resize must keep the newest"

    def test_reset_rebases_utilization_window(self):
        fr = FlightRecorder(capacity=8)
        rec = fr.record_raw("encode", 1, 1)
        fr.reset()
        assert fr.records() == []
        util = fr.utilization()
        assert util["busy_seconds"] == 0.0
        assert util["span_records"] == 0


class TestAggregatedLaunchRecords:
    """The acceptance surface: dump_flight-visible records for
    aggregated encode and decode launches with queue-wait + sub-spans."""

    def setup_method(self):
        self.ec = make_rs(4, 2)
        self.sinfo = StripeInfo(4 * 512, 512)

    def _agg_records(self):
        return [
            r for r in flight_recorder().records() if r["group"] != "#raw"
        ]

    def test_encode_launch_record_has_queue_wait_and_subspans(self):
        agg = EncodeAggregator(window=4)
        rng = np.random.default_rng(0)
        tickets = [
            agg.submit(
                self.ec, rng.integers(0, 256, (2, 4, 512), dtype=np.uint8)
            )
            for _ in range(4)
        ]
        for t in tickets:
            t.result()
        recs = [r for r in self._agg_records() if r["kind"] == "encode"]
        assert recs, "aggregated encode left no flight record"
        rec = recs[-1]
        assert rec["tickets"] == 4
        assert rec["stripes"] == 8
        assert rec["batch"] == 8  # padded to the pow2 bucket
        assert rec["reason"] == "flush_window"
        # the timeline: submit -> dispatch -> settle, spans derived
        assert rec["dispatch_ts"] >= rec["submit_ts"]
        assert rec["settle_ts"] >= rec["dispatch_ts"]
        assert rec["queue_wait_s"] >= 0.0
        assert rec["h2d_s"] > 0.0, "dispatch span missing"
        # kernel/d2h spans exist (may be ~0 when the device finished
        # under the reap, which is exactly what they measure)
        assert rec["kernel_s"] >= 0.0 and rec["d2h_s"] >= 0.0
        # a clean launch raises no FAILURE flags; overlap is benign —
        # it just means the device finished before the reaper arrived,
        # which depends on host speed, not on correctness
        assert not any(
            v for k, v in rec["flags"].items() if k != "overlap"
        )

    def test_decode_launch_record_has_subspans(self):
        agg = DecodeAggregator(window=2)
        data = payload(self.sinfo, 2, seed=5)
        shards = stripe_mod.encode(self.sinfo, self.ec, data)
        have = {i: shards[i] for i in range(6) if i != 2}
        pends = [
            stripe_mod.decode_shards_launch(
                self.sinfo, self.ec, have, {2}, aggregator=agg
            )
            for _ in range(2)
        ]
        for p in pends:
            p.result()
        recs = [r for r in self._agg_records() if r["kind"] == "decode"]
        assert recs, "aggregated decode left no flight record"
        rec = recs[-1]
        assert rec["tickets"] == 2
        assert rec["h2d_s"] > 0.0
        assert rec["settle_ts"] >= rec["dispatch_ts"] >= rec["submit_ts"]

    def test_injected_launch_fault_flags_fallback(self):
        """codec.launch armed to fail: the record says the launch
        completed on the host (fallback flag), not silently."""
        agg = EncodeAggregator(window=0)
        global_injector().inject("codec.launch", 5, hits=1)
        pend = stripe_mod.encode_launch(
            self.sinfo, self.ec, payload(self.sinfo, 1, seed=6),
            aggregator=agg,
        )
        pend.result()
        recs = self._agg_records()
        assert recs[-1]["flags"]["fallback"]
        assert recs[-1]["kernel_s"] > 0.0, "host compute must bank as kernel_s"

    def test_guard_timeout_flags_timeout_then_bypass(self):
        """A dispatch wedged past ec_tpu_launch_timeout_ms: the launch's
        record carries timeout+fallback; the NEXT launch (backend now
        DEGRADED, probe gated) is flagged degraded_bypass."""
        real = self.ec.encode_array

        def wedge(arr):
            time.sleep(0.5)
            return real(arr)

        device_guard().configure(timeout_ms=50, probe_interval_ms=10_000_000)
        # burn the immediate post-degrade probe allowance up front so the
        # bypass launch below cannot self-heal through a probe
        self.ec.encode_array = wedge
        try:
            agg = EncodeAggregator(window=0)
            pend = stripe_mod.encode_launch(
                self.sinfo, self.ec, payload(self.sinfo, 1, seed=7),
                aggregator=agg,
            )
            pend.result()
            wedged = self._agg_records()[-1]
            assert wedged["flags"]["timeout"], wedged
            assert wedged["flags"]["fallback"]
            # the deadline wait on the wedged device is DEAD time, not
            # staging: it must not inflate h2d_s / device busy-seconds
            assert wedged["h2d_s"] == 0.0, wedged
            assert device_guard().degraded
            # burn the post-degrade probe with a dead device
            device_guard().maybe_probe(
                lambda: (_ for _ in ()).throw(RuntimeError("dead"))
            )
            pend = stripe_mod.encode_launch(
                self.sinfo, self.ec, payload(self.sinfo, 1, seed=8),
                aggregator=agg,
            )
            pend.result()
        finally:
            self.ec.encode_array = real
        bypass = self._agg_records()[-1]
        assert bypass["flags"]["degraded_bypass"], bypass
        assert bypass["flags"]["fallback"]
        assert not bypass["flags"]["timeout"]

    def test_sticky_error_flags_error(self):
        """A launch that fails on device AND host leaves an error-flagged
        record (the co-riders' EcError has a timeline entry)."""
        agg = EncodeAggregator(window=0)
        real_dev = self.ec.encode_array
        real_host = self.ec.encode_array_host

        def boom(arr):
            raise RuntimeError("dev boom")

        def boom_host(arr):
            raise RuntimeError("host boom")

        self.ec.encode_array = boom
        self.ec.encode_array_host = boom_host
        try:
            pend = stripe_mod.encode_launch(
                self.sinfo, self.ec, payload(self.sinfo, 1, seed=9),
                aggregator=agg,
            )
            with pytest.raises(Exception):
                pend.result()
        finally:
            self.ec.encode_array = real_dev
            self.ec.encode_array_host = real_host
        rec = self._agg_records()[-1]
        assert rec["flags"]["error"], rec

    def test_utilization_feeds_perf_dump(self):
        agg = EncodeAggregator(window=0)
        pend = stripe_mod.encode_launch(
            self.sinfo, self.ec, payload(self.sinfo, 2, seed=10),
            aggregator=agg,
        )
        pend.result()
        dump = ec_dispatch.perf_dump()
        for key in (
            "device_busy_seconds",
            "device_occupancy",
            "flight_records",
            "flight_mean_queue_wait_ms",
        ):
            assert key in dump, key
        assert dump["device_busy_seconds"] > 0.0
        assert 0.0 < dump["device_occupancy"] <= 1.0
        assert dump["flight_records"] >= 1


class TestTraceExport:
    def test_live_run_exports_valid_chrome_trace(self):
        """The acceptance criterion: a live aggregated run (encode +
        decode + a fallback-flagged launch) exports Chrome trace JSON
        that passes the contract validator — required keys, integer µs
        timestamps, no overlapping same-lane slices."""
        ec = make_rs(4, 2)
        sinfo = StripeInfo(4 * 512, 512)
        agg = EncodeAggregator(window=2)
        dagg = DecodeAggregator(window=0)
        rng = np.random.default_rng(1)
        tickets = [
            agg.submit(ec, rng.integers(0, 256, (2, 4, 512), dtype=np.uint8))
            for _ in range(4)
        ]
        for t in tickets:
            t.result()
        data = payload(sinfo, 2, seed=11)
        shards = stripe_mod.encode(sinfo, ec, data)
        have = {i: shards[i] for i in range(6) if i != 1}
        stripe_mod.decode_shards_launch(
            sinfo, ec, have, {1}, aggregator=dagg
        ).result()
        global_injector().inject("codec.launch", 5, hits=1)
        stripe_mod.encode_launch(
            sinfo, ec, payload(sinfo, 1, seed=12), aggregator=agg
        ).result()
        records = flight_recorder().records()
        assert len(records) >= 3
        trace = export_chrome_trace(records)
        validate_chrome_trace(trace)
        names = {e["name"] for e in trace["traceEvents"]}
        # the stage sub-spans render as their own slices
        assert {"encode:h2d", "encode:kernel", "encode:d2h"} <= names
        assert any(n.startswith("decode") for n in names)
        # the fallback launch landed on its own lane
        lanes = {e["tid"] for e in trace["traceEvents"]}
        assert "host fallback" in lanes
        # aggregator lanes carry queue_wait slices
        assert "queue_wait" in names

    def test_idle_gaps_are_explicit(self):
        """Two launches separated by a real gap produce an explicit
        `idle` slice between them on the device lane."""
        fr = FlightRecorder(capacity=8)
        t0 = time.monotonic()
        for offset in (0.0, 0.5):
            rec = {
                "seq": 0,
                "kind": "encode",
                "group": "g",
                "tickets": 1,
                "stripes": 1,
                "batch": 1,
                "bytes": 512,
                "devices": 1,
                "reason": "",
                "submit_ts": t0 + offset,
                "dispatch_ts": t0 + offset,
                "settle_ts": t0 + offset + 0.01,
                "queue_wait_s": 0.0,
                "h2d_s": 0.005,
                "kernel_s": 0.004,
                "d2h_s": 0.001,
                "flags": {"sharded": False, "fallback": False,
                          "degraded_bypass": False, "timeout": False,
                          "throttle_stall": False, "error": False},
            }
            fr.commit(rec)
        trace = export_chrome_trace(fr.records())
        validate_chrome_trace(trace)
        idles = [e for e in trace["traceEvents"] if e["name"] == "idle"]
        assert idles, "gap between launches must render an idle slice"
        # the gap is ~490ms of the 500ms offset
        assert idles[0]["dur"] > 400_000

    def test_monotonic_ts_and_args_flags(self):
        fr = FlightRecorder(capacity=8)
        fr.record_raw("encode", 4, 4096, devices=2)
        trace = export_chrome_trace(fr.records())
        validate_chrome_trace(trace)
        ev = [e for e in trace["traceEvents"] if e["pid"] == "devices"][0]
        assert ev["args"]["devices"] == 2
        assert "sharded" in ev["args"].get("flags", "")


class TestDumpFlightAsok:
    def test_ec_write_shows_in_dump_flight_over_asok(self, tmp_path):
        """End to end: an EC client write's aggregated encode launch is
        visible through the OSD asok `dump_flight` with queue-wait +
        sub-spans, and the payload round-trips through trace_export."""
        import asyncio

        async def run():
            from ceph_tpu.client import Rados
            from ceph_tpu.common.admin_socket import admin_command
            from ceph_tpu.common.config import Config
            from ceph_tpu.mon import MonMap, Monitor
            from ceph_tpu.osd.osd import OSD

            from test_mon import free_port_addrs

            monmap = MonMap(addrs=free_port_addrs(1))
            mons = [
                Monitor(n, monmap, election_timeout=0.3)
                for n in monmap.addrs
            ]
            for m in mons:
                await m.start()
                await m.wait_for_quorum()

            def conf(i):
                return Config(
                    {
                        "name": f"osd.{i}",
                        "osd_heartbeat_interval": 0.1,
                        "osd_heartbeat_grace": 0.6,
                        "admin_socket": str(tmp_path / f"osd.{i}.asok"),
                    },
                    env=False,
                )

            osds = [OSD(i, monmap, conf=conf(i)) for i in range(3)]
            for o in osds:
                await o.start()
            for o in osds:
                await o.wait_for_up()
            client = Rados(monmap)
            await client.connect()
            rv, rs, _ = await client.mon_command(
                {
                    "prefix": "osd erasure-code-profile set",
                    "name": "fl21",
                    "profile": ["k=2", "m=1", "plugin=tpu"],
                }
            )
            assert rv == 0, rs
            await client.pool_create(
                "flp", "erasure", profile="fl21", pg_num=1
            )
            io = await client.open_ioctx("flp")
            flight_recorder().reset()
            await io.write_full("obj", bytes(range(256)) * 64)
            loop = asyncio.get_event_loop()
            sock = str(tmp_path / "osd.0.asok")
            dump = await loop.run_in_executor(
                None, lambda: admin_command(sock, "dump_flight")
            )
            agg = [
                r for r in dump["records"]
                if r["kind"] == "encode" and r["group"] != "#raw"
            ]
            assert agg, dump["records"]
            rec = agg[-1]
            assert rec["settle_ts"] >= rec["dispatch_ts"] >= rec["submit_ts"]
            assert rec["queue_wait_s"] >= 0.0
            assert rec["h2d_s"] > 0.0
            assert "utilization" in dump
            assert dump["utilization"]["span_records"] >= 1
            # the asok payload feeds trace_export directly
            trace = export_chrome_trace(dump["records"])
            validate_chrome_trace(trace)
            await client.shutdown()
            for o in osds:
                await o.stop()
            for m in mons:
                await m.stop()
            await asyncio.sleep(0.05)

        asyncio.run(run())
