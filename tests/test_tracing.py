"""Observability-layer tests (docs/OBSERVABILITY.md): trace-context
propagation across messenger round-trips, PerfHistogram bucket math, the
end-to-end op trace on a toy cluster, and the OSD→mgr→mon SLOW_OPS
health pipeline (appearing in `health detail`, clearing on drain, and
refusing spoofed mgr digests)."""

import asyncio
import json

import pytest

from ceph_tpu.common import tracer as tracer_mod
from ceph_tpu.common.perf_counters import (
    PerfCountersBuilder,
    PerfHistogram,
    PerfHistogram2D,
    PerfHistogramAxis,
)
from ceph_tpu.common.tracer import Tracer
from ceph_tpu.msg.message import decode_message, encode_message
from ceph_tpu.msg.messages import MMonMgrReport, MPing
from ceph_tpu.msg.messenger import Messenger

from test_msg import Collector, make_pair


# --- histogram bucket math ----------------------------------------------------


class TestHistogramMath:
    def test_log2_axis_bounds_and_index(self):
        axis = PerfHistogramAxis(lowest=1.0, buckets=4)
        # bucket i covers (lowest*2^(i-1), lowest*2^i]; last bucket +Inf
        assert axis.bounds == [1.0, 2.0, 4.0]
        assert axis.index(0.5) == 0
        assert axis.index(1.0) == 0  # boundary value lands in its bucket
        assert axis.index(1.5) == 1
        assert axis.index(2.0) == 1
        assert axis.index(3.0) == 2
        assert axis.index(4.0) == 2
        assert axis.index(100.0) == 3  # overflow -> +Inf bucket

    def test_histogram_dump_is_cumulative_with_inf(self):
        h = PerfHistogram(PerfHistogramAxis(lowest=1.0, buckets=4))
        for v in (0.5, 3.0, 100.0):
            h.sample(v)
        d = h.dump()["histogram"]
        assert d["buckets"] == [[1.0, 1], [2.0, 1], [4.0, 2], ["+Inf", 3]]
        assert d["count"] == 3
        assert d["sum"] == pytest.approx(103.5)

    def test_2d_histogram_cells(self):
        h = PerfHistogram2D(
            PerfHistogramAxis(lowest=10.0, buckets=3),
            PerfHistogramAxis(lowest=1.0, buckets=2),
        )
        h.sample(5.0, 0.5)    # x bucket 0, y bucket 0
        h.sample(15.0, 99.0)  # x bucket 1, y overflow
        d = h.dump()["histogram2d"]
        assert d["counts"][0][0] == 1
        assert d["counts"][1][1] == 1
        assert d["count"] == 2
        assert d["x_le"][-1] == "+Inf" and d["y_le"][-1] == "+Inf"

    def test_builder_hinc_and_dump_histograms(self):
        b = PerfCountersBuilder("osd")
        b.add_u64_counter("op")
        b.add_histogram("op_latency", lowest=1e-3, buckets=5)
        b.add_histogram_2d("op_size_latency")
        pc = b.create_perf_counters()
        pc.inc("op")
        pc.hinc("op_latency", 0.004)
        pc.hinc2("op_size_latency", 8192, 0.004)
        dump = pc.dump()
        assert dump["op"] == 1
        assert dump["op_latency"]["histogram"]["count"] == 1
        # dump_histograms returns ONLY the histogram counters
        hists = pc.dump_histograms()
        assert set(hists) == {"op_latency", "op_size_latency"}


# --- trace-context propagation ------------------------------------------------


class TestTraceContextPropagation:
    def test_envelope_roundtrip_carries_context(self):
        msg = MPing(stamp=1.0)
        msg.trace_id, msg.span_id = 0x1234, 0x5678
        env, payload = encode_message(msg)
        out = decode_message(env, payload)
        assert (out.trace_id, out.span_id) == (0x1234, 0x5678)

    def test_untraced_message_extracts_none(self):
        msg = MPing(stamp=1.0)
        env, payload = encode_message(msg)
        assert tracer_mod.extract(decode_message(env, payload)) is None

    def test_inject_extract_recorded_only(self):
        t = Tracer("client", enabled=True)
        span = t.start_span("client:op")
        msg = MPing(stamp=0.0)
        tracer_mod.inject(span, msg)
        ctx = tracer_mod.extract(msg)
        assert ctx is not None
        assert ctx.trace_id == span.trace_id and ctx.span_id == span.span_id
        # a disabled tracer's span must NOT leak a context
        off = Tracer("client", enabled=False).start_span("client:op")
        msg2 = MPing(stamp=0.0)
        tracer_mod.inject(off, msg2)
        assert tracer_mod.extract(msg2) is None

    def test_remote_context_links_trace_across_tracers(self):
        a = Tracer("client", enabled=True)
        b = Tracer("osd.0", enabled=True)
        root = a.start_span("client:op")
        child = b.start_span("osd:op", remote=root.context())
        assert child.trace_id == root.trace_id
        assert child.parent_id == root.span_id
        assert child.span_id != root.span_id  # per-tracer random id bases
        # local parent wins over remote
        local = b.start_span("sub", parent=child, remote=root.context())
        assert local.parent_id == child.span_id

    def test_span_scope_contextvar(self):
        t = Tracer("x", enabled=True)
        assert tracer_mod.current_span() is None
        with tracer_mod.span_scope(t.start_span("outer")) as sp:
            assert tracer_mod.current_span() is sp
            with tracer_mod.span_scope(sp.child("inner")) as inner:
                assert tracer_mod.current_span() is inner
            assert tracer_mod.current_span() is sp
        assert tracer_mod.current_span() is None

    def test_messenger_roundtrip_joins_trace(self):
        """A trace-carrying message delivered through a real (TCP)
        messenger records a msgr span on the receiver, parent-linked to
        the sender's span and sharing its trace id."""

        async def run():
            server, coll, client = await make_pair()
            server.tracer = Tracer("osd.0", enabled=True)
            sender = Tracer("client.1", enabled=True)
            span = sender.start_span("client:op")
            msg = MPing(stamp=1.0)
            tracer_mod.inject(span, msg)
            await client.send_to(server.addr, msg)
            await asyncio.wait_for(coll.got.wait(), 5)
            span.finish()
            hops = [s for s in server.tracer.export() if s["name"] == "msgr:MPing"]
            assert len(hops) == 1
            assert hops[0]["trace_id"] == span.trace_id
            assert hops[0]["parent_id"] == span.span_id
            # untraced messages must not create spans
            coll.got.clear()
            await client.send_to(server.addr, MPing(stamp=2.0))
            await asyncio.wait_for(coll.got.wait(), 5)
            assert len(server.tracer.export()) == 1
            await client.shutdown()
            await server.shutdown()

        asyncio.run(run())


# --- end-to-end op trace on a toy cluster ------------------------------------


class TestEndToEndTrace:
    def test_ec_write_yields_one_parent_linked_trace(self, tmp_path):
        """One client EC write = ONE trace: client:op → msgr:MOSDOp →
        osd:op → ec:write → codec stages, parent-linked across the
        client and OSD processes, retrievable via `dump_tracing`."""

        async def run():
            from ceph_tpu.client import Rados
            from ceph_tpu.common.admin_socket import admin_command
            from ceph_tpu.common.config import Config
            from ceph_tpu.mon import MonMap, Monitor
            from ceph_tpu.osd.osd import OSD

            from test_mon import free_port_addrs
            from test_cluster import stop_cluster

            monmap = MonMap(addrs=free_port_addrs(1))
            mons = [Monitor(n, monmap, election_timeout=0.3) for n in monmap.addrs]
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
                        "jaeger_tracing_enable": True,
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
                    "name": "tr21",
                    "profile": ["k=2", "m=1", "plugin=tpu"],
                }
            )
            assert rv == 0, rs
            await client.pool_create("trpool", "erasure", profile="tr21", pg_num=1)
            ioctx = await client.open_ioctx("trpool")
            # trace exactly ONE op: the EC write
            client.objecter.tracer.enabled = True
            await ioctx.write_full("traced", b"T" * 8192)
            client.objecter.tracer.enabled = False

            roots = [
                s
                for s in client.objecter.tracer.export()
                if s["name"] == "client:op"
            ]
            assert len(roots) == 1, roots
            trace_id = roots[0]["trace_id"]
            client_spans = [
                s
                for s in client.objecter.tracer.export()
                if s["trace_id"] == trace_id
            ]

            primary = next(
                o
                for o in osds
                if any(p.peering.is_primary() for p in o.pgs.values())
            )
            loop = asyncio.get_event_loop()
            dump = await loop.run_in_executor(
                None,
                lambda: admin_command(
                    str(tmp_path / f"osd.{primary.whoami}.asok"), "dump_tracing"
                ),
            )
            osd_spans = dump["traces"].get(str(trace_id), [])
            names = {s["name"] for s in osd_spans}
            assert "msgr:MOSDOp" in names
            assert "osd:op" in names
            assert "ec:write" in names
            assert any(n.startswith("codec:") for n in names), names

            # every span is parent-linked into the one trace
            ids = {s["span_id"] for s in client_spans} | {
                s["span_id"] for s in osd_spans
            }
            for s in list(client_spans) + list(osd_spans):
                assert s["parent_id"] is None or s["parent_id"] in ids
            # durations sum sensibly: children start at/after their parent
            # (comparable within one process's monotonic clock)
            by_id = {s["span_id"]: s for s in osd_spans}
            for s in osd_spans:
                parent = by_id.get(s["parent_id"])
                if parent is not None:
                    assert s["start"] >= parent["start"]

            await client.shutdown()
            await stop_cluster(mons, osds)

        asyncio.run(run())


class TestCodecSpans:
    def test_traced_encode_makes_no_transfer_of_its_own(self):
        """An op span on the codec path changes nothing it observes: a
        traced and an untraced encode of the same batch give the same
        parity bytes and the same number of device launches."""
        import numpy as np

        from ceph_tpu.codec import registry
        from ceph_tpu.ops import dispatch

        ec = registry.instance().factory("tpu", {"k": "4", "m": "2"})
        batch = np.random.default_rng(9).integers(0, 256, (3, 4, 4096), dtype=np.uint8)

        def encode():
            before = dispatch.LAUNCHES.snapshot()["launches"]
            parity = np.asarray(ec.encode_array(batch))
            return parity, dispatch.LAUNCHES.snapshot()["launches"] - before

        plain, plain_launches = encode()
        tr = Tracer("test", enabled=True)
        root = tr.start_span("ec:write")
        with tracer_mod.span_scope(root):
            traced, traced_launches = encode()
        root.finish()
        assert np.array_equal(plain, traced)
        assert traced_launches == plain_launches == 1
        names = [s["name"] for s in tr.export()]
        assert names == ["ec:write", "codec:tpu:encode"], names


# --- SLOW_OPS health pipeline -------------------------------------------------


class TestSlowOpsHealth:
    def test_slow_ops_raise_and_clear_in_health_detail(self):
        """An in-flight op older than osd_op_complaint_time flows OSD →
        MMgrReport → mgr digest → MMonMgrReport → mon SLOW_OPS, shows a
        per-daemon breakdown under `health detail`, surfaces in the
        prometheus healthcheck gauge, and clears once the op drains."""

        async def run():
            from ceph_tpu.client import Rados
            from ceph_tpu.mgr import Mgr
            from ceph_tpu.mgr.prometheus import PrometheusModule

            from test_cluster import start_cluster, stop_cluster, wait_until

            monmap, mons, osds = await start_cluster(1, 1)
            mgr = Mgr("x", monmap)
            mgr.beacon_interval = 0.1
            await mgr.start()
            await mgr.wait_for_active()
            prom = PrometheusModule()
            mgr.register_module(prom)

            client = Rados(monmap)
            await client.connect()

            osd = osds[0]
            osd.op_tracker.complaint_time = 0.05
            token = osd.op_tracker.create("artificially stuck op")

            async def health(detail=False):
                cmd = {"prefix": "health"}
                if detail:
                    cmd["detail"] = True
                rv, rs, out = await client.mon_command(cmd)
                assert rv == 0, rs
                return json.loads(out)

            def mon_sees_slow():
                slow = mons[0].pg_digest.get("slow_ops") or {}
                return bool(slow.get("osd.0", {}).get("count"))

            await wait_until(mon_sees_slow, 5.0, "slow op reaching the mon")
            payload = await health(detail=True)
            assert payload["status"] == "HEALTH_WARN"
            assert "SLOW_OPS" in payload["checks"]
            assert "1 slow ops" in payload["checks"]["SLOW_OPS"]
            assert any(
                line.startswith("osd.0:") for line in payload["detail"]["SLOW_OPS"]
            )
            # the mgr-side gauge mirrors the check while it is raised
            assert 'ceph_tpu_healthcheck{name="SLOW_OPS"' in prom.scrape()

            osd.op_tracker.finish(token)
            await wait_until(
                lambda: not mon_sees_slow(), 5.0, "slow op draining"
            )
            payload = await health(detail=True)
            assert "SLOW_OPS" not in payload["checks"]
            assert payload["status"] == "HEALTH_OK"

            await client.shutdown()
            await mgr.stop()
            await stop_cluster(mons, osds)

        asyncio.run(run())

    def test_mon_drops_digest_from_non_active_mgr(self):
        """Satellite fix: only the mgrmap's ACTIVE mgr may supply the
        PGMap digest — a spoofed MMonMgrReport (standby or impostor) must
        not flip mon-side state like SLOW_OPS or pool quotas."""

        async def run():
            from ceph_tpu.mgr import Mgr

            from test_cluster import start_cluster, stop_cluster, wait_until

            monmap, mons, osds = await start_cluster(1, 1)
            mgr = Mgr("x", monmap)
            mgr.beacon_interval = 0.1
            await mgr.start()
            await mgr.wait_for_active()
            await wait_until(
                lambda: "slow_ops" in mons[0].pg_digest, 5.0, "real digest"
            )

            evil = Messenger("mgr.evil")
            evil.add_dispatcher_tail(Collector())
            spoof = {
                "pools": {},
                "osds": {},
                "total_used_raw": 0,
                "slow_ops": {"osd.9": {"count": 99, "oldest_sec": 999.0}},
            }
            mon_addr = next(iter(monmap.addrs.values()))
            await evil.send_to(
                mon_addr, MMonMgrReport(digest=json.dumps(spoof).encode())
            )
            await asyncio.sleep(0.5)  # several beacon intervals
            assert "osd.9" not in (mons[0].pg_digest.get("slow_ops") or {})
            checks, _ = mons[0].health_checks()
            assert "SLOW_OPS" not in checks

            await evil.shutdown()
            await mgr.stop()
            await stop_cluster(mons, osds)

        asyncio.run(run())
