"""ISSUE 7 tentpole contracts: the device-launch watchdog + host
fallback, degraded-state health plumbing, and aggregator backpressure.

Acceptance shape: with `codec.launch` armed to fail (or the dispatch
wedged past the deadline), writes and recoveries complete BYTE-IDENTICAL
via the host oracle, the backend marks DEGRADED (gauge + health check),
and a probe self-heals it back to device dispatch."""

import itertools
import threading
import time

import numpy as np
import pytest

from ceph_tpu.codec import ErasureCodeTpuRs
from ceph_tpu.codec.matrix_codec import DecodeAggregator, EncodeAggregator
from ceph_tpu.common.fault_injector import global_injector
from ceph_tpu.ops import dispatch as ec_dispatch
from ceph_tpu.ops.guard import DeviceGuard, DeviceTimeout, device_guard
from ceph_tpu.stripe import StripeInfo
from ceph_tpu.stripe import stripe as stripe_mod


@pytest.fixture(autouse=True)
def _clean_guard_and_injector():
    """Guard state and the process-global injector must never leak
    across tests: a stray DEGRADED flag would silently reroute every
    later launch through the host path."""
    yield
    global_injector().clear()
    device_guard().mark_healthy()
    device_guard().configure(timeout_ms=20000, probe_interval_ms=2000)


def make_rs(k=4, m=2):
    ec = ErasureCodeTpuRs()
    ec.init({"k": str(k), "m": str(m)})
    return ec


def payload(sinfo, stripes, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, stripes * sinfo.stripe_width, dtype=np.uint8)


class TestHostOracle:
    """encode_array_host / decode_array_host are byte-identical to the
    device dispatch — the precondition for transparent fallback."""

    def test_encode_host_matches_device_rs42(self):
        ec = make_rs(4, 2)
        sinfo = StripeInfo(4 * 512, 512)
        data = payload(sinfo, 3, seed=1).reshape(3, 4, 512)
        dev = np.asarray(ec.encode_array(data))
        host = ec.encode_array_host(data)
        assert np.array_equal(dev, host)

    def test_encode_host_matches_device_xor_path(self):
        ec = make_rs(2, 1)  # m=1 all-ones row: the xor_reduce fast path
        sinfo = StripeInfo(2 * 512, 512)
        data = payload(sinfo, 2, seed=2).reshape(2, 2, 512)
        dev = np.asarray(ec.encode_array(data))
        host = ec.encode_array_host(data)
        assert np.array_equal(dev, host)

    def test_decode_host_matches_device_all_rs42_patterns(self):
        ec = make_rs(4, 2)
        sinfo = StripeInfo(4 * 512, 512)
        data = payload(sinfo, 2, seed=3).reshape(2, 4, 512)
        shards = np.concatenate(
            [data, np.asarray(ec.encode_array(data))], axis=1
        )  # (stripes, 6, 512)
        for r in (1, 2):
            for erasures in itertools.combinations(range(6), r):
                idx = ec.decode_index(list(erasures))
                survivors = shards[:, idx, :]
                dev = np.asarray(ec.decode_array(list(erasures), survivors))
                host = ec.decode_array_host(list(erasures), survivors)
                assert np.array_equal(dev, host), erasures


class TestLaunchFallback:
    """codec.launch armed to fail -> the aggregated launch completes on
    the host oracle, byte-identical, and the backend marks DEGRADED."""

    def setup_method(self):
        self.ec = make_rs(4, 2)
        self.sinfo = StripeInfo(4 * 4096, 4096)

    def test_encode_fallback_byte_identical_and_degraded(self):
        data = payload(self.sinfo, 2, seed=10)
        direct = stripe_mod.encode(self.sinfo, self.ec, data)
        before = ec_dispatch.FALLBACK_LAUNCHES.snapshot()["launches"]
        global_injector().inject("codec.launch", 5, hits=1)
        agg = EncodeAggregator(window=0)
        pend = stripe_mod.encode_launch(
            self.sinfo, self.ec, data, aggregator=agg
        )
        out = pend.result()
        for i in direct:
            assert np.array_equal(direct[i], out[i]), i
        assert (
            ec_dispatch.FALLBACK_LAUNCHES.snapshot()["launches"] == before + 1
        )
        assert device_guard().degraded
        assert agg.perf.get("host_fallbacks") == 1

    def test_decode_fallback_all_rs42_patterns_byte_identical(self):
        """The acceptance-criteria sweep: every RS(4,2) erasure pattern
        reconstructs byte-identically through the host-fallback path with
        codec.launch armed to fail."""
        data = payload(self.sinfo, 2, seed=11)
        shards = stripe_mod.encode(self.sinfo, self.ec, data)
        agg = DecodeAggregator(window=0)
        for r in (1, 2):
            for erasures in itertools.combinations(range(6), r):
                have = {
                    i: shards[i] for i in range(6) if i not in erasures
                }
                global_injector().inject("codec.launch", 5, hits=1)
                pend = stripe_mod.decode_shards_launch(
                    self.sinfo, self.ec, have, set(erasures), aggregator=agg
                )
                out = pend.result()
                for e in erasures:
                    assert np.array_equal(out[e], shards[e]), (erasures, e)
                device_guard().mark_healthy()
        assert agg.perf.get("host_fallbacks") == 21  # C(6,1)+C(6,2)

    def test_wedged_dispatch_times_out_to_fallback(self):
        """A dispatch that BLOCKS past ec_tpu_launch_timeout_ms (the
        round-4/5 hang shape) is abandoned by the watchdog and the launch
        completes on the host — in-flight writes no longer chain-stall
        behind a wedged backend."""
        data = payload(self.sinfo, 1, seed=12)
        direct = stripe_mod.encode(self.sinfo, self.ec, data)
        real = self.ec.encode_array

        def wedge(arr):
            time.sleep(0.5)  # well past the 50 ms deadline below
            return real(arr)

        device_guard().configure(timeout_ms=50)
        self.ec.encode_array = wedge
        try:
            agg = EncodeAggregator(window=0)
            pend = stripe_mod.encode_launch(
                self.sinfo, self.ec, data, aggregator=agg
            )
            out = pend.result()
        finally:
            self.ec.encode_array = real
        for i in direct:
            assert np.array_equal(direct[i], out[i]), i
        assert device_guard().degraded
        assert "deadline" in device_guard().reason

    def test_degraded_bypass_then_probe_self_heal(self):
        """While DEGRADED, launches bypass the device (no new device
        dispatches); once the probe interval elapses a successful probe
        heals the backend and dispatch returns to the device path."""
        data = payload(self.sinfo, 1, seed=13)
        agg = EncodeAggregator(window=0)
        device_guard().configure(probe_interval_ms=10_000_000)
        device_guard().mark_degraded("test")
        # burn the immediate post-degrade probe with a still-dead device,
        # so the long interval now gates re-probing
        assert not device_guard().maybe_probe(
            lambda: (_ for _ in ()).throw(RuntimeError("still dead"))
        )
        launches_before = ec_dispatch.LAUNCHES.snapshot()["launches"]
        pend = stripe_mod.encode_launch(
            self.sinfo, self.ec, data, aggregator=agg
        )
        pend.result()
        # bypass: no device dispatch happened
        assert (
            ec_dispatch.LAUNCHES.snapshot()["launches"] == launches_before
        )
        # shorten the interval: the next launch probes and self-heals
        device_guard().configure(probe_interval_ms=1)
        time.sleep(0.005)
        pend = stripe_mod.encode_launch(
            self.sinfo, self.ec, data, aggregator=agg
        )
        pend.result()
        assert not device_guard().degraded
        assert device_guard().probes >= 1
        assert (
            ec_dispatch.LAUNCHES.snapshot()["launches"] > launches_before
        )

    def test_perf_dump_exports_gauge_and_fallback_counters(self):
        dump = ec_dispatch.perf_dump()
        for key in (
            "backend_degraded",
            "backend_degraded_total",
            "backend_probes",
            "fallback_launches",
        ):
            assert key in dump, key
        device_guard().mark_degraded("gauge test")
        assert ec_dispatch.perf_dump()["backend_degraded"] == 1
        device_guard().mark_healthy()
        assert ec_dispatch.perf_dump()["backend_degraded"] == 0


class TestDeviceGuardUnit:
    def test_call_enforces_deadline(self):
        g = DeviceGuard(timeout_ms=50, probe_interval_ms=0)
        with pytest.raises(DeviceTimeout):
            g.call(lambda: time.sleep(1.0))

    def test_call_inline_when_disabled(self):
        g = DeviceGuard(timeout_ms=0, probe_interval_ms=0)
        assert g.call(lambda: 42) == 42

    def test_nested_call_runs_on_the_callers_worker(self):
        g = DeviceGuard(timeout_ms=2000, probe_interval_ms=0)
        outer = []

        def inner():
            return threading.current_thread()

        def body():
            outer.append(threading.current_thread())
            return g.call(inner)

        assert g.call(body) is outer[0]
        assert outer[0] is not threading.current_thread()

    def test_workers_keep_serving_after_timeouts(self):
        g = DeviceGuard(timeout_ms=50, probe_interval_ms=0)
        release = threading.Event()
        try:
            for _ in range(6):  # more stuck calls than the pool has workers
                with pytest.raises(DeviceTimeout):
                    g.call(release.wait)
            assert g.call(lambda: 7, timeout_ms=2000) == 7
        finally:
            release.set()

    def test_call_abandoned_while_queued_never_runs(self):
        from ceph_tpu.ops.guard import _launch_workers

        g = DeviceGuard(timeout_ms=1, probe_interval_ms=0)
        release = threading.Event()
        ran = []
        try:
            for _ in range(64):  # every idle worker blocks
                _launch_workers().submit(release.wait)
            with pytest.raises(DeviceTimeout):
                g.call(lambda: ran.append(1))
        finally:
            release.set()
        assert g.call(lambda: 7, timeout_ms=2000) == 7
        time.sleep(0.1)
        assert ran == [], "a call abandoned before it started still ran"

    def test_call_reraises_worker_exception(self):
        g = DeviceGuard(timeout_ms=1000, probe_interval_ms=0)
        with pytest.raises(RuntimeError, match="boom"):
            g.call(lambda: (_ for _ in ()).throw(RuntimeError("boom")))

    def test_probe_interval_gates_reprobes(self):
        g = DeviceGuard(timeout_ms=1000, probe_interval_ms=10_000_000)
        g.mark_degraded("x")
        # immediately after degrading, the first probe IS allowed (the
        # probe clock resets so a transient error heals fast)...
        assert g.maybe_probe(lambda: None)
        assert not g.degraded
        g.mark_degraded("y")
        g.maybe_probe(lambda: (_ for _ in ()).throw(RuntimeError("dead")))
        # ...but after a failed probe the interval gates the next one
        assert not g.maybe_probe(lambda: None)
        assert g.degraded
        assert g.probe_failures == 1

    def test_probe_disabled_means_sticky_degraded(self):
        g = DeviceGuard(timeout_ms=1000, probe_interval_ms=0)
        g.mark_degraded("x")
        assert not g.maybe_probe(lambda: None)
        assert g.degraded


class TestBackpressure:
    """ec_tpu_inflight_max_bytes bounds admitted-but-unsettled bytes:
    over the bound, submitters settle older launches first."""

    def setup_method(self):
        self.ec = make_rs(4, 2)
        self.sinfo = StripeInfo(4 * 4096, 4096)

    def test_admission_settles_older_groups(self):
        stripe_bytes = self.sinfo.stripe_width  # 16 KiB per submission
        agg = EncodeAggregator(
            window=64, inflight_max_bytes=2 * stripe_bytes
        )
        pends = [
            stripe_mod.encode_launch(
                self.sinfo, self.ec, payload(self.sinfo, 1, seed=i),
                aggregator=agg,
            )
            for i in range(6)
        ]
        # the throttle pushed back at least once and never let admitted
        # credit exceed the bound by more than one submission
        assert agg.perf.get("throttle_stalls") >= 1
        assert agg.inflight.current <= 3 * stripe_bytes
        oracle = [
            stripe_mod.encode(
                self.sinfo, self.ec, payload(self.sinfo, 1, seed=i)
            )
            for i in range(6)
        ]
        for pend, want in zip(pends, oracle):
            got = pend.result()
            for i in want:
                assert np.array_equal(want[i], got[i])
        # all credit returned once everything settled
        assert agg.inflight.current == 0

    def test_oversized_submission_is_admitted(self):
        agg = EncodeAggregator(window=0, inflight_max_bytes=1024)
        pend = stripe_mod.encode_launch(
            self.sinfo, self.ec, payload(self.sinfo, 4, seed=1),
            aggregator=agg,
        )
        pend.result()  # larger than the whole bound: must not wedge
        assert agg.inflight.current == 0

    def test_credit_released_on_sticky_failure(self):
        from ceph_tpu.codec.interface import EcError

        agg = EncodeAggregator(window=0, inflight_max_bytes=1 << 20)
        real, real_host = self.ec.encode_array, self.ec.encode_array_host

        def boom(*a, **kw):
            raise RuntimeError("both paths dead")

        self.ec.encode_array = boom
        self.ec.encode_array_host = boom
        try:
            pend = stripe_mod.encode_launch(
                self.sinfo, self.ec, payload(self.sinfo, 1, seed=2),
                aggregator=agg,
            )
            with pytest.raises(EcError):
                pend.result()
        finally:
            self.ec.encode_array = real
            self.ec.encode_array_host = real_host
        assert agg.inflight.current == 0  # failed groups leak no credit


class TestDegradedHealthPlumbing:
    """OSD status -> mgr digest -> mon HEALTH_WARN, and the mgr's own
    healthcheck gauge surface — built from one common/health.py shape."""

    def _mgr_with_degraded_osd(self):
        from ceph_tpu.mgr.mgr import DaemonState, Mgr
        from ceph_tpu.mon.monmap import MonMap

        mgr = Mgr("hx", MonMap(addrs={"a": "127.0.0.1:1"}))
        st = DaemonState()
        st.status = {
            "tpu_backend": {
                "degraded": True,
                "degraded_for_sec": 3.2,
                "reason": "encode launch failed: DeviceTimeout",
                "fallback_launches": 7,
            }
        }
        mgr.daemons["osd.0"] = st
        return mgr

    def test_mgr_health_check_and_digest_slice(self):
        mgr = self._mgr_with_degraded_osd()
        checks = mgr.health_checks()
        assert "TPU_BACKEND_DEGRADED" in checks
        assert checks["TPU_BACKEND_DEGRADED"]["severity"] == "HEALTH_WARN"
        assert "osd.0" in checks["TPU_BACKEND_DEGRADED"]["summary"]
        digest = mgr.pg_digest()
        assert digest["tpu_degraded"]["osd.0"]["fallback_launches"] == 7

    def test_mgr_check_clears_when_healthy(self):
        mgr = self._mgr_with_degraded_osd()
        mgr.daemons["osd.0"].status["tpu_backend"]["degraded"] = False
        assert "TPU_BACKEND_DEGRADED" not in mgr.health_checks()

    def test_mon_health_from_digest(self):
        from ceph_tpu.mon import MonMap, Monitor

        mon = Monitor("a", MonMap(addrs={"a": "127.0.0.1:1"}))
        mon.pg_digest = {
            "tpu_degraded": {
                "osd.1": {
                    "degraded_for_sec": 12.0,
                    "reason": "encode launch failed",
                    "fallback_launches": 3,
                }
            }
        }
        checks, details = mon.health_checks()
        assert "TPU_BACKEND_DEGRADED" in checks
        assert "osd.1" in checks["TPU_BACKEND_DEGRADED"]
        assert any("osd.1" in line for line in details["TPU_BACKEND_DEGRADED"])
        mon.pg_digest = {}
        checks, _ = mon.health_checks()
        assert "TPU_BACKEND_DEGRADED" not in checks

    def test_osd_status_carries_backend_verdict(self):
        from ceph_tpu.osd.osd import _tpu_backend_status

        device_guard().mark_degraded("status test")
        st = _tpu_backend_status()
        assert st["degraded"] is True
        assert st["reason"] == "status test"
        device_guard().mark_healthy()
        assert _tpu_backend_status()["degraded"] is False


class TestObjecterBackoff:
    """Resend pacing satellite: bounded exponential backoff + jitter,
    resends counted in a PerfCounter."""

    def _objecter(self):
        from ceph_tpu.client.objecter import Objecter
        from ceph_tpu.mon.monmap import MonMap

        return Objecter("client.bk", MonMap(addrs={"a": "127.0.0.1:1"}))

    def test_backoff_grows_and_caps(self):
        ob = self._objecter()
        delays = [ob._backoff_delay(a) for a in range(12)]
        # jittered into [0.5, 1.0) of the nominal value, capped at ~1 s
        assert 0.025 <= delays[0] < 0.05
        assert all(d <= 1.0 for d in delays)
        assert delays[10] >= 0.5  # capped region: still >= cap * 0.5
        # nominal (de-jittered) schedule is monotone non-decreasing
        noms = [min(1.0, 0.05 * (1 << min(a, 16))) for a in range(12)]
        assert noms == sorted(noms)

    def test_backoff_is_jittered_across_instances(self):
        a, b = self._objecter(), self._objecter()
        # two clients virtually never produce identical 8-delay runs —
        # the desynchronization that prevents retry storms
        run_a = [a._backoff_delay(i) for i in range(8)]
        run_b = [b._backoff_delay(i) for i in range(8)]
        assert run_a != run_b

    def test_backoff_fails_fast_when_deadline_inside_delay(self):
        """ISSUE 17 bugfix regression: an op whose deadline lands inside
        the next backoff window must raise NOW — the old shape slept the
        remaining budget away and failed only at the top of the loop."""
        import time

        from ceph_tpu.msg.messages import ReqId

        ob = self._objecter()
        span = ob.tracer.start_span("t")
        reqid = ReqId("client.bk", 1)
        with pytest.raises(TimeoutError, match="inside resend backoff"):
            # backoff floor is 0.0125 s; 1 ms of budget sits inside it
            ob._backoff_or_timeout(
                time.monotonic() + 0.001, 0, reqid, "oid", span
            )
        assert ob.perf.get("op_timeout") == 1
        # ample budget: the jittered delay comes back, nothing counted
        d = ob._backoff_or_timeout(
            time.monotonic() + 60.0, 0, reqid, "oid", span
        )
        assert 0.0 < d <= 1.0
        assert ob.perf.get("op_timeout") == 1
        span.finish()

    def test_resends_counted_in_perfcounter(self):
        import asyncio

        async def run():
            from ceph_tpu.msg.messages import PgId
            from ceph_tpu.osd.osdmap import OsdInfo

            ob = self._objecter()
            # a target whose OSD is unreachable: every send fails and the
            # resend loop backs off until the op deadline (CRUSH bypassed;
            # this tests the retry loop, not placement)
            ob._calc_target = lambda pool_id, oid: (PgId(1, 0, -1), 0)
            ob.osdmap.osds[0] = OsdInfo(addr="127.0.0.1:1", up=True)
            ob.osdmap.epoch = 1
            with pytest.raises(TimeoutError):
                await ob.op_submit(1, "oid", [], timeout=0.4)
            assert ob.perf.get("op") == 1
            assert ob.perf.get("op_timeout") == 1
            assert ob.perf.get("op_resend") >= 1
            await ob.stop()

        asyncio.run(run())


class TestInjectargsAsok:
    """The injectargs-style asok command arms the SAME process-global
    hooks the data path checks — the harness/tests contract."""

    def test_arm_codec_launch_over_asok_drives_host_fallback(self, tmp_path):
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
                Monitor(n, monmap, election_timeout=0.3) for n in monmap.addrs
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
                    "name": "ia21",
                    "profile": ["k=2", "m=1", "plugin=tpu"],
                }
            )
            assert rv == 0, rs
            await client.pool_create("iap", "erasure", profile="ia21", pg_num=1)
            io = await client.open_ioctx("iap")
            sock = str(tmp_path / "osd.0.asok")
            loop = asyncio.get_event_loop()

            def asok(**kw):
                # the sync client must not block the loop the asok
                # server runs on (test_cluster.py's executor pattern)
                return loop.run_in_executor(
                    None, lambda: admin_command(sock, "injectargs", **kw)
                )

            # arm through the asok, exactly as an operator would
            out = await asok(point="codec.launch", error=5, hits=1)
            assert "codec.launch" in out["armed"]
            before = ec_dispatch.FALLBACK_LAUNCHES.snapshot()["launches"]
            data = bytes(range(256)) * 64
            await io.write_full("armed-obj", data)
            assert await io.read("armed-obj") == data  # fallback, not EIO
            assert (
                ec_dispatch.FALLBACK_LAUNCHES.snapshot()["launches"] > before
            )
            # perf dump surfaces the degraded gauge + fallback counters
            dump = await loop.run_in_executor(
                None, lambda: admin_command(sock, "perf dump")
            )
            assert "fallback_launches" in dump["ec_dispatch"]
            # clear + runtime config set through the same command
            out = await asok(clear=True, conf={"ec_tpu_probe_interval_ms": 1})
            assert out["armed"] == []
            assert osds[0].conf.get("ec_tpu_probe_interval_ms") == 1
            # unknown names are rejected by the catalog
            with pytest.raises(RuntimeError, match="unregistered"):
                await asok(point="no.such.point")

            await client.shutdown()
            for o in osds:
                await o.stop()
            for m in mons:
                await m.stop()
            await asyncio.sleep(0.05)

        asyncio.run(run())
