"""Recovery-storm hardening contracts (ISSUE 15), at their seams:

1. Reserver preemption — higher-priority recovery preempts a granted
   backfill reservation (callback exactly once), release is
   exactly-once, and re-grants are deterministic after the preemptor
   releases.
2. Mon flap dampening — the down→out grace grows exponentially with
   recent markdowns, the churn cap bounds auto-outs per sweep tick,
   and a genuinely dead OSD (one markdown) still goes out at the base
   interval.
3. RecoveryStormController — engage/disengage thresholds, wave-batched
   round-robin admission bounded by the in-flight cap, SLO-aware
   shed/ramp from local io-accounting burn, decode-window widening,
   backfill preemption, and the status/perf surfaces.
4. Recovery-path fault points — a dropped PushOp (ec.recover_push)
   self-heals through the stalled-push retry.
5. Surfaces — the recovery_wave flight records render as their own
   Perfetto row, and the mgr progress module aggregates storm slices
   into a whole-OSD rebuild bar.
"""

import time

import pytest

from ceph_tpu.common.config import Config
from ceph_tpu.osd.reserver import Reserver


class TestReserverPreemption:
    def test_higher_priority_preempts_lowest_holder_once(self):
        r = Reserver(lambda: 1)
        fired = []
        assert r.try_reserve("backfill", priority=0,
                             on_preempt=lambda: fired.append("bf"))
        assert r.try_reserve("storm", priority=3)
        assert fired == ["bf"]
        assert r.holders() == {"storm": 3}
        assert r.preemptions == 1
        # the preempted key's slot is gone: releasing it is a no-op
        # (exactly once — the callback already surrendered it)
        assert not r.release("backfill")
        assert r.holders() == {"storm": 3}

    def test_equal_priority_never_preempts(self):
        r = Reserver(lambda: 1)
        assert r.try_reserve("a", priority=2)
        assert not r.try_reserve("b", priority=2)
        assert not r.try_reserve("c", priority=1)
        assert r.holders() == {"a": 2}
        assert r.preemptions == 0

    def test_release_is_exactly_once(self):
        r = Reserver(lambda: 2)
        assert r.try_reserve("a")
        assert r.release("a")
        assert not r.release("a")  # second release: no-op, reported
        assert not r.release("never-held")
        assert r.held() == 0

    def test_regrant_is_deterministic_after_preemptor_releases(self):
        r = Reserver(lambda: 1)
        state = {"held": True}

        def on_preempt():
            state["held"] = False

        assert r.try_reserve("bf", priority=0, on_preempt=on_preempt)
        assert r.try_reserve("storm", priority=3)
        assert not state["held"]
        # while the storm holds the slot, the backfill's tick-retry is
        # denied (equal-or-lower priority never preempts)
        assert not r.try_reserve("bf", priority=0, on_preempt=on_preempt)
        assert r.release("storm")
        # the next retry re-grants — and a sibling at the same priority
        # cannot bounce it
        assert r.try_reserve("bf", priority=0, on_preempt=on_preempt)
        assert not r.try_reserve("bf2", priority=0)
        assert r.holders() == {"bf": 0}

    def test_preemption_picks_the_lowest_priority_victim(self):
        r = Reserver(lambda: 2)
        fired = []
        assert r.try_reserve("low", priority=1,
                             on_preempt=lambda: fired.append("low"))
        assert r.try_reserve("mid", priority=2,
                             on_preempt=lambda: fired.append("mid"))
        assert r.try_reserve("high", priority=5)
        assert fired == ["low"]
        assert set(r.holders()) == {"mid", "high"}

    def test_backfill_pg_surrenders_and_resumes_on_preemption(self):
        """The PG wiring: a preempted backfill releases its remote
        grants, stops walking at the chunk boundary, and re-reserves on
        a later tick once the slot frees."""
        from test_backfill import _backfilling_pg

        from ceph_tpu.msg.messages import MBackfillReserve

        pg, osd = _backfilling_pg(n_objects=6)
        pg._kick_backfill()
        assert pg._bf_local_reserved
        # a remote slot stands granted (without starting the chunk, so
        # the preemption — not an in-flight push — is what stops us)
        pg._bf_granted.add(1)
        # a storm-priority reservation preempts the backfill slot
        assert osd.local_reserver.try_reserve(("storm", 0), priority=3)
        assert not pg._bf_local_reserved
        # the surrender sent a RELEASE for the granted remote slot
        releases = [
            m for _osd, m in osd.sent
            if isinstance(m, MBackfillReserve)
            and m.op == MBackfillReserve.RELEASE
        ]
        assert releases, "preempted backfill kept its remote grant"
        # while the storm holds the slot, ticks cannot re-reserve
        pg._kick_backfill()
        assert not pg._bf_local_reserved
        # storm done: the next tick re-grants and backfill resumes
        osd.local_reserver.release(("storm", 0))
        pg._kick_backfill()
        assert pg._bf_local_reserved


class _FakeMon:
    """Just enough of Monitor for OSDMonitor: leader + instant paxos."""

    def __init__(self, conf=None):
        self.conf = conf or Config({"name": "mon.t"}, env=False)
        self.osdmon = None
        self.pg_digest = {}

    def is_leader(self):
        return True

    def propose(self, service, blob, on_done=None):
        self.osdmon.apply_commit(blob)
        if on_done is not None:
            on_done(1)

    def publish_osdmap(self):
        pass


def _mon_with_osds(n=4, conf=None):
    from ceph_tpu.mon.osd_monitor import OSDMonitor
    from ceph_tpu.msg.messages import MOSDBoot

    mon = _FakeMon(conf=conf)
    osdmon = OSDMonitor(mon, min_down_reporters=2)
    mon.osdmon = osdmon
    osdmon.on_active()
    for i in range(n):
        osdmon.prepare_boot(MOSDBoot(osd=i, addr=f"a{i}", epoch=0))
    return mon, osdmon


def _mark_down(osdmon, osd):
    from ceph_tpu.msg.messages import MOSDFailure

    for reporter in ("osd.8", "osd.9"):
        osdmon.prepare_failure(
            MOSDFailure(target=osd, target_addr="", failed_for=1.0,
                        epoch=1),
            reporter=reporter,
        )


class TestMonFlapDampening:
    def _conf(self, **over):
        base = {
            "name": "mon.t",
            "mon_osd_down_out_interval": 2.0,
            "mon_osd_flap_window": 300.0,
            "mon_osd_flap_backoff": 2.0,
            "mon_osd_flap_max_auto_out_per_tick": 4,
        }
        base.update(over)
        return Config(base, env=False)

    def test_markdown_history_grows_the_grace_exponentially(self):
        mon, osdmon = _mon_with_osds(conf=self._conf())
        now = time.monotonic()
        assert osdmon._down_out_grace(1, now) == 2.0  # no history
        osdmon._note_markdown(1, now)
        assert osdmon._down_out_grace(1, now) == 2.0  # first failure
        osdmon._note_markdown(1, now)
        assert osdmon._down_out_grace(1, now) == 4.0
        osdmon._note_markdown(1, now)
        assert osdmon._down_out_grace(1, now) == 8.0
        stats = osdmon.flap_stats()
        assert stats["osds"][1]["markdowns"] == 3
        assert stats["osds"][1]["grace_sec"] == 8.0

    def test_window_expiry_forgives_old_markdowns(self):
        mon, osdmon = _mon_with_osds(conf=self._conf(mon_osd_flap_window=10.0))
        now = time.monotonic()
        osdmon._recent_markdowns[1] = [now - 60.0, now - 30.0, now]
        assert osdmon._down_out_grace(1, now) == 2.0  # only 1 in window
        assert osdmon._recent_markdowns[1] == [now]

    def test_quorum_markdown_records_history(self):
        mon, osdmon = _mon_with_osds(conf=self._conf())
        _mark_down(osdmon, 2)
        assert not osdmon.osdmap.is_up(2)
        assert osdmon._recent_markdown_count(2, time.monotonic()) == 1

    def test_sweep_dampens_flapper_but_outs_dead_osd(self):
        mon, osdmon = _mon_with_osds(conf=self._conf())
        now = time.monotonic()
        # osd.1: flapper with 3 recent markdowns, down 3s (grace 8s)
        _mark_down(osdmon, 1)
        osdmon._recent_markdowns[1] = [now, now, now]
        osdmon._down_since[1] = now - 3.0
        # osd.2: genuinely dead, first markdown, down 3s (grace 2s)
        _mark_down(osdmon, 2)
        osdmon._down_since[2] = now - 3.0
        osdmon._tick_down_out()
        assert osdmon.osdmap.osds[1].in_, "dampening failed to hold"
        assert not osdmon.osdmap.osds[2].in_, "dead OSD never outed"
        assert osdmon.auto_outs_total == 1
        assert osdmon.dampened_holds >= 1
        # the flapper still goes out once its (longer) grace elapses
        osdmon._down_since[1] = now - 9.0
        osdmon._tick_down_out()
        assert not osdmon.osdmap.osds[1].in_

    def test_boot_restarts_the_down_clock(self):
        """A bounce (down, boot, down) between two sweeps must not
        inherit the first episode's down-clock."""
        from ceph_tpu.msg.messages import MOSDBoot

        mon, osdmon = _mon_with_osds(conf=self._conf())
        _mark_down(osdmon, 1)
        osdmon._down_since[1] = time.monotonic() - 3.0  # past the 2 s grace
        osdmon.prepare_boot(MOSDBoot(osd=1, addr="a1b", epoch=0))
        assert 1 not in osdmon._down_since
        _mark_down(osdmon, 1)
        osdmon._tick_down_out()
        assert osdmon.osdmap.osds[1].in_, "fresh down episode outed at once"
        assert osdmon.auto_outs_total == 0

    def test_churn_cap_bounds_auto_outs_per_tick(self):
        mon, osdmon = _mon_with_osds(
            n=6, conf=self._conf(mon_osd_flap_max_auto_out_per_tick=2)
        )
        now = time.monotonic()
        for i in range(5):
            _mark_down(osdmon, i)
            osdmon._down_since[i] = now - 10.0
        osdmon._tick_down_out()
        outed = [i for i in range(5) if not osdmon.osdmap.osds[i].in_]
        assert len(outed) == 2, outed
        # the rest keep their down-clock and go out on later ticks
        osdmon._tick_down_out()
        outed = [i for i in range(5) if not osdmon.osdmap.osds[i].in_]
        assert len(outed) == 4
        osdmon._tick_down_out()
        assert sum(
            1 for i in range(5) if not osdmon.osdmap.osds[i].in_
        ) == 5
        assert osdmon.auto_outs_total == 5


@pytest.fixture(autouse=True)
def _clear_engaged_storms():
    """Stub controllers engaged-but-never-disengaged would otherwise
    pin the process-wide engaged refcount (the controller<->conf
    observer cycle delays their GC) and block the shared decode-window
    restore for later tests in the same process."""
    from ceph_tpu.osd import recovery_controller as rc

    for c in list(rc._ENGAGED):
        rc._ENGAGED.discard(c)
    yield
    for c in list(rc._ENGAGED):
        rc._ENGAGED.discard(c)


class _StormPeering:
    def __init__(self, missing):
        self.missing_oids = list(missing)

    def is_primary(self):
        return True

    def is_active(self):
        return True

    def all_missing_oids(self):
        return sorted(self.missing_oids)


class _StormPG:
    def __init__(self, oids):
        self.peering = _StormPeering(oids)
        self.recovering = set()
        self.admitted = []

    def _recover_one(self, oid):
        if oid in self.recovering:
            return
        self.recovering.add(oid)
        self.admitted.append(oid)

    def finish(self, oid):
        self.recovering.discard(oid)
        self.peering.missing_oids.remove(oid)


class _StormAggregator:
    def __init__(self):
        self.windows = []

    def configure(self, window=None, **_kw):
        self.windows.append(window)


class _StormOSD:
    def __init__(self, **conf_over):
        from ceph_tpu.common.io_accounting import IOAccountant

        base = {
            "name": "osd.0",
            "osd_recovery_storm_min_objects": 4,
            "osd_recovery_storm_wave_objects": 4,
            "osd_recovery_storm_min_wave_objects": 1,
            "osd_recovery_storm_max_inflight": 8,
            "osd_recovery_storm_slo_target_ms": 0.0,
        }
        base.update(conf_over)
        self.conf = Config(base, env=False)
        self.whoami = 0
        self.pgs = {}
        self.local_reserver = Reserver(lambda: 1)
        self.decode_aggregator = _StormAggregator()
        self.io_accountant = IOAccountant()


def _controller(**conf_over):
    from ceph_tpu.osd.recovery_controller import RecoveryStormController

    osd = _StormOSD(**conf_over)
    return osd, RecoveryStormController(osd)


class TestRecoveryStormController:
    def test_stays_idle_below_the_engage_threshold(self):
        osd, ctl = _controller()
        osd.pgs[(1, 0)] = _StormPG(["a", "b"])  # 2 < min 4
        ctl.tick()
        assert not ctl.engaged
        assert ctl.storms_started == 0
        assert osd.pgs[(1, 0)].admitted == []

    def test_engages_and_admits_waves_round_robin(self):
        osd, ctl = _controller()
        pg_a = osd.pgs[(1, 0)] = _StormPG([f"a{i}" for i in range(6)])
        pg_b = osd.pgs[(1, 1)] = _StormPG([f"b{i}" for i in range(6)])
        ctl.tick()
        assert ctl.engaged
        assert ctl.storms_started == 1
        assert ctl.waves == 1
        # wave of 4, round-robin: two objects from EACH pg, not four
        # from the first
        assert ctl.objects_admitted == 4
        assert len(pg_a.admitted) == 2 and len(pg_b.admitted) == 2
        # the decode window widened to the wave size on engage
        assert osd.decode_aggregator.windows[-1] >= 4

    def test_inflight_cap_bounds_admission_and_disengage_restores(self):
        osd, ctl = _controller(osd_recovery_storm_max_inflight=5)
        pg = osd.pgs[(1, 0)] = _StormPG([f"o{i}" for i in range(12)])
        ctl.tick()  # wave 1: 4 admitted
        ctl.tick()  # wave 2: capped at 5 total in flight -> 1 more
        assert len(pg.recovering) == 5
        assert ctl.objects_admitted == 5
        # nothing more until recoveries land
        ctl.tick()
        assert len(pg.recovering) == 5
        for oid in list(pg.recovering):
            pg.finish(oid)
        while pg.peering.missing_oids or pg.recovering:
            ctl.tick()
            for oid in list(pg.recovering):
                pg.finish(oid)
        ctl.tick()
        assert not ctl.engaged
        assert ctl.storms_completed == 1
        # the decode window restored to the configured default
        assert osd.decode_aggregator.windows[-1] == int(
            osd.conf.get("ec_tpu_decode_aggregate_window")
        )
        # ...and the reservation released
        assert osd.local_reserver.held() == 0

    def test_engage_preempts_a_granted_backfill_slot(self):
        osd, ctl = _controller()
        fired = []
        assert osd.local_reserver.try_reserve(
            ("bf", 1, 0), priority=0, on_preempt=lambda: fired.append(1)
        )
        osd.pgs[(1, 0)] = _StormPG([f"o{i}" for i in range(6)])
        ctl.tick()
        assert ctl.engaged
        assert fired == [1], "storm did not preempt the backfill slot"
        assert ("storm", 0) in osd.local_reserver.holders()
        assert ctl.preempted_backfills == 1

    def test_slo_burn_sheds_and_recovery_ramps(self):
        osd, ctl = _controller(
            osd_recovery_storm_slo_target_ms=10.0,
            osd_recovery_storm_slo_objective=0.5,
            osd_recovery_storm_burn_threshold=1.0,
            osd_recovery_storm_max_inflight=1000,
            osd_recovery_storm_wave_objects=8,
        )
        pg = osd.pgs[(1, 0)] = _StormPG([f"o{i}" for i in range(400)])

        def _tick_past_cadence():
            # burn evaluations are cadence-gated (completion-driven
            # ticks must not shrink the window); simulate elapsed time
            ctl._last_burn_eval -= 1.0
            ctl.tick()

        ctl.tick()  # engage; burn baseline snapshot
        assert ctl.engaged and ctl._wave == 8
        # between evaluations a completion-driven tick must NOT step
        # the wave (the stale-verdict guard)
        for _ in range(8):
            osd.io_accountant.account(1, "c", "read", 4096, 0.050)
        ctl.tick()
        assert ctl._wave == 8 and ctl.sheds == 0
        # a burn window full of slow client ops: every op 50 ms > the
        # 10 ms target -> bad fraction 1.0 / budget 0.5 = burn 2.0
        _tick_past_cadence()
        assert ctl._burn > 1.0
        assert ctl.sheds >= 1
        assert ctl._wave == 4
        for _ in range(8):
            osd.io_accountant.account(1, "c", "read", 4096, 0.050)
        _tick_past_cadence()
        assert ctl._wave == 2
        # idle window (no new ops): burn 0 -> ramp back toward ceiling
        _tick_past_cadence()
        assert ctl.ramps >= 1
        assert ctl._wave == 4
        _tick_past_cadence()
        assert ctl._wave == 8

    def test_last_storm_out_restores_the_shared_window(self):
        """The decode aggregator is process-wide: one OSD disengaging
        must not narrow a sibling's mid-storm window; the config
        default returns only when the LAST storm completes."""
        osd_a, ctl_a = _controller()
        osd_b, ctl_b = _controller()
        # both share "the" aggregator in production; the stubs record
        # their own configure calls, so assert via call absence/presence
        pg_a = osd_a.pgs[(1, 0)] = _StormPG([f"a{i}" for i in range(4)])
        pg_b = osd_b.pgs[(1, 0)] = _StormPG([f"b{i}" for i in range(4)])
        ctl_a.tick()
        ctl_b.tick()
        assert ctl_a.engaged and ctl_b.engaged
        widened_calls_b = len(osd_b.decode_aggregator.windows)
        # A finishes first: with B still engaged, NO restore happens
        for oid in list(pg_a.recovering):
            pg_a.finish(oid)
        ctl_a.tick()
        assert not ctl_a.engaged
        assert len(osd_a.decode_aggregator.windows) == 1  # widen only
        # B finishes: the last storm out restores from config
        for oid in list(pg_b.recovering):
            pg_b.finish(oid)
        ctl_b.tick()
        assert not ctl_b.engaged
        assert len(osd_b.decode_aggregator.windows) == widened_calls_b + 1
        assert osd_b.decode_aggregator.windows[-1] == int(
            osd_b.conf.get("ec_tpu_decode_aggregate_window")
        )

    def test_runtime_ceiling_shrink_clamps_live_wave(self):
        osd, ctl = _controller()
        osd.pgs[(1, 0)] = _StormPG([f"o{i}" for i in range(6)])
        ctl.tick()
        assert ctl._wave == 4
        osd.conf.set("osd_recovery_storm_wave_objects", 2)
        assert ctl._wave == 2  # observer clamped immediately

    def test_wave_commits_flight_records_and_perf_surfaces(self):
        from ceph_tpu.ops.flight_recorder import flight_recorder

        waves0 = sum(
            1 for r in flight_recorder().records()
            if r["kind"] == "recovery_wave"
        )
        osd, ctl = _controller()
        osd.pgs[(1, 0)] = _StormPG([f"o{i}" for i in range(6)])
        ctl.tick()
        recs = [
            r for r in flight_recorder().records()
            if r["kind"] == "recovery_wave"
        ]
        assert len(recs) == waves0 + 1
        assert recs[-1]["stripes"] == 4  # objects in the wave
        assert recs[-1]["sched_class"] == "recovery"
        assert recs[-1]["group"].startswith("storm:")
        perf = ctl.perf_dump()
        assert perf["waves"] == 1
        assert perf["objects_admitted"] == 4
        assert perf["engaged"] == 1
        assert perf["wave_objects"] == 4
        st = ctl.status()
        assert st["objects_total"] == 6
        assert st["engaged"] is True

    def test_final_status_reemits_then_clears(self):
        osd, ctl = _controller()
        pg = osd.pgs[(1, 0)] = _StormPG([f"o{i}" for i in range(4)])
        ctl.tick()
        for oid in list(pg.recovering):
            pg.finish(oid)
        ctl.tick()
        assert not ctl.engaged
        finals = [ctl.status() for _ in range(ctl.FINAL_REPORTS)]
        assert all(
            f["objects_done"] == f["objects_total"] == 4 for f in finals
        )
        assert ctl.status() == {}

    def test_note_osdmap_tracks_victims(self):
        class _Info:
            def __init__(self, up, in_):
                self.up, self.in_ = up, in_

        class _Map:
            def __init__(self, osds):
                self.osds = osds

        osd, ctl = _controller()
        old = _Map({1: _Info(True, True), 2: _Info(True, True)})
        new = _Map({1: _Info(False, True), 2: _Info(True, True)})
        ctl.note_osdmap(old, new)
        assert 1 in ctl.victims
        back = _Map({1: _Info(True, True), 2: _Info(True, True)})
        ctl.note_osdmap(new, back)
        assert 1 not in ctl.victims


class TestPushRetryFaultPoint:
    def test_dropped_push_self_heals_via_retry(self):
        """ec.recover_push drops a PushOp at the target; the primary's
        stalled-push retry re-sends and recovery completes."""
        from test_ec_backend import Cluster

        from ceph_tpu.common.fault_injector import global_injector
        from ceph_tpu.osd.osdmap import POOL_TYPE_ERASURE, PgPool

        pool = PgPool(
            id=1, name="ec", type=POOL_TYPE_ERASURE, size=3, min_size=2,
            erasure_code_profile="p", stripe_width=2 * 4096,
        )
        profiles = {"p": {"plugin": "tpu", "k": "2", "m": "1"}}
        c = Cluster(pool, profiles)
        c.write("obj", 0, b"x" * 5000)
        # the target loses its shard -> recovery pushes to it
        c.missing["obj"] = {2}
        inj = global_injector()
        inj.inject("ec.recover_push", 5, hits=1)
        done = []
        try:
            c.primary.recover_object("obj", {2}, done.append)
            c.pump()
            # the push was dropped: recovery is parked in WRITING
            assert not done
            rec = c.primary.recovery_ops["obj"]
            assert rec.pending_pushes == {2}
            time.sleep(0.02)
            assert c.primary.retry_stalled_pushes(0.01) == 1
            c.pump()
        finally:
            inj.clear("ec.recover_push")
        assert done == [0]
        assert c.primary.push_retries == 1

    def test_retry_disabled_with_nonpositive_grace(self):
        from test_ec_backend import Cluster

        from ceph_tpu.osd.osdmap import POOL_TYPE_ERASURE, PgPool

        pool = PgPool(
            id=1, name="ec", type=POOL_TYPE_ERASURE, size=3, min_size=2,
            erasure_code_profile="p", stripe_width=2 * 4096,
        )
        c = Cluster(pool, {"p": {"plugin": "tpu", "k": "2", "m": "1"}})
        c.write("obj", 0, b"y" * 4096)
        assert c.primary.retry_stalled_pushes(0.0) == 0


class TestStormTraceExport:
    def test_wave_records_render_their_own_perfetto_row(self):
        from ceph_tpu.ops.flight_recorder import FlightRecorder, new_record
        from ceph_tpu.tools.trace_export import (
            export_chrome_trace,
            validate_chrome_trace,
        )

        fr = FlightRecorder(capacity=16)
        wave = new_record("recovery_wave", group="storm:osd.2", tickets=3,
                          stripes=9, batch=9, sched_class="recovery")
        wave["dispatch_ts"] = wave["submit_ts"]
        wave["settle_ts"] = wave["submit_ts"] + 0.005
        fr.commit(wave)
        dec = new_record("decode", group="g", stripes=4, nbytes=4096)
        dec["h2d_s"] = 0.001
        dec["kernel_s"] = 0.001
        fr.commit(dec)
        trace = export_chrome_trace(fr.records())
        validate_chrome_trace(trace)
        storm_rows = [
            e for e in trace["traceEvents"]
            if e.get("pid") == "recovery storm"
        ]
        assert storm_rows, "no recovery-storm row in the export"
        assert storm_rows[0]["tid"] == "storm:osd.2"
        assert storm_rows[0]["args"]["objects"] == 9
        assert storm_rows[0]["args"]["pgs"] == 3
        # wave records stay OFF the device lanes (they are admission
        # spans, not device work)
        assert not any(
            e.get("pid") == "devices" and "recovery_wave" in e.get("name", "")
            for e in trace["traceEvents"]
        )


class TestProgressStormBars:
    def _mgr_module(self):
        import importlib
        import sys

        sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent))
        from test_progress import _FakeMgr

        from ceph_tpu.mgr.progress import ProgressModule

        mgr = _FakeMgr()
        mod = ProgressModule(stall_sec=60.0)
        mod.mgr = mgr
        return mgr, mod

    def test_storm_slices_aggregate_into_a_whole_osd_bar(self):
        mgr, mod = self._mgr_module()
        for daemon, done, total in (("osd.1", 3, 10), ("osd.2", 2, 6)):
            mgr.statuses[daemon] = {
                "recovery_storm": {
                    "engaged": True,
                    "victims": ["osd.0"],
                    "objects_done": done,
                    "objects_total": total,
                },
            }
        mod.tick()
        digest = mod.progress_digest()
        assert len(digest["storms"]) == 1
        bar = digest["storms"][0]
        assert bar["pgid"] == "osd.0"
        assert bar["kind"] == "storm"
        assert bar["objects_done"] == 5
        assert bar["objects_total"] == 16
        # the storm bar rides the progress gauge families labeled
        # kind="storm"
        fams = {name: rows for name, _t, _h, rows in mod.prometheus_metrics()}
        assert any(
            'kind="storm"' in row
            for row in fams["ceph_tpu_progress_fraction"]
        )
        # storms do NOT pollute the per-PG cluster aggregate (their
        # objects already count through per-PG recovery events)
        assert digest["cluster"]["objects_total"] == 0

    def test_completed_storm_expires_as_completed(self):
        mgr, mod = self._mgr_module()
        mgr.statuses["osd.1"] = {
            "recovery_storm": {
                "engaged": True, "victims": ["osd.0"],
                "objects_done": 4, "objects_total": 4,
            },
        }
        mod.tick()
        assert len(mod.storms) == 1
        mgr.statuses["osd.1"] = {}
        ev = next(iter(mod.storms.values()))
        ev.last_seen -= mod.EVENT_EXPIRE_SEC + 1
        completed0 = mod.completed
        mod.tick()
        assert not mod.storms
        assert mod.completed == completed0 + 1
