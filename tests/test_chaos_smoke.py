"""Seed-fixed chaos smoke in tier-1 (ISSUE 7 acceptance): a real
mon+mgr+OSD cluster under mixed load survives socket faults, shard-read
EIO bursts, a gray OSD (ISSUE 17: one daemon's shard reads delayed ~50x
— hedged reads bound client p99, the laggy detector raises and clears
OSD_SLOW_PEER on exactly the victim), device-launch failures (host
fallback), a deep scrub under
client load with planted shard corruption (ISSUE 9: detected via
aggregated TPU verify launches, client p99 inside the QoS bound), an
OSD flap, a whole-OSD recovery storm (ISSUE 15: kill + dampened
auto-out + wave-batched rebuild under load with simultaneous
rebuild-time and p99 bounds), and a flapping-OSD phase (mon dampening
keeps the map stable while a genuinely dead OSD still rebuilds) —
converging to all-PGs-clean with ZERO lost writes and health clear of
SLOW_OPS / TPU_BACKEND_DEGRADED.

The full-size variant lives in `python -m ceph_tpu.tools.chaos`; this is
the `--smoke` configuration run in-process so tier-1 exercises the same
code path the operator harness does."""

from ceph_tpu.tools.chaos import run_chaos


class TestChaosSmoke:
    def test_smoke_converges_with_zero_lost_writes(self):
        report = run_chaos(seed=0xC405, smoke=True)
        assert report["converged"], report
        assert report["lost_writes"] == 0, report
        # every chaos phase actually ran
        assert len(report["events"]) == 13, report["events"]
        # ISSUE 17: the gray-OSD phase — one OSD's shard reads delayed
        # ~50x while its heartbeats stayed on time.  Hedged/re-planned
        # reads kept client p99 under the injected delay, the victim
        # (and only the victim) raised OSD_SLOW_PEER and cleared after
        # the delay lifted (asserted inside the phase), hedge spend
        # stayed within the token-bucket budget, and the healthy
        # control window hedged ~never
        assert report["gray_p99_ms"] is not None, report
        assert 0.0 < report["gray_p99_ms"] <= 2000.0, report
        assert report["gray_p99_ms"] < report["gray_delay_ms"], report
        assert report["gray_hedges"] >= 1, report
        assert report["gray_hedge_wins"] >= 1, report
        assert 0.0 < report["hedge_rate"], report
        assert report["control_hedges"] <= 2, report
        assert report["gray_victim"] >= 0, report
        assert report["gray_reads"] >= 1, report
        # ISSUE 10: the mixed-load phase attributed the load per pool
        # (windowed p99 keys ride the report for the bench fold), held
        # the SLO burn rate under bound, and kept trace retention
        # inside the token-bucket budget while complaint-age ops were
        # always retained (the bound assertions live inside the phase —
        # a violation fails the run, not just this check)
        assert "slo_worst_burn_rate" in report, report
        assert report["slo_worst_burn_rate"] <= 1.0, report
        assert "pool_p99_ms" in report and report["pool_p99_ms"], report
        ts = report["trace_sampling"]
        assert ts["kept_tail"] >= 1, report
        assert ts["unsampled"] >= 1, report
        assert ts["retained_spans"] >= 1, report
        # the launch-fault phase really drove the host fallback
        assert report["degraded_entered"], report
        assert report["fallback_launches"] >= 1, report
        # ISSUE 11: the pipelined-wedge phase armed launch faults while
        # depth>1 launches were in flight — every ticket recovered
        # byte-identically (asserted inside the phase) and the ring
        # provably ran deeper than one launch
        assert report["pipeline_wedge_tickets"] >= 4, report
        assert report["pipeline_max_inflight_depth"] >= 2, report
        # 4 launches through a depth-2 ring MUST overflow it: a zero here
        # means _drain_pipeline silently stopped bounding the ring
        assert report["pipeline_drains"] >= 1, report
        # ISSUE 20: the offload-fallback phase armed launch faults while
        # the csum and compressor services had launches in flight under
        # mixed load — directly-submitted tickets matched the host
        # oracle and compressed blobs round-tripped (asserted inside the
        # phase), the csums BlueStore actually STORED under fire equal
        # utils/crc32c of the stored form, both services really fell
        # back at least once, the offload_inflight mempool drained to
        # zero, and client p99 stayed inside the bound
        assert report["offload_csum_launches"] >= 1, report
        assert report["offload_csum_fallbacks"] >= 1, report
        assert report["offload_compress_fallbacks"] >= 1, report
        assert report["offload_stored_blocks"] >= 8, report
        assert report["offload_leaked_bytes"] == 0, report
        assert 0.0 <= report["offload_p99_ms"] <= 2000.0, report
        # ISSUE 9: the deep-scrub-under-load phase detected the planted
        # corruption through aggregated device verify launches (fewer
        # launches than objects = one launch covered many), and client
        # writes stayed inside the QoS bound while the scrub ran (the
        # bound itself is asserted inside the phase — a violation fails
        # the run, not just this check)
        assert report["scrub_errors_detected"] >= 1, report
        assert report["verify_launches"] >= 1, report
        assert report["verify_launches"] < report["scrub_objects"], report
        assert report["scrub_p99_ms"] >= 0.0, report
        # ISSUE 13: the HBM mempool ledger metered the run — a nonzero
        # peak (launches held device memory) and ZERO leaked bytes once
        # the pipelines drained (also asserted inside the run; these
        # keys are what bench folds alongside the throughput numbers)
        assert report["hbm_peak_bytes"] > 0, report
        assert report["hbm_leaked_bytes"] == 0, report
        # ISSUE 12: the whole run executed under dynamic lockdep — zero
        # lock-order violations across the concurrent aggregator/
        # scheduler/pipeline/cache stack, and the observed ordering
        # graph rides the report (non-empty: instrumented locks engaged)
        assert report["lockdep_violations"] == 0, report
        assert report["lockdep_graph"], report
        # ISSUE 14: the metrics-history module sampled real MMgrReports
        # the whole run with trend windows short enough to genuinely
        # evaluate — a healthy converged run keeps every trend sentinel
        # quiet (also asserted inside the run), and the store's
        # fixed-memory meta-stats ride the report
        assert report["history_sentinels_fired"] == 0, report
        assert report["history_sentinels_active"] == [], report
        assert report["history_store"]["series"] >= 1, report
        assert report["history_store"]["bytes"] > 0, report
        # ...and the perf_compare regressions slice folded into the
        # tracked JSON (no committed chaos baselines yet, so the slice
        # documents the comparison rather than flagging)
        assert "regressions" in report, report
        assert "flagged" in report["regressions"], report
        # ISSUE 15: the recovery-storm phase's keys are present and
        # bounded — the dead OSD rebuilt via wave-batched decode
        # launches (launches < objects recovered, witnessed by flight
        # records) inside the rebuild-time bound while client p99 held
        # (both bounds also asserted inside the phase)
        assert report["rebuild_seconds"] > 0.0, report
        assert report["rebuild_seconds"] <= 30.0, report
        assert report["storm_p99_ms"] >= 0.0, report
        assert report["storm_p99_ms"] <= 2000.0, report
        assert report["storm_waves"] >= 1, report
        assert report["storm_wave_flight_records"] >= 1, report
        assert report["storm_objects"] >= 5, report
        assert (
            report["storm_decode_launches"] < report["storm_objects"]
        ), report
        # ...and the flap-dampening phase: zero auto-outs while the OSD
        # bounced, markdown history retained, the dampened grace grew,
        # and the genuinely dead flapper still got outed (later) so its
        # data rebuilt
        assert report["flap_auto_outs"] == 0, report
        assert report["flap_markdowns"] >= 2, report
        assert report["flap_grace_sec"] >= 4.0, report
        assert report["flap_dead_out_wait_sec"] >= 3.0, report
        # ISSUE 16: the cluster-event timeline — the committed clog tail
        # was non-empty, carried no unexpected ERR entries (asserted
        # inside the run), every armed fault point audited, and BOTH
        # failure stories read straight out of `log last` in order
        assert report["clog_entries"] >= 1, report
        assert report["clog_errors"] >= 1, report  # planted corruption
        assert report["audit_entries"] >= 1, report
        assert report["storm_timeline"] == [
            "down", "out", "storm_engaged", "wave", "storm_complete",
        ], report
        assert report["flap_timeline"] == ["down", "dampened", "out"], report
        # health settled: no stuck SLOW_OPS, no lingering degraded check
        assert "SLOW_OPS" not in report["health_checks"], report
        assert "TPU_BACKEND_DEGRADED" not in report["health_checks"], report
        # machine-readable metrics came from the histogram substrate.
        # Both p99 keys are None when the tail spilled past the
        # histogram range (kept JSON-valid), so guard before comparing.
        assert report["p99_op_latency_sec"] is not None, report
        assert report["p99_op_latency_sec"] > 0.0, report
        assert report["recovery_decode_launches"] >= 0
        # ISSUE 8: the tracked-metric keys ROADMAP item 4 promotes into
        # PROGRESS/bench reporting ride the chaos JSON
        assert report.get("chaos_p99_ms") is not None, report
        assert report["chaos_p99_ms"] > 0.0, report
        assert "recovery_occupancy" in report, report
        assert report["recovery_occupancy"] >= 0.0, report
        # ...alongside a flight-recorder summary (launches + occupancy)
        assert "flight" in report, report
        assert report["flight"]["launches"] >= 1, report
        assert 0.0 <= report["flight"]["occupancy"] <= 1.0, report
        assert "progress_events_seen" in report, report
