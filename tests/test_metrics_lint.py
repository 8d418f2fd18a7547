"""Metrics lint — exposition well-formedness for the mgr's prometheus
module (the CI satellite of ISSUE 1).

Scrapes `PrometheusModule.scrape()` from a running toy cluster and
validates the text-format contract a real Prometheus server (and
`promtool check metrics`) enforces: every family announced exactly once
with HELP + TYPE before its samples, no duplicate families, and
histogram families carrying monotonically non-decreasing cumulative
`le` buckets ending at +Inf with consistent `_sum`/`_count`.
"""

import asyncio
import re

import pytest

_SAMPLE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(?P<labels>[^}]*)\})? (?P<value>\S+)$'
)


def lint_exposition(text: str) -> dict:
    """Parse and validate an exposition payload; returns
    {family: {"type", "help", "samples": [(name, labels, value)]}}.
    Raises AssertionError on any contract violation."""
    assert text.endswith("\n"), "exposition must end with a newline"
    families: dict[str, dict] = {}
    current = None  # family the last HELP/TYPE block opened
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            _, _, rest = line.partition("# HELP ")
            name, _, help_ = rest.partition(" ")
            assert name not in families, f"line {lineno}: duplicate family {name}"
            families[name] = {"type": None, "help": help_, "samples": []}
            current = name
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, _, ftype = rest.partition(" ")
            assert name == current, (
                f"line {lineno}: TYPE for {name} outside its HELP block"
            )
            assert families[name]["type"] is None, (
                f"line {lineno}: duplicate TYPE for {name}"
            )
            assert ftype in ("counter", "gauge", "histogram", "summary", "untyped")
            families[name]["type"] = ftype
            continue
        assert not line.startswith("#"), f"line {lineno}: unknown comment {line!r}"
        m = _SAMPLE.match(line)
        assert m, f"line {lineno}: malformed sample {line!r}"
        name = m.group("name")
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            stripped = name.removesuffix(suffix)
            if stripped != name and stripped in families and families[
                stripped
            ]["type"] == "histogram":
                base = stripped
                break
        assert base in families, f"line {lineno}: sample {name} has no HELP/TYPE"
        assert base == current, (
            f"line {lineno}: sample {name} outside family {current} block"
        )
        float(m.group("value"))  # every value parses as a number
        labels = {}
        for part in (m.group("labels") or "").split(","):
            if part:
                k, _, v = part.partition("=")
                labels[k] = v.strip('"')
        families[base]["samples"].append((name, labels, float(m.group("value"))))
    for name, fam in families.items():
        assert fam["type"] is not None, f"family {name} has HELP but no TYPE"
        assert fam["help"].strip(), f"family {name} has empty HELP"
        if fam["type"] == "histogram":
            _check_histogram(name, fam["samples"])
    return families


def _check_histogram(name: str, samples: list) -> None:
    """Per label-set (minus `le`): buckets cumulative and non-decreasing,
    +Inf last, and _count == the +Inf bucket."""
    series: dict[tuple, dict] = {}
    for sname, labels, value in samples:
        key = tuple(sorted((k, v) for k, v in labels.items() if k != "le"))
        rec = series.setdefault(key, {"buckets": [], "sum": None, "count": None})
        if sname == f"{name}_bucket":
            assert "le" in labels, f"{name}: bucket sample without le"
            le = float("inf") if labels["le"] == "+Inf" else float(labels["le"])
            rec["buckets"].append((le, value))
        elif sname == f"{name}_sum":
            rec["sum"] = value
        elif sname == f"{name}_count":
            rec["count"] = value
    for key, rec in series.items():
        assert rec["buckets"], f"{name}{dict(key)}: histogram without buckets"
        les = [le for le, _ in rec["buckets"]]
        assert les == sorted(les), f"{name}{dict(key)}: le bounds not sorted"
        assert les[-1] == float("inf"), f"{name}{dict(key)}: missing +Inf bucket"
        counts = [c for _, c in rec["buckets"]]
        assert counts == sorted(counts), (
            f"{name}{dict(key)}: cumulative bucket counts decrease"
        )
        assert rec["sum"] is not None and rec["count"] is not None, (
            f"{name}{dict(key)}: missing _sum/_count"
        )
        assert rec["count"] == counts[-1], (
            f"{name}{dict(key)}: _count != +Inf bucket"
        )


class TestLintHelper:
    """The linter itself must catch the failure modes it exists for."""

    def test_accepts_wellformed_histogram(self):
        text = (
            "# HELP h latency\n# TYPE h histogram\n"
            'h_bucket{le="0.1"} 1\nh_bucket{le="+Inf"} 3\n'
            "h_sum 1.5\nh_count 3\n"
        )
        fam = lint_exposition(text)
        assert fam["h"]["type"] == "histogram"

    @pytest.mark.parametrize(
        "text,why",
        [
            ("m 1\n", "sample without HELP/TYPE"),
            ("# HELP m x\n# TYPE m gauge\n# HELP m x\n# TYPE m gauge\nm 1\n",
             "duplicate family"),
            ("# HELP h x\n# TYPE h histogram\n"
             'h_bucket{le="1"} 5\nh_bucket{le="+Inf"} 3\nh_sum 1\nh_count 3\n',
             "decreasing cumulative buckets"),
            ("# HELP h x\n# TYPE h histogram\n"
             'h_bucket{le="1"} 1\nh_sum 1\nh_count 1\n',
             "missing +Inf bucket"),
        ],
    )
    def test_rejects_malformed(self, text, why):
        with pytest.raises(AssertionError):
            lint_exposition(text)


def _sanitize(name: str) -> str:
    return "".join(c if c.isalnum() or c == "_" else "_" for c in name)


def _docs_metric_tokens() -> set[str]:
    """Backticked `ceph_tpu_*` tokens from docs/OBSERVABILITY.md (labels
    stripped; a trailing `*` marks a documented prefix family)."""
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "OBSERVABILITY.md",
    )
    with open(path) as f:
        text = f.read()
    tokens = set()
    for m in re.finditer(r"`(ceph_tpu_[A-Za-z0-9_.*]+)(?:\{[^`]*\})?`", text):
        tokens.add(m.group(1))
    return tokens


class TestClusterScrapeLint:
    def test_scrape_from_toy_cluster_is_wellformed(self):
        """Boot mon+OSDs+mgr, drive a few ops, and lint the full scrape:
        the histogram families (op_latency et al.) must be real Prometheus
        histograms, every family well-announced, and — the ISSUE 8
        cross-lint — every `ec_dispatch` perf-dump counter, the canonical
        device-utilization families, and the progress gauges present in
        BOTH the scrape and docs/OBSERVABILITY.md, and vice versa."""

        async def run():
            from ceph_tpu.client import Rados
            from ceph_tpu.mgr import Mgr, ProgressModule
            from ceph_tpu.mgr.prometheus import PrometheusModule
            from ceph_tpu.ops import dispatch as ec_dispatch

            from test_cluster import start_cluster, stop_cluster, wait_until

            from ceph_tpu.mgr.iostat import IostatModule

            monmap, mons, osds = await start_cluster(1, 2)
            mgr = Mgr("x", monmap)
            mgr.beacon_interval = 0.1
            await mgr.start()
            await mgr.wait_for_active()
            prom = PrometheusModule()
            mgr.register_module(prom)
            mgr.register_module(ProgressModule())
            # short windows + a pinned SLO target so the burn/target
            # gauges carry samples within the test's wait budget
            iostat = IostatModule(window_sec=2.0, slo_target_ms=5000.0)
            mgr.register_module(iostat)
            # metrics-history meta-gauges + dashboard map_errors
            # (ISSUE 14): both modules export through the same hook
            from ceph_tpu.mgr import DashboardModule, MetricsHistoryModule

            history = MetricsHistoryModule()
            mgr.register_module(history)
            dashboard = DashboardModule()
            mgr.register_module(dashboard)
            # cluster-event timeline families (ISSUE 16)
            from ceph_tpu.mgr import ClogModule

            clog_mod = ClogModule()
            mgr.register_module(clog_mod)

            client = Rados(monmap)
            await client.connect()
            await client.pool_create("lintp", "replicated", size=2, pg_num=2)
            io = await client.open_ioctx("lintp")
            for i in range(4):
                await io.write_full(f"o{i}", b"x" * 4096)
            # a committed ERROR clog entry for the clog-family cross-lint
            # (the pool create above already produced the audit entry)
            osds[0].clog_error("lint: planted inconsistency probe")

            # one eager encode so the occupancy distribution has a
            # bucket (devices_per_launch.<n> keys exist only once a
            # coding dispatch ran in this process)
            import numpy as np

            from ceph_tpu.codec import ErasureCodeTpuRs

            ec = ErasureCodeTpuRs()
            ec.init({"k": "2", "m": "1"})
            np.asarray(ec.encode_array(
                np.zeros((1, 2, 512), dtype=np.uint8)
            ))

            # ...and one eager csum submit + device-compressor batch so
            # the ISSUE 20 offload services carry their full counter
            # sets (the launch-path keys materialize lazily on first
            # use) before the round-trip snapshot below
            from ceph_tpu.compressor import get_compressor
            from ceph_tpu.ops.checksum_offload import (
                default_csum_aggregator,
            )
            from ceph_tpu.ops.offload_runtime import offload_perf_dump

            np.asarray(default_csum_aggregator().submit_blocks(
                np.zeros((2, 512), dtype=np.uint8)
            ).result())
            get_compressor("device").compress_batch(
                [bytes(65536), bytes(65536)]
            )
            offload_keys = set(offload_perf_dump())
            assert {"services", "csum.pending", "csum.launches",
                    "compress.pending", "compress.launches",
                    "csum.host_fallbacks",
                    "compress.host_fallbacks"} <= offload_keys

            # snapshot the perf-dump key set BEFORE waiting on the
            # scrape: the OSD reports the same process-wide counters, so
            # every key here must round-trip through MMgrReport
            dispatch_keys = set(ec_dispatch.perf_dump())
            # ISSUE 9 cross-lint: the verify counters ride the dispatch
            # namespace, and the launch scheduler's per-class slice
            # round-trips twice — inside ec_dispatch (sched.*) and under
            # its canonical ec_sched prefix on MMgrReport
            from ceph_tpu.ops.launch_scheduler import launch_scheduler

            assert {"verify_launches", "verify_stripes",
                    "verify_bytes"} <= dispatch_keys
            sched_keys = set(launch_scheduler().perf_dump())
            assert {f"sched.{k}" for k in sched_keys} <= dispatch_keys
            # ISSUE 11 cross-lint: the pipeline-ring slice and the
            # device-resident chunk-cache counters ride the dispatch
            # namespace too
            from ceph_tpu.ops.device_cache import device_chunk_cache

            assert {
                f"pipeline.{k}" for k in ec_dispatch.PIPELINE.snapshot()
            } <= dispatch_keys
            assert {
                f"cache.{k}" for k in device_chunk_cache().perf_dump()
            } <= dispatch_keys

            def all_reported():
                text = prom.scrape()
                if "op_latency" not in text or not all(
                    f"ceph_tpu_ec_dispatch_{_sanitize(k)}" in text
                    for k in dispatch_keys
                ):
                    return False
                # ...and the ISSUE 20 offload-service slice arrived
                if not all(
                    f"ceph_tpu_offload_{_sanitize(k)}" in text
                    for k in offload_keys
                ):
                    return False
                # ...and the iostat module consumed a pool_io report:
                # the per-pool attribution families must carry SAMPLES
                # (ISSUE 10), not just announce themselves
                if 'ceph_tpu_pool_ops{pool="' not in text:
                    return False
                # ..and the report carrying op SAMPLES arrived: the
                # dispatch counters are process-wide, so when earlier
                # tests already ran coding dispatches every key exists
                # in the OSD's FIRST report — which may have been sent
                # before the writes above completed.  Waiting on the
                # announcement alone races the next beacon against the
                # count>0 assertion below.
                op_lat = lint_exposition(text)[
                    "ceph_tpu_op_latency"]["samples"]
                return any(n == "ceph_tpu_op_latency_count" and v > 0
                           for n, _, v in op_lat)

            await wait_until(
                all_reported, 5.0, "op_latency samples + ec_dispatch in scrape"
            )
            families = lint_exposition(prom.scrape())

            # the tentpole's promised families are present and typed right
            assert families["ceph_tpu_op_latency"]["type"] == "histogram"
            assert families["ceph_tpu_osd_up"]["type"] == "gauge"
            assert families["ceph_tpu_healthcheck"]["type"] == "gauge"
            # a daemon that sampled ops has a non-empty latency series
            op_lat = families["ceph_tpu_op_latency"]["samples"]
            assert any(n == "ceph_tpu_op_latency_count" and v > 0
                       for n, _, v in op_lat)

            docs = _docs_metric_tokens()
            docs_exact = {t for t in docs if not t.endswith("*")}
            docs_prefix = {t[:-1] for t in docs if t.endswith("*")}

            def documented(name: str) -> bool:
                return name in docs_exact or any(
                    name.startswith(p) for p in docs_prefix
                )

            # direction 1: every ec_dispatch perf-dump counter reaches
            # the scrape AND is documented
            for key in dispatch_keys:
                fam = f"ceph_tpu_ec_dispatch_{_sanitize(key)}"
                assert fam in families, f"{fam} missing from scrape"
                assert documented(fam), (
                    f"{fam} (perf-dump key {key!r}) not in "
                    "docs/OBSERVABILITY.md metrics index"
                )
            # the canonical utilization names + progress gauges too
            for fam in (
                "ceph_tpu_ec_device_busy_seconds",
                "ceph_tpu_ec_device_occupancy",
                "ceph_tpu_progress_fraction",
                "ceph_tpu_progress_rate_objects",
                "ceph_tpu_progress_eta_seconds",
                "ceph_tpu_progress_active",
            ):
                assert fam in families, f"{fam} missing from scrape"
                assert documented(fam), f"{fam} not documented"
            # ...and the canonical ec_sched families (ISSUE 9): every
            # scheduler perf-dump key reaches the scrape under its
            # ceph_tpu_ec_sched_* name AND is documented
            for key in sched_keys:
                fam = f"ceph_tpu_ec_sched_{_sanitize(key)}"
                assert fam in families, f"{fam} missing from scrape"
                assert documented(fam), f"{fam} not documented"
            # the scheduler's queue-depth export must be a gauge — a
            # counter-typed depth would corrupt PromQL rate() queries
            assert (
                families["ceph_tpu_ec_sched_client_queue_depth"]["type"]
                == "gauge"
            )
            # ISSUE 11: the pipeline/cache families have EXPLICIT index
            # rows (the broad `ceph_tpu_ec_dispatch_*` prose token must
            # not be what documents them), and their level exports are
            # gauges while the hit/miss traffic stays counter-typed
            assert "ceph_tpu_ec_dispatch_pipeline_*" in docs, (
                "pipeline family needs its own docs index row"
            )
            assert "ceph_tpu_ec_dispatch_cache_*" in docs, (
                "device-cache family needs its own docs index row"
            )
            for fam in (
                "ceph_tpu_ec_dispatch_pipeline_depth",
                "ceph_tpu_ec_dispatch_pipeline_inflight",
                "ceph_tpu_ec_dispatch_cache_resident_bytes",
                "ceph_tpu_ec_dispatch_cache_entries",
            ):
                assert families[fam]["type"] == "gauge", fam
            assert (
                families["ceph_tpu_ec_dispatch_cache_hits"]["type"]
                == "counter"
            )
            # verify-aggregator families round-trip like the encode/
            # decode aggregators'
            assert any(
                f.startswith("ceph_tpu_ec_verify_aggregator_")
                for f in families
            ), "verify aggregator families missing from scrape"

            # ISSUE 10 cross-lint: every family the iostat module
            # exports reaches the scrape AND the docs index, with the
            # promised gauge-vs-counter-vs-histogram typing
            iostat_fams = {
                name: ftype
                for name, ftype, _h, _r in iostat.prometheus_metrics()
            }
            for fam, ftype in iostat_fams.items():
                assert fam in families, f"{fam} missing from scrape"
                assert families[fam]["type"] == ftype, (
                    f"{fam}: scrape type {families[fam]['type']} != "
                    f"module type {ftype}"
                )
                assert documented(fam), f"{fam} not documented"
            assert iostat_fams["ceph_tpu_pool_ops"] == "counter"
            assert iostat_fams["ceph_tpu_pool_ops_rate"] == "gauge"
            assert iostat_fams["ceph_tpu_pool_slo_burn_rate"] == "gauge"
            assert (
                iostat_fams["ceph_tpu_pool_latency_seconds"] == "histogram"
            )
            # the attribution families carry real per-pool samples whose
            # labels include op class
            pool_ops = families["ceph_tpu_pool_ops"]["samples"]
            assert any(
                labels.get("op") in ("read", "write", "recovery")
                and float(v) > 0
                for _n, labels, v in pool_ops
            ), pool_ops
            # SLO gauges have samples (a target was pinned) and the
            # burn family carries both windows
            burn = families["ceph_tpu_pool_slo_burn_rate"]["samples"]
            assert {l.get("window") for _n, l, _v in burn} >= {
                "fast", "slow",
            }, burn
            assert families["ceph_tpu_pool_slo_target_seconds"]["samples"]

            # ISSUE 13 cross-lint: the HBM mempool ledger families are
            # gauge-typed (residency rises AND falls), pool-labeled
            # strictly from the ledger's own pool set, documented, and
            # carry per-daemon samples once the OSDs reported
            from ceph_tpu.common.mempool import ledger as hbm_ledger

            hbm_pools = set(hbm_ledger().snapshot())
            for fam in (
                "ceph_tpu_mempool_bytes",
                "ceph_tpu_mempool_buffers",
                "ceph_tpu_mempool_peak_bytes",
            ):
                assert fam in families, f"{fam} missing from scrape"
                assert families[fam]["type"] == "gauge", fam
                assert documented(fam), f"{fam} not documented"
                samples = families[fam]["samples"]
                assert samples, f"{fam} announced but carries no samples"
                for _n, labels, _v in samples:
                    assert labels.get("pool") in hbm_pools, (
                        f"{fam} sample labeled with unknown pool "
                        f"{labels.get('pool')!r}"
                    )
                    assert labels.get("daemon", "").startswith("osd."), (
                        labels
                    )
            for fam in (
                "ceph_tpu_hbm_pressure_ratio",
                "ceph_tpu_hbm_target_bytes",
            ):
                assert fam in families, f"{fam} missing from scrape"
                assert families[fam]["type"] == "gauge", fam
                assert documented(fam), f"{fam} not documented"
                assert families[fam]["samples"], fam
            # direction 2: every scraped mempool family maps back to a
            # ledger export (bytes / buffers / peak_bytes only)
            for fam in families:
                if fam.startswith("ceph_tpu_mempool_"):
                    suffix = fam.removeprefix("ceph_tpu_mempool_")
                    assert suffix in (
                        "bytes", "buffers", "peak_bytes",
                    ), f"scraped {fam} has no mempool ledger source"

            # ISSUE 14 cross-lint: every family the metrics-history
            # module exports reaches the scrape AND the docs index
            # with its declared typing, and vice versa — every scraped
            # ceph_tpu_history_* family maps back to a module export.
            # The meta-gauges are the fixed-memory witness: gauges for
            # levels (series/points/bytes/sentinel state), counters
            # for the monotone eviction/append/fired totals.
            history_fams = {
                name: ftype
                for name, ftype, _h, _r in history.prometheus_metrics()
            }
            for fam, ftype in history_fams.items():
                assert fam in families, f"{fam} missing from scrape"
                assert families[fam]["type"] == ftype, (
                    f"{fam}: scrape type {families[fam]['type']} != "
                    f"module type {ftype}"
                )
                assert documented(fam), f"{fam} not documented"
                assert families[fam]["samples"], (
                    f"{fam} announced but carries no samples"
                )
            assert history_fams["ceph_tpu_history_series"] == "gauge"
            assert history_fams["ceph_tpu_history_bytes"] == "gauge"
            assert history_fams["ceph_tpu_history_points"] == "gauge"
            assert (
                history_fams["ceph_tpu_history_sentinel_active"] == "gauge"
            )
            assert history_fams["ceph_tpu_history_evictions"] == "counter"
            assert (
                history_fams["ceph_tpu_history_sentinels_fired"]
                == "counter"
            )
            # the sentinel-activity gauge renders one row per known
            # sentinel code, all quiet on a healthy cluster
            sentinel_rows = families[
                "ceph_tpu_history_sentinel_active"]["samples"]
            assert {
                l.get("sentinel") for _n, l, _v in sentinel_rows
            } == {
                "TPU_THROUGHPUT_REGRESSION",
                "TPU_OCCUPANCY_COLLAPSE",
                "TPU_QUEUE_WAIT_INFLATION",
            }
            assert all(v == 0 for _n, _l, v in sentinel_rows)
            for fam in families:
                if fam.startswith("ceph_tpu_history_"):
                    assert fam in history_fams, (
                        f"scraped {fam} has no metrics_history "
                        "prometheus_metrics() source"
                    )
            # dashboard satellite: map_errors is a real scrape family
            # now, not a module-local counter nobody can see
            assert (
                families["ceph_tpu_dashboard_map_errors"]["type"]
                == "counter"
            )
            assert documented("ceph_tpu_dashboard_map_errors")
            assert families["ceph_tpu_dashboard_map_errors"]["samples"]

            # trace-sampling families (ISSUE 10 layer 3): every
            # sampling_stats() key the OSD reports round-trips onto the
            # scrape as ceph_tpu_trace_<key>, and vice versa; knobs and
            # the pending depth are gauges, the verdicts counters
            trace_keys = set(osds[0].tracer.sampling_stats())
            for key in trace_keys:
                fam = f"ceph_tpu_trace_{_sanitize(key)}"
                assert fam in families, f"{fam} missing from scrape"
                assert documented(fam), f"{fam} not documented"
            for fam in families:
                if fam.startswith("ceph_tpu_trace_"):
                    key = fam.removeprefix("ceph_tpu_trace_")
                    assert key in {_sanitize(k) for k in trace_keys}, (
                        f"scraped {fam} has no sampling_stats() source"
                    )
            assert families["ceph_tpu_trace_sampled"]["type"] == "counter"
            assert families["ceph_tpu_trace_kept_tail"]["type"] == "counter"
            for fam in (
                "ceph_tpu_trace_sample_rate",
                "ceph_tpu_trace_budget_per_sec",
                "ceph_tpu_trace_pending_traces",
            ):
                assert families[fam]["type"] == "gauge", fam

            # recovery-storm families (ISSUE 15): every controller
            # perf-dump key round-trips onto the scrape as
            # ceph_tpu_recovery_storm_<key> AND is documented, and vice
            # versa — every scraped recovery_storm family maps back to
            # a controller export.  Levels (wave size, in-flight depth,
            # engagement, burn rate) are gauges; the wave/shed/ramp/
            # storm totals stay counters.
            storm_keys = set(osds[0].recovery_storm.perf_dump())
            for key in storm_keys:
                fam = f"ceph_tpu_recovery_storm_{_sanitize(key)}"
                assert fam in families, f"{fam} missing from scrape"
                assert documented(fam), f"{fam} not documented"
                assert families[fam]["samples"], (
                    f"{fam} announced but carries no samples"
                )
            for fam in families:
                if fam.startswith("ceph_tpu_recovery_storm_"):
                    key = fam.removeprefix("ceph_tpu_recovery_storm_")
                    assert key in {_sanitize(k) for k in storm_keys}, (
                        f"scraped {fam} has no RecoveryStormController "
                        "perf_dump() source"
                    )
            for fam in (
                "ceph_tpu_recovery_storm_wave_objects",
                "ceph_tpu_recovery_storm_inflight",
                "ceph_tpu_recovery_storm_engaged",
                "ceph_tpu_recovery_storm_burn_rate",
            ):
                assert families[fam]["type"] == "gauge", fam
            for fam in (
                "ceph_tpu_recovery_storm_waves",
                "ceph_tpu_recovery_storm_objects_admitted",
                "ceph_tpu_recovery_storm_sheds",
                "ceph_tpu_recovery_storm_ramps",
                "ceph_tpu_recovery_storm_storms_started",
                "ceph_tpu_recovery_storm_storms_completed",
                "ceph_tpu_recovery_storm_preempted_backfills",
            ):
                assert families[fam]["type"] == "counter", fam

            # ISSUE 20 cross-lint: the device-offload service registry —
            # every offload_perf_dump() key round-trips onto the scrape
            # as ceph_tpu_offload_<service>_<counter> AND is documented,
            # and vice versa.  pending/services are levels (gauges);
            # launch and fallback totals stay counters; the per-service
            # launch-shape distributions render as real histogram
            # families the linter's bucket checks already validated.
            for key in offload_keys:
                fam = f"ceph_tpu_offload_{_sanitize(key)}"
                assert fam in families, f"{fam} missing from scrape"
                assert documented(fam), f"{fam} not documented"
            live_offload = {_sanitize(k) for k in offload_perf_dump()}
            for fam in families:
                if fam.startswith("ceph_tpu_offload_"):
                    key = fam.removeprefix("ceph_tpu_offload_")
                    assert key in live_offload, (
                        f"scraped {fam} has no offload_perf_dump() "
                        "source — update the exporter or the docs"
                    )
            assert families["ceph_tpu_offload_services"]["type"] == "gauge"
            for svc in ("encode", "decode", "verify", "compress", "csum"):
                assert (
                    families[f"ceph_tpu_offload_{svc}_pending"]["type"]
                    == "gauge"
                ), svc
            for fam in (
                "ceph_tpu_offload_csum_launches",
                "ceph_tpu_offload_csum_host_fallbacks",
                "ceph_tpu_offload_compress_launches",
                "ceph_tpu_offload_compress_host_fallbacks",
            ):
                assert families[fam]["type"] == "counter", fam
            assert (
                families["ceph_tpu_offload_csum_stripes_per_launch"][
                    "type"] == "histogram"
            )
            assert (
                families["ceph_tpu_offload_compress_launch_bytes"][
                    "type"] == "histogram"
            )

            # ISSUE 16 cross-lint: the clog module subscribes to the
            # committed log stream and polls the health-event history —
            # every family it exports reaches the scrape with its
            # declared typing AND the docs index, carrying real samples
            # (the planted clog_error + the pool-create audit line),
            # and vice versa: every scraped clog/health-event family
            # maps back to the module.
            def clog_reported():
                text = prom.scrape()
                return (
                    'ceph_tpu_clog_messages_total{channel="cluster",'
                    'severity="error"}' in text
                    and 'channel="audit"' in text
                )

            await wait_until(
                clog_reported, 8.0, "clog families carry samples"
            )
            families = lint_exposition(prom.scrape())
            clog_fams = {
                name: ftype
                for name, ftype, _h, _r in clog_mod.prometheus_metrics()
            }
            for fam, ftype in clog_fams.items():
                assert fam in families, f"{fam} missing from scrape"
                assert families[fam]["type"] == ftype, (
                    f"{fam}: scrape type {families[fam]['type']} != "
                    f"module type {ftype}"
                )
                assert documented(fam), f"{fam} not documented"
            # traffic totals are counters; the mute state is a gauge —
            # a counter-typed mute would corrupt alerting expressions
            assert clog_fams["ceph_tpu_clog_messages_total"] == "counter"
            assert clog_fams["ceph_tpu_health_events_total"] == "counter"
            assert clog_fams["ceph_tpu_health_muted"] == "gauge"
            rows = families["ceph_tpu_clog_messages_total"]["samples"]
            assert rows
            for _n, labels, v in rows:
                assert labels.get("channel") in ("cluster", "audit"), labels
                assert labels.get("severity") in (
                    "debug", "info", "warn", "error",
                ), labels
                assert v > 0, (labels, v)
            assert any(
                l.get("channel") == "cluster"
                and l.get("severity") == "error" and v >= 1
                for _n, l, v in rows
            ), rows
            assert any(
                l.get("channel") == "audit" and v >= 1 for _n, l, v in rows
            ), rows
            assert families["ceph_tpu_health_events_total"]["samples"]
            for fam in families:
                if fam.startswith("ceph_tpu_clog_") or fam in (
                    "ceph_tpu_health_events_total",
                    "ceph_tpu_health_muted",
                ):
                    assert fam in clog_fams, (
                        f"scraped {fam} has no clog module "
                        "prometheus_metrics() source"
                    )

            # direction 2 (vice versa): every documented metric exists
            # in the scrape, and every scraped ec_dispatch/progress
            # family maps back to a perf-dump key / module gauge
            for token in sorted(docs_exact):
                assert any(
                    f == token or f.startswith(token) for f in families
                ), f"documented {token} absent from scrape"
            for token in sorted(docs_prefix):
                assert any(f.startswith(token) for f in families), (
                    f"documented prefix {token}* matches nothing in scrape"
                )
            sanitized_keys = {_sanitize(k) for k in dispatch_keys}
            sanitized_sched = {_sanitize(k) for k in sched_keys}
            for fam in families:
                if fam.startswith("ceph_tpu_ec_dispatch_"):
                    key = fam.removeprefix("ceph_tpu_ec_dispatch_")
                    assert key in sanitized_keys, (
                        f"scraped {fam} has no ops/dispatch.perf_dump() "
                        "source — update the exporter or the docs"
                    )
                if fam.startswith("ceph_tpu_ec_sched_"):
                    key = fam.removeprefix("ceph_tpu_ec_sched_")
                    assert key in sanitized_sched, (
                        f"scraped {fam} has no launch_scheduler "
                        "perf_dump() source — update the exporter or "
                        "the docs"
                    )
                if fam.startswith("ceph_tpu_progress_"):
                    assert documented(fam), f"scraped {fam} undocumented"
                # scraped attribution families map back to the iostat
                # module's export list (the df pool gauges predate the
                # module and keep their own families)
                if fam.startswith("ceph_tpu_top_client_") or (
                    fam.startswith("ceph_tpu_pool_")
                    and fam not in (
                        "ceph_tpu_pool_stored_bytes",
                        "ceph_tpu_pool_objects",
                        "ceph_tpu_pool_used_raw_bytes",
                    )
                ):
                    assert fam in iostat_fams, (
                        f"scraped {fam} has no iostat "
                        "prometheus_metrics() source"
                    )

            await client.shutdown()
            await mgr.stop()
            await stop_cluster(mons, osds)

        asyncio.run(run())


class TestAuditDiscipline:
    """ISSUE 16 satellite: state-changing admin-socket commands are
    enumerable as mutating and actually land on the committed audit
    channel — the timeline must record every operator action."""

    def test_mutating_asok_commands_audit_to_committed_log(self):
        async def run():
            import os
            import tempfile

            from ceph_tpu.common.admin_socket import admin_command

            from test_cluster import start_cluster, stop_cluster, wait_until

            monmap, mons, osds = await start_cluster(1, 1)
            tmp = tempfile.mkdtemp(prefix="lint-asok-")
            path = os.path.join(tmp, "osd.0.asok")
            osds[0].conf.set("admin_socket", path)
            await osds[0]._start_admin_socket()
            sock = osds[0].admin_socket
            assert sock is not None

            # the state-changing hooks are registered mutating; the
            # read-only introspection surfaces are not
            muts = sock.mutating_prefixes()
            assert "injectargs" in muts, muts
            assert "mark_unfound_lost" in muts, muts
            for ro in ("help", "perf dump", "config show",
                       "dump_ops_in_flight", "dump_historic_ops"):
                assert ro not in muts, f"{ro} must not be mutating"
            # ...and the audit sink is wired (a mutating command with no
            # audit_cb would change state silently)
            assert sock.audit_cb is not None

            # drive a real mutating command over the socket (the sync
            # client runs in a thread so the server coroutine can serve
            # it) and watch the audit entry reach the COMMITTED mon log
            result = await asyncio.to_thread(
                admin_command, path, "injectargs", clear=True
            )
            assert "armed" in result, result
            await wait_until(
                lambda: any(
                    e["channel"] == "audit"
                    and "injectargs" in e["msg"]
                    and e["who"] == "osd.0"
                    for e in mons[0].logmon.entries
                ),
                5.0,
                "asok audit entry committed",
            )
            # a read-only command leaves no audit trace
            before = sum(
                1 for e in mons[0].logmon.entries
                if e["channel"] == "audit"
            )
            dump = await asyncio.to_thread(admin_command, path, "perf dump")
            # the layer-section table rides the asok dump only: no
            # ceph_tpu_* scrape family is generated from it
            assert all(
                set(v) == {"calls", "total_ns", "self_ns", "bytes"}
                for v in dump["layers"].values()
            ), dump["layers"]
            await asyncio.sleep(0.2)
            after = sum(
                1 for e in mons[0].logmon.entries
                if e["channel"] == "audit"
            )
            assert after == before, "read-only asok command was audited"

            await stop_cluster(mons, osds)

        asyncio.run(run())
