"""Round-over-round trajectory gating (ISSUE 14, tools/perf_compare.py):
a bench-round corpus stays schema-valid (pure parsing, no device), and
the comparator judges a round against the trailing same-platform best —
a recorded TPU number cannot silently reset.  The corpus here is a small
synthetic one written per test, shaped like the driver's round records."""

import json
import os
import subprocess
import sys

import pytest

from ceph_tpu.tools.perf_compare import (
    check_corpus,
    compare,
    compare_round,
    default_rounds_dir,
    load_rounds,
    metric_slice,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# round -> (platform, encode GB/s): CPU rounds around one TPU round
_CORPUS = {1: ("cpu", 0.069), 2: ("cpu", 1.8), 3: ("tpu", 23.374),
           4: ("cpu", 0.048), 5: ("cpu", 0.036)}


@pytest.fixture
def corpus(tmp_path):
    for n, (platform, gbps) in _CORPUS.items():
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps({
            "n": n, "rc": 0,
            "parsed": {"metric": "rs_8_3_encode_GBps_per_chip",
                       "value": gbps, "unit": "GB/s",
                       "platform": platform},
        }))
    return str(tmp_path)


class TestCorpus:
    """The schema gate: a malformed bench JSON or a silent schema drift
    in a round corpus is reported."""

    def test_default_rounds_dir_is_the_repo_root(self):
        assert default_rounds_dir() == REPO

    def test_check_passes_over_rounds(self, corpus):
        problems = check_corpus(corpus)
        assert problems == [], problems

    def test_rounds_load_with_known_trajectory(self, corpus):
        rounds = load_rounds(corpus)
        assert [r["round"] for r in rounds] == sorted(_CORPUS)
        # the TPU measurement is the story perf_compare exists to
        # defend: it must parse out of the corpus
        by_round = {r["round"]: r for r in rounds}
        assert by_round[3]["platform"] == "tpu"
        assert by_round[3]["metrics"][
            "rs_8_3_encode_GBps_per_chip"] > 20.0

    def test_cli_check_exits_zero(self, corpus):
        proc = subprocess.run(
            [sys.executable, "-m", "ceph_tpu.tools.perf_compare",
             "--check", "--rounds-dir", corpus],
            capture_output=True, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stdout.decode()
        payload = json.loads(proc.stdout.decode())
        assert payload["ok"] is True
        assert payload["checked"] == len(_CORPUS)
        assert payload["trajectory"]

    def test_check_fails_on_malformed_round(self, tmp_path):
        (tmp_path / "BENCH_r01.json").write_text("{not json")
        problems = check_corpus(str(tmp_path))
        assert problems and "not JSON" in problems[0]

    def test_check_fails_on_schema_drift(self, tmp_path):
        # rc=0 round whose parsed slice lost the metric contract
        (tmp_path / "BENCH_r01.json").write_text(json.dumps({
            "n": 1, "rc": 0, "parsed": {"speed": 3},
        }))
        problems = check_corpus(str(tmp_path))
        assert any("metric" in p for p in problems), problems
        # non-finite value
        (tmp_path / "BENCH_r01.json").write_text(json.dumps({
            "n": 1, "rc": 0,
            "parsed": {"metric": "m", "value": None, "unit": "GB/s"},
        }))
        problems = check_corpus(str(tmp_path))
        assert any("finite" in p for p in problems), problems

    def test_empty_dir_is_a_problem(self, tmp_path):
        assert check_corpus(str(tmp_path))


class TestMetricSlice:
    def test_legacy_single_metric_shape(self):
        assert metric_slice({
            "metric": "rs_8_3_encode_GBps_per_chip", "value": 0.069,
            "unit": "GB/s", "platform": "cpu",
        }) == {"rs_8_3_encode_GBps_per_chip": 0.069}

    def test_rich_shape_flattens_every_known_metric(self):
        parsed = {
            "metric": "rs_8_3_encode_GBps_per_chip", "value": 2.5,
            "platform": "cpu",
            "decode": {"metric": "rs_8_3_decode_GBps_per_chip",
                       "value": 4.2},
            "verify": {"metric": "rs_8_3_verify_GBps_per_chip",
                       "value": 3.0},
            "pipelined": {
                "metric": "rs_8_3_encode_GBps_per_chip_pipelined",
                "value": 17.17,
            },
            "multichip": {
                "metric": "rs_8_3_encode_GBps_aggregate", "value": 9.0,
                "decode": {"metric": "rs_8_3_decode_GBps_aggregate",
                           "value": 8.0},
            },
            "chaos": {"chaos_p99_ms": 120.5, "recovery_occupancy": 2.0,
                      "rebuild_seconds": 6.25, "storm_p99_ms": 240.0},
        }
        got = metric_slice(parsed)
        assert got == {
            "rs_8_3_encode_GBps_per_chip": 2.5,
            "rs_8_3_decode_GBps_per_chip": 4.2,
            "rs_8_3_verify_GBps_per_chip": 3.0,
            "rs_8_3_encode_GBps_per_chip_pipelined": 17.17,
            "rs_8_3_encode_GBps_aggregate": 9.0,
            "rs_8_3_decode_GBps_aggregate": 8.0,
            "chaos_p99_ms": 120.5,
            "recovery_occupancy": 2.0,
            "chaos_rebuild_seconds": 6.25,
            "chaos_storm_p99_ms": 240.0,
        }

    def test_mislabeled_and_nonfinite_values_ignored(self):
        assert metric_slice({
            "metric": "something_else", "value": 1.0,
            "decode": {"metric": "rs_8_3_decode_GBps_per_chip",
                       "value": float("inf")},
        }) == {}
        assert metric_slice(None) == {}


def _rounds():
    """A synthetic trailing corpus mirroring the real trajectory shape:
    CPU rounds, one TPU round at 23.374, CPU fallbacks after."""
    return [
        {"round": 2, "rc": 0, "platform": "cpu",
         "metrics": {"rs_8_3_encode_GBps_per_chip": 0.069}},
        {"round": 3, "rc": 0, "platform": "tpu",
         "metrics": {"rs_8_3_encode_GBps_per_chip": 23.374}},
        {"round": 4, "rc": 0, "platform": "cpu",
         "metrics": {"rs_8_3_encode_GBps_per_chip": 0.048,
                     "chaos_p99_ms": 100.0}},
    ]


class TestCompare:
    def test_next_tpu_round_judged_against_23_4(self):
        """THE acceptance story: a TPU round at 10 GB/s is flagged
        against round 3's 23.374, not silently accepted because the
        recent CPU rounds were slower."""
        out = compare(
            {"metric": "rs_8_3_encode_GBps_per_chip", "value": 10.0,
             "platform": "tpu"},
            _rounds(),
        )
        base = out["baselines"]["rs_8_3_encode_GBps_per_chip"]
        assert base == {"value": 23.374, "round": 3, "platform": "tpu"}
        assert out["count"] == 1
        flag = out["flagged"][0]
        assert flag["metric"] == "rs_8_3_encode_GBps_per_chip"
        assert flag["baseline_round"] == 3
        assert flag["vs_baseline"] < 0.5

    def test_healthy_tpu_round_passes(self):
        out = compare(
            {"metric": "rs_8_3_encode_GBps_per_chip", "value": 25.0,
             "platform": "tpu"},
            _rounds(),
        )
        assert out["flagged"] == []

    def test_cpu_fallback_not_judged_against_tpu(self):
        """Platform scoping: a CPU fallback round compares against the
        CPU best (0.069), never the TPU 23.374 — a fallback is a
        fallback, not a 99.7% regression."""
        out = compare(
            {"metric": "rs_8_3_encode_GBps_per_chip", "value": 0.06,
             "platform": "cpu"},
            _rounds(),
        )
        base = out["baselines"]["rs_8_3_encode_GBps_per_chip"]
        assert base["value"] == 0.069 and base["platform"] == "cpu"
        assert out["flagged"] == []  # 0.06 is within 0.8x of 0.069

    def test_lower_is_better_metric_flags_inflation(self):
        out = compare(
            {"platform": "cpu", "chaos": {"chaos_p99_ms": 500.0}},
            _rounds(),
        )
        assert out["count"] == 1
        assert out["flagged"][0]["metric"] == "chaos_p99_ms"
        assert out["flagged"][0]["direction"] == "lower"
        out = compare(
            {"platform": "cpu", "chaos": {"chaos_p99_ms": 90.0}},
            _rounds(),
        )
        assert out["flagged"] == []

    def test_storm_rebuild_keys_gate_lower_is_better(self):
        """ISSUE 15: rebuild time and under-storm p99 fold from the
        chaos JSON and flag when a round slows the whole-OSD rebuild
        (or lets it eat client latency) past the ratio."""
        rounds = _rounds() + [{
            "round": 5, "rc": 0, "platform": "cpu",
            "metrics": {"chaos_rebuild_seconds": 5.0,
                        "chaos_storm_p99_ms": 200.0},
        }]
        out = compare(
            {"platform": "cpu",
             "chaos": {"rebuild_seconds": 9.0, "storm_p99_ms": 190.0}},
            rounds,
        )
        flagged = {f["metric"] for f in out["flagged"]}
        assert flagged == {"chaos_rebuild_seconds"}, out["flagged"]
        out = compare(
            {"platform": "cpu",
             "chaos": {"rebuild_seconds": 5.5, "storm_p99_ms": 190.0}},
            rounds,
        )
        assert out["flagged"] == []
        # baselines carry the best (lowest) committed values
        assert out["baselines"]["chaos_rebuild_seconds"]["value"] == 5.0
        assert out["baselines"]["chaos_storm_p99_ms"]["value"] == 200.0

    def test_no_baseline_no_flag(self):
        """First round / new metric / platform switch: nothing to judge
        against, by design."""
        out = compare(
            {"metric": "rs_8_3_encode_GBps_per_chip", "value": 0.001,
             "platform": "gpu"},
            _rounds(),
        )
        # platform-scoped metrics have no gpu history; the unscoped
        # chaos baseline may exist but the current round carries no
        # chaos slice — nothing flags either way
        assert "rs_8_3_encode_GBps_per_chip" not in out["baselines"]
        assert out["flagged"] == []

    def test_compare_round_against_corpus(self, corpus):
        """The one-call fold over a round corpus on disk: a collapsed
        TPU round flags against round 3."""
        out = compare_round(
            {"metric": "rs_8_3_encode_GBps_per_chip", "value": 1.0,
             "platform": "tpu"},
            corpus,
        )
        assert out["rounds_compared"]
        assert any(
            f["metric"] == "rs_8_3_encode_GBps_per_chip"
            and f["baseline"] > 20.0
            for f in out["flagged"]
        ), out
