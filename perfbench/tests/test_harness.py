"""CPU tests of the benchmark harness (no chip).

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q

Every cell resolves to its files; each cell's traffic runs a tiny
rehearsal through the in-process cluster and its comparison passes; the
comparison, the work counts and the trace reduction are checked against
numbers worked out by hand; a configuration's own profile keys and
shard reference reach the pool and the check; the run refuses to report
without a TPU.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from perfbench import devtrace, harness, reference, verify, work
from perfbench.tests import cauchy_reference
from perfbench.traffic import OpRecord

ROOT = str(harness.ROOT)
BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def tiny(name: str, bench: dict = BENCH) -> harness.Cell:
    """The cell at a size the CPU runs in seconds: the same code path,
    pool geometry (k, m, stripe unit, OSD count) and traffic shape."""
    cell = harness.load_cell(name, bench)
    cell.cfg["pg_num"] = 4
    if cell.cfg["api"] == "rbd":
        cell.cfg["image_size"] = 8 << 20
        cell.cfg["image_order"] = 20
    tr = cell.traffic
    tr["size"] = min(tr["size"], 64 << 10)
    tr.update(objects=16, in_flight=4, warmup_ops=8, check_objects=4)
    if "align" in tr:
        tr["align"] = min(tr["align"], tr["size"])
    return cell


def test_benchmark_json_names_and_files():
    assert BENCH["paths"] == ["perfbench"]
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    assert "setup_s" in metric_names
    for c in BENCH["configs"]:
        assert NAME.match(c["name"]) and os.path.isfile(os.path.join(ROOT, c["file"]))
        cfg = harness.load_json(harness.ROOT / c["file"])
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(ROOT, "perfbench", "traffic", f"{w['traffic']}.json"))
    for m in BENCH["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "perfbench", "metrics", f"{m['name']}.py"))
        assert m["moves"] in [e["name"] for e in BENCH["end_to_end"]]
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves(name):
    cell = harness.load_cell(name, BENCH)
    assert cell.ref.chunk_count(cell.cfg) <= cell.cfg["osds"]
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.per_layer


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearses_on_cpu(name):
    run, devs = harness.run_cell(tiny(name), 2**31 + 7, 2.0, False, require_tpu=False)
    assert verify.passed(run.compared), run.compared
    assert run.compared["objects_checked"]["value"] >= 1
    assert len(run.ops) > 0 and all(o.ok and o.exact for o in run.ops)
    assert run.counters["all_launches"] >= 1


def _equals_native(rows_of, profile: dict) -> None:
    from ceph_tpu.codec import registry

    rng = np.random.default_rng(5)
    for k, m in ((8, 3), (4, 2)):
        native = registry.instance().factory("native",
                                             {"k": str(k), "m": str(m), **profile})
        data = rng.integers(0, 256, (3, k, 4096), dtype=np.uint8)
        parity = reference.encode(data, rows_of(k, m))
        for s in range(3):
            chunks = {i: data[s, i].copy() for i in range(k)}
            out = np.zeros((m, 4096), np.uint8)
            chunks.update({k + j: out[j] for j in range(m)})
            native.encode_chunks(chunks)
            assert np.array_equal(out, parity[s])


def test_reference_equals_isa_l_model():
    """The reference is written from ISA-L's definitions; the repo's
    native plugin models ISA-L, so the two must agree byte for byte."""
    _equals_native(reference.vandermonde_rows, {})


def test_cauchy_reference_equals_isa_l_model():
    _equals_native(cauchy_reference.cauchy_rows, {"technique": "cauchy"})


def test_reference_modules_import_nothing_of_the_program():
    code = ("import sys; import perfbench.reference, perfbench.tests.cauchy_reference; "
            "print(sorted(m for m in sys.modules if m.split('.')[0] == 'ceph_tpu'))")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "[]"


def test_profile_and_work_of_the_configs():
    """Both configurations send the five profile keys they always sent,
    hold their shards to plain RS, and count the bytes counted by hand."""
    rs83 = harness.load_cell("objstore_rs83.write_4m", BENCH)
    rs42 = harness.load_cell("rbd_ec42.randwrite_4k", BENCH)
    assert rs83.ref is rs42.ref is reference
    assert harness.profile_args(rs83.cfg) == [
        "k=8", "m=3", "plugin=tpu", "crush-failure-domain=osd", "stripe_unit=4096"]
    assert harness.profile_args(rs42.cfg) == [
        "k=4", "m=2", "plugin=tpu", "crush-failure-domain=osd", "stripe_unit=4096"]
    acked = [OpRecord(0, 0.0, 1.0, 4 << 20, True)] * 3
    assert work.window_work(rs83, acked, {"decode_stripes": 0}) == {
        "encode_bytes": 3 * 5767168, "decode_bytes": None}
    degraded = harness.load_cell("objstore_rs83.degraded_read_4m", BENCH)
    assert work.window_work(degraded, acked, {"decode_stripes": 128}) == {
        "encode_bytes": None, "decode_bytes": 4718592}
    assert work.window_work(rs42, acked, {"decode_stripes": 0}) == {
        "encode_bytes": None, "decode_bytes": None}


def _bench_with(tmp_path, cfg: dict, traffic: str = "write_4m") -> dict:
    """BENCHMARK.json with one more configuration, written to tmp_path,
    and one cell of it."""
    path = tmp_path / f"{cfg['name']}.json"
    path.write_text(json.dumps(cfg))
    name = f"{cfg['name']}.{traffic}"
    return {**BENCH,
            "configs": BENCH["configs"] + [{"name": cfg["name"], "file": str(path)}],
            "workloads": BENCH["workloads"] + [
                {"name": name, "config": cfg["name"], "traffic": traffic, "chips": 1}]}


def _rs42_objects(**extra) -> dict:
    cfg = harness.load_json(harness.ROOT / "perfbench/configs/objstore_rs83.json")
    return {**cfg, "name": "cauchy42", "k": 4, "m": 2, "osds": 6, **extra}


@pytest.mark.parametrize("extra,says", [
    ({"profile": {"d": 5, "k": 4}}, "repeats ['k']"),
    ({"reference": "no_such_reference"}, "no_such_reference"),
    ({"reference": "work"}, "lacks ['chunk_count', 'stripe_width'"),
])
def test_bad_config_fails_at_load(tmp_path, extra, says):
    bench = _bench_with(tmp_path, _rs42_objects(**extra))
    with pytest.raises(ValueError) as e:
        harness.load_cell("cauchy42.write_4m", bench)
    assert "config 'cauchy42'" in str(e.value) and says in str(e.value)


def test_configuration_decides_the_code(tmp_path, monkeypatch):
    """An RS(4,2) pool with technique=cauchy and the Cauchy reference:
    the extra key reaches the mon's profile, the run is correct, and the
    stored shards are not the default Vandermonde reference's."""
    cfg = _rs42_objects(profile={"technique": "cauchy"},
                        reference="tests.cauchy_reference")
    cell = tiny("cauchy42.write_4m", _bench_with(tmp_path, cfg))
    assert cell.ref is cauchy_reference
    profiles, vandermonde = [], []
    start_cluster, shard_mismatches = harness.start_cluster, verify.shard_mismatches

    async def start_and_read_profile(cfg):
        up = await start_cluster(cfg)
        rv, rs, out = await up[1].mon_command(
            {"prefix": "osd erasure-code-profile get", "name": f"{cfg['name']}_profile"})
        assert rv == 0, rs
        profiles.append(json.loads(out))
        return up

    def against_both(ref, cfg, copies, payload):
        vandermonde.append(shard_mismatches(reference, cfg, copies, payload))
        return shard_mismatches(ref, cfg, copies, payload)

    monkeypatch.setattr(harness, "start_cluster", start_and_read_profile)
    monkeypatch.setattr(verify, "shard_mismatches", against_both)
    run, _ = harness.run_cell(cell, 2**31 + 13, 2.0, False, require_tpu=False)
    assert profiles[0]["technique"] == "cauchy" and profiles[0]["k"] == "4"
    assert verify.passed(run.compared), run.compared
    assert run.compared["objects_checked"]["value"] == len(vandermonde) >= 1
    # every object's two parity shards differ from the Vandermonde code's
    assert all(n >= 2 for n in vandermonde), vandermonde


def test_shard_comparison_fails_on_a_corrupted_shard():
    cfg = {"k": 8, "m": 3, "stripe_unit": 4096}
    payload = np.random.default_rng(1).integers(0, 256, 4 << 20, dtype=np.uint8).tobytes()
    shards = reference.object_shards(payload, cfg)
    copies = {s: [b] for s, b in enumerate(shards)}
    assert verify.shard_mismatches(reference, cfg, copies, payload) == 0
    bad = bytearray(shards[9])
    bad[12345] ^= 0x40
    assert verify.shard_mismatches(reference, cfg, {**copies, 9: [bytes(bad)]},
                                   payload) == 1
    assert verify.shard_mismatches(reference, cfg,
                                   {s: c for s, c in copies.items() if s != 2},
                                   payload) == 1


def test_work_counts_by_hand():
    rs83 = {"k": 8, "m": 3, "stripe_unit": 4096}
    rs42 = {"k": 4, "m": 2, "stripe_unit": 4096}
    # a 4 MiB object is 128 stripes of 32 KiB; each moves 11 chunks of 4 KiB
    assert work.stripes_per_object(reference, rs83, 4 << 20) == 128
    assert reference.encode_bytes(rs83, 128) == 128 * 11 * 4096 == 5767168
    assert reference.decode_bytes(rs83, 128, 1) == 128 * 9 * 4096 == 4718592
    # RS(4,2): a 4 MiB object is 256 stripes of 16 KiB, 6 chunks each
    assert work.stripes_per_object(reference, rs42, 4 << 20) == 256
    assert reference.encode_bytes(rs42, 256) == 256 * 6 * 4096 == 6291456
    assert reference.decode_bytes(rs42, 1, 1) == 5 * 4096


def test_trace_reduction_on_a_built_trace():
    ms = 1_000_000
    lines = {
        "XLA Ops": [("%reshape.2 = u8[128,8,4096] reshape(%data.1)", 10 * ms, 2 * ms),
                    ("%_gf_code_swar.1 = u8[128,3,4096] custom-call(%reshape.2)", 12 * ms, 3 * ms),
                    ("%copy.3 = u8[128,3,4096] copy(%x)", 14 * ms, 2 * ms),  # overlaps
                    ("%_gf_code_swar.1 = u8[128,3,4096] custom-call(%reshape.2)", 95 * ms, 10 * ms)],
        "XLA Modules": [("jit__gf_code_swar(1234)", 10 * ms, 6 * ms),
                        ("jit__gf_code_swar(1234)", 95 * ms, 10 * ms)],
    }
    samples = [(20 * ms, "msg/messenger.py:send"), (30 * ms, "msg/messenger.py:send"),
               (40 * ms, "utils/crc32c.py:crc32c")]
    t = devtrace.summarize([lines], 0, 100 * ms, samples)
    assert t.window_ns == 100 * ms
    # busy: [10, 16) and [95, 100) clipped at the window's end
    assert t.busy_ns == 11 * ms
    assert abs(t.idle_share - 0.89) < 1e-12
    assert t.ops_ns == {"reshape": 2 * ms, "_gf_code_swar": 8 * ms, "copy": 2 * ms}
    assert t.kernel_ns("gf_code") == 11 * ms
    # gaps: [0,10), [16,95); the longest first, named by the host's samples
    assert [round(s, 6) for _, s in t.gaps] == [0.079, 0.01]
    assert t.gaps[0][0].startswith("msg/messenger.py:send")
    assert t.gaps[1][0].startswith("no host sample")


def test_stable_names():
    assert devtrace.stable_name("jit__gf_code_swar(1068948282606563240)") == "jit__gf_code_swar"
    assert devtrace.stable_name("%copy.2 = u8[128,3,4096]{2,0,1} copy(u8[1] %b)") == "copy"
    assert devtrace.stable_name("fusion.12") == "fusion"


def _cpu_env(tmp_path) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax-cache")
    env.pop("PYTHONPATH", None)
    return env


def test_run_refuses_without_a_tpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_cpu_env(tmp_path), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_run_refuses_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and perfbench/ has no system
    under test: the run fails and prints no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_cpu_env(tmp_path), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert not any(line.startswith("{") and "correct" in line
                   for line in proc.stdout.splitlines())


def test_result_line_shape():
    import perfbench.run as R

    run, devs = harness.run_cell(tiny(CELLS[0]), 3, 1.0, False, require_tpu=False)
    line = R.result(run, devs, False)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert "setup_s" in line["metrics"]
    json.dumps(line)


@pytest.mark.parametrize("pick", ["cycle", "sequential", "uniform"])
def test_pickers_cover_the_working_set(pick):
    from perfbench.traffic import Generator

    cfg = {"api": "rados"}
    tr = {"size": 4096, "objects": 64, "pick": pick}
    gen = Generator(cfg, tr, 7, None)
    seen = [gen._next_target() for _ in range(2000)]
    assert set(seen) <= set(range(64))
    if pick in ("cycle", "sequential"):
        assert sorted(seen[:64]) == list(range(64))
    again = Generator(cfg, tr, 7, None)
    assert [again._next_target() for _ in range(2000)] == seen
