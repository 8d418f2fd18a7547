"""CPU tests of the per-layer metrics read from the program's layer
sections (perfbench/layers.py, perfbench/metrics/*_ms_per_op.py,
osd_queue_wait_ms.py, loop_sections_pct.py).

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q
"""

from __future__ import annotations

import threading

import pytest

from perfbench import harness
from perfbench.run import per_layer_reader
from perfbench.tests.test_harness import BENCH, tiny
from perfbench.traffic import OpRecord

LAYER_METRICS = ["client_ms_per_op", "msgr_ms_per_op", "osd_ec_ms_per_op",
                 "store_ms_per_op", "crc32c_ms_per_op", "device_wait_ms_per_op",
                 "osd_queue_wait_ms", "loop_sections_pct"]
MS = 1_000_000


def test_every_layer_metric_is_declared_for_every_cell():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    for name in LAYER_METRICS:
        m = declared[name]
        assert "workloads" not in m and m["moves"] == "throughput_MBps"
        assert m["source"] == "program_counter"


def _run(ops: int) -> harness.Run:
    run = harness.Run(cell=None, seed=1, seconds=4.0)
    run.window = (10.5, 14.5)  # covers the whole seconds 11, 12 and 13
    run.ops = [OpRecord(0, 11.0, 11.0 + 2.9 * i / ops, 4096, True, True)
               for i in range(ops)]
    run.ops.append(OpRecord(0, 10.6, 10.7, 4096, True, True))  # outside
    return run


def _rec(calls, total, own=None):
    return {"calls": calls, "total_ns": total,
            "self_ns": total if own is None else own, "bytes": 0}


def test_readers_by_hand(monkeypatch):
    from ceph_tpu.common import tracer

    loop_id = threading.main_thread().ident
    loop = {"client.op": _rec(10, 10 * MS), "client.crc32c": _rec(10, 5 * MS),
            "msgr.encode": _rec(10, 40 * MS, 30 * MS), "msgr.crc32c": _rec(10, 10 * MS),
            "osd.op": _rec(10, 60 * MS, 20 * MS), "ec.write": _rec(10, 40 * MS, 20 * MS),
            "store.txn": _rec(10, 20 * MS),
            "offload.settle_wait": _rec(5, 30 * MS, 10 * MS),
            "offload.dispatch_wait": _rec(5, 10 * MS), "offload.submit": _rec(5, 2 * MS)}
    every = {**loop, "osd.queue_wait": _rec(4, 8 * MS, 0), "device.block": _rec(5, 20 * MS)}

    def window(lo, hi, thread_id=None):
        assert (lo, hi) == (10.5, 14.5)
        return (loop if thread_id == loop_id else every), 11.0, 14.0

    monkeypatch.setattr(tracer, "layer_window", window)
    run = _run(10)
    got = {name: per_layer_reader(name)(run) for name in LAYER_METRICS}
    assert got == pytest.approx({
        "client_ms_per_op": 1.0, "msgr_ms_per_op": 3.0, "osd_ec_ms_per_op": 4.0,
        "store_ms_per_op": 2.0, "crc32c_ms_per_op": 1.5,
        "device_wait_ms_per_op": 4.0, "osd_queue_wait_ms": 2.0,
        # self times of every loop section over the 3 covered seconds
        "loop_sections_pct": 100 * 0.137 / 3,
    })


def test_readers_give_none_without_layer_sections(monkeypatch):
    """The parent program has no layer sections: every reader returns
    None and raises nothing."""
    from ceph_tpu.common import tracer

    monkeypatch.delattr(tracer, "layer_window")
    for name in LAYER_METRICS:
        assert per_layer_reader(name)(_run(10)) is None, name


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_readers_on_a_cpu_rehearsal(name):
    run, _ = harness.run_cell(tiny(name), 2**31 + 11, 2.5, False, require_tpu=False)
    got = {m: per_layer_reader(m)(run) for m in LAYER_METRICS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert 0 < got["loop_sections_pct"] <= 100
    assert got["msgr_ms_per_op"] > 0 and got["osd_ec_ms_per_op"] > 0
