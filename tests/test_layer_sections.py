"""Layer sections (common/tracer.py `section`): the per-thread table of
calls, total and self time, its per-second windows, the crc32c caller
attribution, the profiler annotations, and the sections the served EC
path leaves behind on a small in-process cluster."""

from __future__ import annotations

import asyncio
import glob
import os
import threading
import time

import numpy as np
import pytest

from ceph_tpu.common import tracer
from ceph_tpu.common.tracer import layer_section, section, sectioned
from ceph_tpu.utils.crc32c import crc32c


def _delta(after: dict, before: dict) -> dict:
    out = {}
    for name, rec in after.items():
        prev = before.get(name, {})
        d = {k: v - prev.get(k, 0) for k, v in rec.items()}
        if d["calls"]:
            out[name] = d
    return out


def _spin(ns: int) -> None:
    end = time.perf_counter_ns() + ns
    while time.perf_counter_ns() < end:
        pass


def _mine(before: dict) -> dict:
    return _delta(tracer.layer_totals(threading.get_ident()), before)


def test_self_time_excludes_the_child():
    before = tracer.layer_totals(threading.get_ident())
    with section("t_outer.parent"):
        _spin(2_000_000)
        with section("t_inner.child"):
            _spin(5_000_000)
    got = _mine(before)
    parent, child = got["t_outer.parent"], got["t_inner.child"]
    assert parent["calls"] == child["calls"] == 1
    assert child["self_ns"] == child["total_ns"] >= 5_000_000
    assert parent["total_ns"] >= parent["self_ns"] + child["total_ns"]
    assert 2_000_000 <= parent["self_ns"] < parent["total_ns"] - 4_000_000


def test_worker_thread_sections_do_not_subtract_from_the_caller():
    before_all = tracer.layer_totals()
    before = tracer.layer_totals(threading.get_ident())

    def work():
        with section("t_dev.worker"):
            _spin(5_000_000)

    with section("t_wait.caller"):
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    caller = _mine(before)["t_wait.caller"]
    assert caller["self_ns"] == caller["total_ns"] >= 5_000_000
    assert "t_dev.worker" not in _mine(before)
    worker = _delta(tracer.layer_totals(), before_all)["t_dev.worker"]
    assert worker["calls"] == 1 and worker["total_ns"] >= 5_000_000


def test_crc32c_takes_the_enclosing_layer_and_counts_bytes():
    before = tracer.layer_totals(threading.get_ident())
    data = bytes(range(256)) * 16
    with section("t_store.txn"):
        digest = crc32c(data)
        crc32c(np.frombuffer(data, dtype=np.uint8))
    crc32c(b"abc")
    got = _mine(before)
    assert digest == crc32c(data) == crc32c(data, 0)
    assert got["t_store.crc32c"]["calls"] == 2
    assert got["t_store.crc32c"]["bytes"] == 2 * len(data)
    assert got["misc.crc32c"]["bytes"] == 3
    impls = [k for k in tracer.layer_totals() if k.startswith("crc32c.impl.")]
    assert len(impls) == 1, impls
    assert tracer.layer_totals()[impls[0]]["calls"] == 1


def test_layer_section_and_counter_entries():
    before = tracer.layer_totals(threading.get_ident())
    with layer_section("probe"):
        pass
    tracer.count_section("t_osd.queue_wait", 7_000)
    got = _mine(before)
    assert got["misc.probe"]["calls"] == 1
    assert got["t_osd.queue_wait"] == {"calls": 1, "total_ns": 7_000,
                                       "self_ns": 0, "bytes": 0}


def test_sectioned_refuses_a_coroutine():
    with pytest.raises(TypeError):
        @sectioned("t_osd.op")
        async def op():
            pass

    @sectioned("t_osd.sync")
    def sync(x):
        return x + 1

    before = tracer.layer_totals(threading.get_ident())
    assert sync(1) == 2
    assert _mine(before)["t_osd.sync"]["calls"] == 1


def test_window_counts_whole_seconds_only():
    """A section counts in the second it ended in; a window keeps the
    whole seconds inside it and says which span it covered."""
    me = threading.get_ident()
    while time.perf_counter() % 1 > 0.8:
        time.sleep(0.05)  # leave room before the next second begins
    sec = int(time.perf_counter())
    with section("t_win.one"):
        pass
    table, lo, hi = tracer.layer_window(sec, sec + 1, me)
    assert (lo, hi) == (sec, sec + 1)
    assert table["t_win.one"]["calls"] == 1
    # no whole second inside: nothing covered
    assert tracer.layer_window(sec + 0.5, sec + 1.5, me) == ({}, sec + 1.0, sec + 1.0)


def test_profile_sections_writes_host_events_on_the_profiler_clock(tmp_path):
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    tracer.profile_sections(True)
    try:
        with section("t_msgr.encode"):
            with layer_section("crc32c"):
                pass
    finally:
        tracer.profile_sections(False)
        jax.profiler.stop_trace()
    with section("t_msgr.untraced"):
        pass
    path = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*", "*.xplane.pb"))[-1]
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"t_msgr.encode", "t_msgr.crc32c"} <= names
    assert "t_msgr.untraced" not in names


def test_served_ec_write_and_degraded_read_leave_every_layer():
    """One 4 MiB write and one degraded read on an RS(4,2) pool of six
    OSDs: the client, messenger, OSD, EC, store, offload and device
    layers and the OSD queue wait all show in the table."""
    from ceph_tpu.client import Rados
    from ceph_tpu.tools.vstart import DevCluster

    async def run() -> dict:
        before = tracer.layer_totals()
        cluster = DevCluster(n_mons=1, n_osds=6, with_mgr=False,
                             conf_overrides={"osd_objectstore": "bluestore",
                                             "osd_data": ""})
        await cluster.start()
        client = Rados(cluster.monmap, name="client.sections")
        try:
            await client.connect()
            rv, rs, _ = await client.mon_command({
                "prefix": "osd erasure-code-profile set", "name": "rs42",
                "profile": ["k=4", "m=2", "plugin=tpu",
                            "crush-failure-domain=osd"],
            })
            assert rv == 0, rs
            await client.pool_create("ecpool", "erasure", profile="rs42", pg_num=1)
            io = await client.open_ioctx("ecpool")
            payload = np.random.default_rng(3).integers(
                0, 256, 4 << 20, dtype=np.uint8).tobytes()
            await io.write_full("obj", payload)
            pool = client.objecter.osdmap.pools[io.pool_id]
            _, ps = client.objecter.osdmap.object_to_pg(io.pool_id, "obj")
            _, _, acting, primary = client.objecter.osdmap.pg_to_up_acting_osds(
                pool.id, ps)
            victim = next(o for o in acting if o != primary)
            await cluster.osds[victim].stop()
            mon = cluster.mons[0]
            deadline = time.monotonic() + 60
            while mon.osdmon.osdmap.is_up(victim):
                assert time.monotonic() < deadline, "victim never marked down"
                await asyncio.sleep(0.05)
            got = await io.read("obj")
            assert got == payload
        finally:
            await client.shutdown()
            await cluster.stop()
        return _delta(tracer.layer_totals(), before)

    got = asyncio.run(run())
    for layer in ("client", "msgr", "osd", "ec", "store", "offload", "device"):
        assert any(k.startswith(layer + ".") and v["total_ns"] > 0
                   for k, v in got.items()), (layer, sorted(got))
    assert got["osd.queue_wait"]["calls"] >= 2
    assert got["store.crc32c"]["bytes"] > 0
    assert got["msgr.crc32c"]["bytes"] > 4 << 20
