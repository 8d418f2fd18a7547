"""On-chip smoke test of the RS(8,3) EC data path, in one process.

    python chip_smoke.py             # one chip: device, codec, cluster
    python chip_smoke.py --chips 4   # four chips: the sharded codec path only

Phases, each printing one JSON line (name, seconds, compile seconds, what
it checked):

- device: `jax.devices()` must report a TPU; there is no CPU fallback.
- codec: the `tpu` plugin's RS(8,3) (`-P k=8 -P m=3 -S 1048576`) encodes
  256 stripes of 1 MiB resident in HBM through `encode_array`, then
  decodes the erasures {0, 5, 9}; every byte is compared with the
  `native` plugin (native/ec_native.cc, the ISA-L model).
- cluster: an 11-OSD in-process cluster with an RS(8,3) pool of 32 PGs
  takes 64 objects of 4 MiB through librados, reads them back, serves
  them degraded with one OSD down, recovers the restarted OSD and
  deep-scrubs every PG clean.
- multichip (`--chips 4` only): the same codec batch sharded over every
  chip by the default dispatch policy, compared with a one-device run
  and with `native`, and checked to span all four devices.

After every data phase the device must have done the work: the launch
counters grew, no launch fell back to the host oracle, no offload
service counted a host fallback, the device guard never went DEGRADED,
and the codec's coder holds a compiled (non-interpret) Pallas plan.

Any failure exits non-zero before the last line, which is exactly
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

import numpy as np

K, M = 8, 3
ERASURES = [0, 5, 9]
SEED = 0
STRIPE_UNIT = 4096  # osd_pool_erasure_code_stripe_unit default


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> str:
    if not cond:
        raise SmokeFailure(what)
    return what


def progress(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def report(phase: str, t0: float, clock, c0: float, checked: list, **info):
    line = {
        "phase": phase,
        "seconds": time.perf_counter() - t0,
        "compile_seconds": clock.read() - c0,
        "checked": checked,
    }
    line.update(info)
    print(json.dumps(line), flush=True)


# -- references ------------------------------------------------------------


def native_encode(native, data: np.ndarray) -> np.ndarray:
    """(S, k, L) -> (S, m, L) parity through the native plugin's C kernel."""
    s_count, k, length = data.shape
    out = np.empty((s_count, M, length), np.uint8)
    for s in range(s_count):
        chunks = {i: data[s, i] for i in range(k)}
        chunks.update({k + j: out[s, j] for j in range(M)})
        native.encode_chunks(chunks)
    return out


def native_decode(native, full: np.ndarray, erasures: list[int]) -> np.ndarray:
    """(S, k+m, L) codewords -> (S, len(erasures), L) rebuilt by native."""
    s_count, n, length = full.shape
    out = np.empty((s_count, len(erasures), length), np.uint8)
    for s in range(s_count):
        avail = {i: full[s, i] for i in range(n) if i not in erasures}
        decoded = dict(avail)
        decoded.update({e: out[s, j] for j, e in enumerate(erasures)})
        native.decode_chunks(set(erasures), avail, decoded)
    return out


# -- device-did-the-work checks ------------------------------------------------


def launch_totals() -> dict[str, int]:
    from ceph_tpu.ops import dispatch

    return {
        "launches": dispatch.LAUNCHES.snapshot()["launches"],
        "decode": dispatch.DECODE_LAUNCHES.snapshot()["launches"],
        "verify": dispatch.VERIFY_LAUNCHES.snapshot()["launches"],
        "sharded": dispatch.SHARDED_LAUNCHES.snapshot()["launches"],
    }


def device_did_the_work(
    ec, before: dict[str, int], chunk_lens, require_pallas: bool = True
) -> list[str]:
    from ceph_tpu.codec.matrix_codec import PLAN_CACHE
    from ceph_tpu.ops import dispatch
    from ceph_tpu.ops.guard import device_guard
    from ceph_tpu.ops.offload_runtime import offload_services, service_aggregator

    after = launch_totals()
    checked = [
        check(
            after["launches"] > before["launches"],
            f"launch counter grew by {after['launches'] - before['launches']}",
        ),
        check(
            dispatch.FALLBACK_LAUNCHES.snapshot()["launches"] == 0,
            "0 host-fallback launches",
        ),
    ]
    for name in offload_services():
        n = service_aggregator(name).perf.get("host_fallbacks")
        check(n == 0, f"offload service {name}: host_fallbacks == 0 (got {n})")
    checked.append(f"0 host_fallbacks in {len(offload_services())} offload services")
    guard = device_guard()
    checked.append(
        check(
            not guard.degraded and guard.degraded_total == 0,
            f"device guard never DEGRADED ({guard.snapshot()})",
        )
    )
    if require_pallas:
        rows = ec.distribution_matrix()[ec.k :]
        plan = PLAN_CACHE.encode_coder(rows).plan
        dplan = PLAN_CACHE.decode_coder(
            ec.distribution_matrix(), ERASURES, ec.k
        )[0].plan
        for length in chunk_lens:
            check(length % 128 == 0, f"chunk length {length} takes the kernel")
        checked.append(
            check(
                plan is not None
                and not plan.interpret
                and dplan is not None
                and not dplan.interpret,
                f"encode/decode coders hold compiled Pallas plans "
                f"(chunk lengths {list(chunk_lens)})",
            )
        )
    return checked


# -- phases -----------------------------------------------------------------


def phase_device(want_chips: int):
    import jax

    devs = jax.devices()
    dev = devs[0]
    check(dev.platform == "tpu", f"platform is tpu (got {dev.platform!r})")
    check(
        len(devs) >= want_chips,
        f"at least {want_chips} devices (got {len(devs)})",
    )
    return dev, len(devs)


def codec_batch(stripes: int, chunk: int) -> np.ndarray:
    rng = np.random.default_rng(SEED)
    return rng.integers(0, 256, (stripes, K, chunk), dtype=np.uint8)


def phase_codec(stripes: int, chunk: int, clock, require_pallas=True):
    """Encode + decode one resident batch, bytes against `native`."""
    import jax

    from ceph_tpu.codec import registry

    t0, c0 = time.perf_counter(), clock.read()
    before = launch_totals()
    ec = registry.instance().factory("tpu", {"k": str(K), "m": str(M)})
    native = registry.instance().factory("native", {"k": str(K), "m": str(M)})
    host = codec_batch(stripes, chunk)
    data = jax.device_put(host)
    t_first = time.perf_counter()
    parity = jax.block_until_ready(ec.encode_array(data))
    first_s = time.perf_counter() - t_first
    platforms = {d.platform for d in parity.devices()}
    want = native_encode(native, host)
    checked = [
        check(
            np.array_equal(np.asarray(parity), want),
            f"encode of {stripes} x {K} x {chunk} equals native "
            f"({want.nbytes} parity bytes)",
        ),
        check(platforms == {jax.devices()[0].platform},
              f"parity resident on {sorted(platforms)}"),
    ]
    # informational only: a few back-to-back launches of the warm kernel
    reps = 5
    t1 = time.perf_counter()
    for _ in range(reps):
        parity = ec.encode_array(data)
    jax.block_until_ready(parity)
    gbps = reps * host.nbytes / (time.perf_counter() - t1) / 1e9

    full = np.concatenate([host, want], axis=1)
    idx = ec.decode_index(ERASURES)
    survivors = jax.device_put(np.ascontiguousarray(full[:, idx, :]))
    rebuilt = np.asarray(ec.decode_array(ERASURES, survivors))
    checked.append(
        check(
            np.array_equal(rebuilt, full[:, ERASURES, :])
            and np.array_equal(rebuilt, native_decode(native, full, ERASURES)),
            f"decode of erasures {ERASURES} from survivors {list(idx)} equals "
            f"the originals and native",
        )
    )
    del data, survivors, parity
    checked += device_did_the_work(ec, before, [chunk], require_pallas)
    report(
        "codec", t0, clock, c0, checked,
        first_encode_seconds=first_s,
        informational_encode_GBps=gbps,
    )


async def _wait_until(pred, timeout: float, what: str) -> None:
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() > deadline:
            raise SmokeFailure(f"timed out after {timeout} s waiting for {what}")
        await asyncio.sleep(0.05)


async def _cluster(n_objects: int, n_degraded: int, obj_size: int,
                   n_pgs: int, clock, require_pallas: bool) -> None:
    from ceph_tpu.client import Rados
    from ceph_tpu.codec import registry
    from ceph_tpu.common.config import Config
    from ceph_tpu.ops.device_cache import device_chunk_cache
    from ceph_tpu.osd.osd import OSD
    from ceph_tpu.tools.vstart import DevCluster

    t0, c0 = time.perf_counter(), clock.read()
    before = launch_totals()
    n_osds = K + M
    cluster = DevCluster(n_mons=1, n_osds=n_osds, with_mgr=True)
    await cluster.start()
    client = Rados(cluster.monmap)
    await client.connect()
    checked = [f"{n_osds} OSDs up"]
    try:
        rv, rs, _ = await client.mon_command({
            "prefix": "osd erasure-code-profile set",
            "name": "rs83",
            "profile": [f"k={K}", f"m={M}", "plugin=tpu",
                        "crush-failure-domain=osd"],
        })
        check(rv == 0, f"erasure profile set ({rs})")
        await client.pool_create("ecpool", "erasure", profile="rs83",
                                 pg_num=n_pgs)
        io = await client.open_ioctx("ecpool")
        rng = np.random.default_rng(SEED + 1)
        blob = rng.integers(0, 256, (n_objects, obj_size), dtype=np.uint8)
        objs = {f"obj{i:03d}": blob[i].tobytes() for i in range(n_objects)}
        del blob

        async def read_all(what: str) -> str:
            for oid, want in objs.items():
                got = await io.read(oid)
                check(got == want, f"{what} read of {oid} equals its write")
            return f"{what}: {len(objs)} objects read back byte-equal"

        progress(f"writing {n_objects} objects")
        n0 = launch_totals()
        for oid, payload in objs.items():
            await io.write_full(oid, payload)
        writes = launch_totals()["launches"] - n0["launches"]
        checked.append(check(
            writes >= n_objects,
            f"{n_objects} writes of {obj_size} B acknowledged "
            f"in {writes} device launches",
        ))
        checked.append(await read_all("clean"))

        progress("stopping one OSD")
        victim_id = n_osds - 1
        victim = cluster.osds[victim_id]
        store = victim.store
        await victim.stop()
        mon = cluster.mons[0]
        await _wait_until(lambda: not mon.osdmon.osdmap.is_up(victim_id),
                          60.0, f"mon marking osd.{victim_id} down")
        checked.append(f"osd.{victim_id} stopped and marked down")
        d0 = launch_totals()["decode"]
        progress("degraded reads")
        checked.append(await read_all("degraded"))
        checked.append(check(
            launch_totals()["decode"] > d0,
            f"degraded reads decoded on the device "
            f"({launch_totals()['decode'] - d0} decode launches)",
        ))

        # writes while degraded leave the stopped OSD's shards missing:
        # recovery must rebuild them once it returns
        extra = rng.integers(0, 256, (n_degraded, obj_size), dtype=np.uint8)
        for i in range(n_degraded):
            oid = f"degraded{i:03d}"
            objs[oid] = extra[i].tobytes()
            await io.write_full(oid, objs[oid])
        del extra
        checked.append(f"{n_degraded} writes acknowledged while degraded")

        progress(f"restarting osd.{victim_id}")
        d0 = launch_totals()["decode"]
        h0 = device_chunk_cache().perf_dump()["hits"]
        revived = OSD(victim_id, cluster.monmap,
                      conf=Config({"name": f"osd.{victim_id}"}, env=False),
                      store=store)
        await revived.start()
        await revived.wait_for_up()
        cluster.osds[victim_id] = revived

        def primaries():
            return [
                pg for o in cluster.osds if o._running
                for pg in o.pgs.values()
                if pg.pool.name == "ecpool" and pg.peering.is_primary()
            ]

        await _wait_until(
            lambda: len(primaries()) == n_pgs
            and all(victim_id in pg.acting() and pg.is_clean
                    for pg in primaries()),
            180.0, f"recovery to clean with osd.{victim_id} acting",
        )
        # a rebuilt shard comes from a device decode, or from the HBM
        # chunk cache that still holds what the degraded write encoded
        rebuilt = launch_totals()["decode"] - d0
        hits = device_chunk_cache().perf_dump()["hits"] - h0
        checked.append(check(
            rebuilt + hits > 0,
            f"osd.{victim_id} restarted on its store; {n_pgs} PGs recovered "
            f"to clean ({rebuilt} device decode launches, {hits} device "
            f"cache hits)",
        ))
        progress("reads after recovery")
        checked.append(await read_all("recovered"))

        async def deep_scrub(pgid):
            # an interval change aborts a scrub (PgScrubber::
            # on_new_interval); the PG is scrubbed again once it is
            # active+clean, and only a finished scrub counts
            for _ in range(5):
                pg = None

                def clean_primary():
                    nonlocal pg
                    pg = next((p for p in primaries()
                               if p.pgid == pgid and p.is_clean), None)
                    return pg is not None

                await _wait_until(clean_primary, 60.0,
                                  f"{pgid} to be active+clean")
                done = asyncio.get_running_loop().create_future()
                if pg.scrub(deep=True, on_done=done.set_result):
                    res = await asyncio.wait_for(done, 120.0)
                    if not res.aborted:
                        return res
                await asyncio.sleep(0.5)
            raise SmokeFailure(f"deep scrub of {pgid} aborted 5 times")

        progress("deep scrub")
        v0 = launch_totals()["verify"]
        scrubbed = 0
        for pgid in sorted({pg.pgid for pg in primaries()}, key=str):
            res = await deep_scrub(pgid)
            check(res.clean and not res.inconsistent,
                  f"deep scrub of {pgid} clean ({res.inconsistent})")
            scrubbed += res.objects_scrubbed
        check(scrubbed == len(objs),
              f"deep scrub covered {scrubbed} of {len(objs)} objects")
        verify = launch_totals()["verify"] - v0
        checked.append(check(
            verify > 0,
            f"deep scrub of {n_pgs} PGs: 0 inconsistencies, {scrubbed} "
            f"objects, {verify} verify launches on the device",
        ))
        ec = registry.instance().factory("tpu", {"k": str(K), "m": str(M)})
        checked += device_did_the_work(
            ec, before, [STRIPE_UNIT], require_pallas
        )
    finally:
        await client.shutdown()
        await cluster.stop()
    report("cluster", t0, clock, c0, checked, objects=n_objects,
           degraded_objects=n_degraded, object_bytes=obj_size, pgs=n_pgs)


def phase_cluster(n_objects: int, n_degraded: int, obj_size: int, n_pgs: int,
                  clock, require_pallas: bool = True) -> None:
    asyncio.run(_cluster(n_objects, n_degraded, obj_size, n_pgs, clock,
                         require_pallas))


def bytes_in_use(device) -> int:
    return (device.memory_stats() or {}).get("bytes_in_use", 0)


def phase_multichip(stripes: int, chunk: int, n_dev: int, clock,
                    require_pallas: bool = True) -> None:
    """The sharded codec path over every chip, against one device and
    against `native`."""
    import jax

    from ceph_tpu.codec import registry
    from ceph_tpu.parallel import dispatch as shard_dispatch

    t0, c0 = time.perf_counter(), clock.read()
    before = launch_totals()
    ec = registry.instance().factory("tpu", {"k": str(K), "m": str(M)})
    native = registry.instance().factory("native", {"k": str(K), "m": str(M)})
    host = codec_batch(stripes, chunk)
    idx = ec.decode_index(ERASURES)

    parity = jax.block_until_ready(ec.encode_array(host))
    per_dev = parity.nbytes // n_dev
    shards = {s.device.id: s.data.nbytes for s in parity.addressable_shards}
    in_use = {d.id: bytes_in_use(d) for d in jax.devices()}
    checked = [
        check(len(parity.sharding.device_set) == n_dev
              and sorted(shards.values()) == [per_dev] * n_dev,
              f"sharded parity spans {n_dev} devices, {per_dev} B each"),
        check(all(v >= per_dev for v in in_use.values()),
              f"every device holds >= {per_dev} B (bytes_in_use {in_use})"),
    ]
    full = np.concatenate([host, np.asarray(parity)], axis=1)
    rebuilt = ec.decode_array(ERASURES, np.ascontiguousarray(full[:, idx, :]))
    checked.append(check(len(rebuilt.sharding.device_set) == n_dev,
                         f"sharded decode spans {n_dev} devices"))
    sharded = launch_totals()["sharded"] - before["sharded"]
    checked.append(check(sharded >= 2, f"{sharded} sharded launches"))

    shard_dispatch.configure(devices=1)
    try:
        one_par = ec.encode_array(host)
        one_rec = ec.decode_array(ERASURES, np.ascontiguousarray(full[:, idx, :]))
        check(len(one_par.sharding.device_set) == 1, "one-device run on 1 device")
        one_par, one_rec = np.asarray(one_par), np.asarray(one_rec)
    finally:
        shard_dispatch.configure(devices=0)
    want = native_encode(native, host)
    checked.append(check(
        np.array_equal(full[:, K:], one_par) and np.array_equal(one_par, want),
        f"sharded encode equals one-device and native ({want.nbytes} B)",
    ))
    rebuilt = np.asarray(rebuilt)
    checked.append(check(
        np.array_equal(rebuilt, one_rec)
        and np.array_equal(rebuilt, native_decode(native, full, ERASURES))
        and np.array_equal(rebuilt, full[:, ERASURES, :]),
        f"sharded decode of {ERASURES} equals one-device and native",
    ))
    checked += device_did_the_work(ec, before, [chunk], require_pallas)
    report("multichip", t0, clock, c0, checked, devices=n_dev)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from ceph_tpu.utils.compile_cache import CompileClock, enable_compile_cache

    cache = enable_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    clock = CompileClock()
    t_all = time.perf_counter()
    try:
        t0 = time.perf_counter()
        dev, count = phase_device(args.chips)
        report("device", t0, clock, 0.0,
               [f"{count} x {dev.platform} ({dev.device_kind})"],
               cache_dir=cache, cache_entries_at_start=entries)
        if args.chips == 4:
            phase_multichip(256, 128 * 1024, count, clock)
        else:
            phase_codec(256, 128 * 1024, clock)
            phase_cluster(64, 8, 4 << 20, 32, clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({
        "phase": "total", "seconds": time.perf_counter() - t_all,
        "compile_seconds": clock.read(), "cache_dir": cache,
        "cache_entries_at_start": entries,
    }), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count,
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
