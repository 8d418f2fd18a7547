"""Perf smoke (tier-1): dispatch-shape invariants of the coding hot paths.

Runs small encode/decode chains on the CPU backend and asserts the
launch counters and plan-cache hit rates, so a regression back to
per-stripe dispatch or per-call plan rebuilds fails `pytest -m 'not
slow'` immediately instead of only dilating `python bench.py`
(ISSUE 3 / ISSUE 5 satellites).  The counters are python-dispatch
witnesses — see ceph_tpu/ops/dispatch.py for what they do and don't
count; DECODE_LAUNCHES isolates the recovery/degraded-read half."""

import numpy as np

from ceph_tpu.codec import ErasureCodeTpuRs
from ceph_tpu.codec.matrix_codec import PLAN_CACHE
from ceph_tpu.ops.dispatch import (
    DECODE_LAUNCHES,
    DEVICES_PER_LAUNCH,
    LAUNCHES,
    SHARDED_LAUNCHES,
    perf_dump,
)
from ceph_tpu.stripe import StripeInfo
from ceph_tpu.stripe import stripe as stripe_mod


def make_rs(k=4, m=2):
    ec = ErasureCodeTpuRs()
    ec.init({"k": str(k), "m": str(m)})
    return ec


class TestPerfSmoke:
    def test_batched_encode_is_one_dispatch(self):
        ec = make_rs()
        sinfo = StripeInfo(4 * 4096, 4096)
        stripes = 32
        obj = np.random.default_rng(0).integers(
            0, 256, stripes * sinfo.stripe_width, dtype=np.uint8
        )
        # warm coder + jit caches with one small stripe
        ec.encode_array(obj[: sinfo.stripe_width].reshape(1, 4, 4096))
        before = LAUNCHES.snapshot()
        shards = stripe_mod.encode(sinfo, ec, obj)
        after = LAUNCHES.snapshot()
        assert after["launches"] - before["launches"] == 1, (
            f"{stripes} stripes took {after['launches'] - before['launches']} "
            "device dispatches; the batched path regressed to per-stripe launches"
        )
        assert after["stripes"] - before["stripes"] == stripes
        assert len(shards) == 6

    def test_degraded_read_chain_dispatch_budget(self):
        """Encode + reconstruct chain: one dispatch for the encode, one
        for the decode — losing a shard must not fan out per stripe."""
        ec = make_rs()
        sinfo = StripeInfo(4 * 4096, 4096)
        obj = np.random.default_rng(1).integers(
            0, 256, 16 * sinfo.stripe_width, dtype=np.uint8
        )
        shards = stripe_mod.encode(sinfo, ec, obj)
        have = {i: shards[i] for i in range(6) if i != 2}
        before = LAUNCHES.snapshot()
        logical = stripe_mod.decode_concat(sinfo, ec, have)
        launches = LAUNCHES.snapshot()["launches"] - before["launches"]
        assert np.array_equal(logical, obj)
        assert launches == 1, launches

    def test_plan_cache_steady_state_hit_rate(self):
        """Re-encoding with the same geometry must hit the coder cache:
        misses stay flat while hits climb."""
        ec = make_rs()
        sinfo = StripeInfo(4 * 4096, 4096)
        obj = np.random.default_rng(2).integers(
            0, 256, 4 * sinfo.stripe_width, dtype=np.uint8
        )
        stripe_mod.encode(sinfo, ec, obj)  # ensure the coder exists
        s0 = PLAN_CACHE.stats()
        for _ in range(5):
            stripe_mod.encode(sinfo, ec, obj)
        s1 = PLAN_CACHE.stats()
        assert s1["hits"] - s0["hits"] == 5
        assert s1["misses"] == s0["misses"], "steady-state encode rebuilt a plan"

    def test_recovery_decode_is_one_decode_dispatch(self):
        """Rebuilding whole shards for a 16-stripe object must cost one
        DECODE dispatch (the ISSUE 5 decode launch-counter contract) —
        and that dispatch also lands on the global total."""
        ec = make_rs()
        sinfo = StripeInfo(4 * 4096, 4096)
        stripes = 16
        obj = np.random.default_rng(3).integers(
            0, 256, stripes * sinfo.stripe_width, dtype=np.uint8
        )
        shards = stripe_mod.encode(sinfo, ec, obj)
        have = {i: shards[i] for i in range(6) if i not in (1, 4)}
        before_d = DECODE_LAUNCHES.snapshot()
        before_t = LAUNCHES.snapshot()
        rebuilt = stripe_mod.decode_shards(sinfo, ec, have, {1, 4})
        after_d = DECODE_LAUNCHES.snapshot()
        after_t = LAUNCHES.snapshot()
        assert np.array_equal(rebuilt[1], shards[1])
        assert np.array_equal(rebuilt[4], shards[4])
        assert after_d["launches"] - before_d["launches"] == 1, (
            f"{stripes}-stripe recovery took "
            f"{after_d['launches'] - before_d['launches']} decode dispatches; "
            "the batched decode path regressed to per-stripe launches"
        )
        assert after_d["stripes"] - before_d["stripes"] == stripes
        assert after_t["launches"] - before_t["launches"] == 1

    def test_decode_plan_cache_steady_state_hit_rate(self):
        """Re-decoding the same erasure pattern must hit the decode coder
        LRU: misses stay flat while hits climb (a regression to per-call
        Gaussian inversions would only show up in recovery latency)."""
        ec = make_rs()
        sinfo = StripeInfo(4 * 4096, 4096)
        obj = np.random.default_rng(4).integers(
            0, 256, 4 * sinfo.stripe_width, dtype=np.uint8
        )
        shards = stripe_mod.encode(sinfo, ec, obj)
        have = {i: shards[i] for i in range(6) if i != 2}
        stripe_mod.decode_shards(sinfo, ec, have, {2})  # coder exists
        s0 = PLAN_CACHE.stats()
        for _ in range(5):
            stripe_mod.decode_shards(sinfo, ec, have, {2})
        s1 = PLAN_CACHE.stats()
        assert s1["hits"] - s0["hits"] == 5
        assert s1["misses"] == s0["misses"], "steady-state decode rebuilt a plan"


class TestShardedCounters:
    """Sharded-launch counter consistency (ISSUE 6 satellite): the
    counters feed asok perf dump and the mgr Prometheus scrape — these
    invariants keep them from silently rotting."""

    def test_sharded_launches_never_exceed_total(self):
        """By construction every sharded dispatch also lands on the
        global total: SHARDED_LAUNCHES <= LAUNCHES, always."""
        s, t = SHARDED_LAUNCHES.snapshot(), LAUNCHES.snapshot()
        assert s["launches"] <= t["launches"]
        assert s["stripes"] <= t["stripes"]
        assert s["bytes"] <= t["bytes"]
        assert DECODE_LAUNCHES.snapshot()["launches"] <= t["launches"]

    def test_devices_per_launch_histogram_consistent(self):
        """Occupancy distribution vs the launch counters: every dispatch
        records exactly one occupancy sample, multi-device samples equal
        the sharded-launch total, and a 1-device dispatch reports zero
        sharded launches."""
        from ceph_tpu.parallel import dispatch as shard_dispatch

        ec = make_rs()
        rng = np.random.default_rng(7)
        min_batch, devices = shard_dispatch.settings()
        try:
            # a guaranteed-sharded launch, then a guaranteed-single one
            shard_dispatch.configure(min_batch=16, devices=0)
            t0 = LAUNCHES.snapshot()["launches"]
            s0 = SHARDED_LAUNCHES.snapshot()["launches"]
            d0 = DEVICES_PER_LAUNCH.snapshot()
            ec.encode_array(rng.integers(0, 256, (32, 4, 4096), dtype=np.uint8))
            shard_dispatch.configure(devices=1)  # degenerate mesh: 1-device run
            ec.encode_array(rng.integers(0, 256, (32, 4, 4096), dtype=np.uint8))
        finally:
            shard_dispatch.configure(min_batch=min_batch, devices=devices)
        t1 = LAUNCHES.snapshot()["launches"]
        s1 = SHARDED_LAUNCHES.snapshot()["launches"]
        d1 = DEVICES_PER_LAUNCH.snapshot()
        assert t1 - t0 == 2
        assert s1 - s0 == 1, "exactly the wide launch lands on SHARDED_LAUNCHES"
        occ_delta = {
            n: d1.get(n, 0) - d0.get(n, 0) for n in set(d0) | set(d1)
        }
        assert sum(occ_delta.values()) == 2, "one occupancy sample per dispatch"
        assert occ_delta.get(1, 0) == 1, "the 1-device run must sample width 1"
        wide = sum(v for n, v in occ_delta.items() if n > 1)
        assert wide == s1 - s0, "multi-device samples must equal sharded total"

    def test_perf_dump_exports_sharded_dimension(self):
        """The asok/mgr export payload carries the sharded counters and
        the devices-per-launch distribution, internally consistent."""
        dump = perf_dump()
        for key in ("launches", "sharded_launches", "decode_launches",
                    "device_launches"):
            assert key in dump, f"missing {key} in ec_dispatch perf dump"
        assert dump["sharded_launches"] <= dump["launches"]
        occ = {
            int(k.split(".")[1]): v
            for k, v in dump.items()
            if k.startswith("devices_per_launch.")
        }
        assert sum(occ.values()) == dump["launches"]
        assert sum(v for n, v in occ.items() if n > 1) == dump["sharded_launches"]
        assert sum(n * v for n, v in occ.items()) == dump["device_launches"]


class TestRmwDeltaSmoke:
    """RMW delta-path perf contract (ISSUE 18 satellite): a cache-hit
    partial overwrite commits a delta flight record that moved zero
    bytes over PCIe — h2d_s == 0 and d2h_s == 0."""

    def test_cache_hit_rmw_commits_zero_pcie_flight_record(self):
        from test_ec_backend import (
            FLAG_EC_OVERWRITES,
            Cluster,
            ec_pool,
            payload,
        )

        from ceph_tpu.common.options import OPTIONS
        from ceph_tpu.ops.device_cache import device_chunk_cache
        from ceph_tpu.ops.flight_recorder import flight_recorder

        cc = device_chunk_cache()
        cc.configure(max_bytes=1 << 24)
        cc.clear()
        try:
            pool, profiles = ec_pool(4, 2, flags=FLAG_EC_OVERWRITES)
            c = Cluster(pool, profiles)
            sw = pool.stripe_width
            base = payload(2 * sw, seed=19)
            c.write("obj", 0, base)  # seeds every chunk resident
            d0 = cc.perf_dump()["delta_updates"]
            patch = payload(600, seed=20)
            c.write("obj", 100, patch)
            assert cc.perf_dump()["delta_updates"] > d0, (
                "the cache-hit overwrite did not take the delta path"
            )
            deltas = [
                r for r in flight_recorder().records()
                if r["flags"].get("delta")
            ]
            assert deltas, "no delta flight record committed"
            rec = deltas[-1]
            assert rec["flags"].get("cache_hit")
            assert rec["h2d_s"] == 0.0, (
                f"delta path uploaded bytes (h2d_s={rec['h2d_s']}); "
                "the zero-PCIe contract regressed"
            )
            assert rec["d2h_s"] == 0.0, (
                f"delta path downloaded bytes (d2h_s={rec['d2h_s']}); "
                "the zero-PCIe contract regressed"
            )
            expect = bytearray(base)
            expect[100:700] = patch
            assert c.read("obj", 0, len(expect)) == bytes(expect)
        finally:
            cc.clear()
            cc.configure(
                max_bytes=int(OPTIONS["ec_tpu_device_cache_bytes"].default)
            )
