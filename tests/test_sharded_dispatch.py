"""Sharded dispatch mode (ISSUE 6): aggregated coding launches spanning
the 8-device virtual CPU mesh.

CPU CI exercises REAL 8-device meshes the way the driver's multi-chip
dry-run does: the session-wide conftest forces
`--xla_force_host_platform_device_count=8` before jax initializes, so
every test here runs against eight actual XLA devices (no mocks).
Coverage pinned by the ISSUE 6 satellite: byte-identical parity vs the
host oracle through the sharded aggregator path, non-divisible batch
remainder handling, and single-device fallback when the mesh is
degenerate (sharding disabled)."""

from __future__ import annotations

import numpy as np
import pytest

from ceph_tpu.codec import ErasureCodeTpuRs
from ceph_tpu.codec.matrix_codec import DecodeAggregator, EncodeAggregator
from ceph_tpu.gf import gf_matmul, isa_rs_vandermonde_matrix
from ceph_tpu.ops.dispatch import (
    DECODE_LAUNCHES,
    DEVICES_PER_LAUNCH,
    LAUNCHES,
    SHARDED_LAUNCHES,
)
from ceph_tpu.parallel import dispatch as shard_dispatch


@pytest.fixture(autouse=True)
def _shard_policy():
    """Give every test a known shard policy and restore defaults after
    (the policy is process-wide; leaking a tiny threshold would shard
    unrelated suites' launches)."""
    shard_dispatch.configure(
        min_batch=shard_dispatch.DEFAULT_MIN_BATCH,
        devices=shard_dispatch.DEFAULT_DEVICES,
    )
    yield
    shard_dispatch.configure(
        min_batch=shard_dispatch.DEFAULT_MIN_BATCH,
        devices=shard_dispatch.DEFAULT_DEVICES,
    )


def make_rs(k=8, m=3):
    ec = ErasureCodeTpuRs()
    ec.init({"k": str(k), "m": str(m)})
    return ec


def _host_parity(ec, data: np.ndarray) -> np.ndarray:
    gfm = isa_rs_vandermonde_matrix(ec.k, ec.m)[ec.k:]
    return np.stack([gf_matmul(gfm, stripe) for stripe in data])


def _batch(S, k, L, seed=0):
    return np.random.default_rng(seed).integers(0, 256, (S, k, L), dtype=np.uint8)


class TestShardedEncode:
    def test_above_threshold_is_one_sharded_launch_spanning_mesh(self):
        """An aggregated-size batch above ec_tpu_shard_min_batch must
        dispatch as ONE launch spanning all 8 devices, byte-identical to
        the host oracle (the ISSUE 6 acceptance invariant)."""
        ec = make_rs()
        shard_dispatch.configure(min_batch=16)
        data = _batch(32, 8, 4096, seed=1)
        t0, s0 = LAUNCHES.snapshot(), SHARDED_LAUNCHES.snapshot()
        d0 = DEVICES_PER_LAUNCH.snapshot()
        parity = np.asarray(ec.encode_array(data))
        t1, s1 = LAUNCHES.snapshot(), SHARDED_LAUNCHES.snapshot()
        assert t1["launches"] - t0["launches"] == 1
        assert s1["launches"] - s0["launches"] == 1
        assert s1["stripes"] - s0["stripes"] == 32
        d1 = DEVICES_PER_LAUNCH.snapshot()
        assert d1.get(8, 0) - d0.get(8, 0) == 1, "launch did not span 8 devices"
        assert np.array_equal(parity, _host_parity(ec, data))

    def test_below_threshold_stays_single_device(self):
        ec = make_rs()
        shard_dispatch.configure(min_batch=64)
        data = _batch(32, 8, 4096, seed=2)
        s0 = SHARDED_LAUNCHES.snapshot()
        parity = np.asarray(ec.encode_array(data))
        assert SHARDED_LAUNCHES.snapshot()["launches"] == s0["launches"]
        assert np.array_equal(parity, _host_parity(ec, data))

    def test_non_divisible_remainder(self):
        """37 stripes over 8 shards: the dispatcher pads with zero
        stripes (exact for GF maps) and slices back — bytes identical."""
        ec = make_rs()
        shard_dispatch.configure(min_batch=16)
        data = _batch(37, 8, 4096, seed=3)
        s0 = SHARDED_LAUNCHES.snapshot()
        parity = np.asarray(ec.encode_array(data))
        s1 = SHARDED_LAUNCHES.snapshot()
        assert s1["launches"] - s0["launches"] == 1
        assert parity.shape == (37, 3, 4096)
        assert np.array_equal(parity, _host_parity(ec, data))

    def test_single_device_fallback_when_degenerate(self):
        """ec_tpu_shard_devices=1 (a degenerate mesh) must keep every
        launch single-device and still byte-exact."""
        ec = make_rs()
        shard_dispatch.configure(min_batch=16, devices=1)
        data = _batch(32, 8, 4096, seed=4)
        s0 = SHARDED_LAUNCHES.snapshot()
        d0 = DEVICES_PER_LAUNCH.snapshot()
        parity = np.asarray(ec.encode_array(data))
        assert SHARDED_LAUNCHES.snapshot()["launches"] == s0["launches"]
        d1 = DEVICES_PER_LAUNCH.snapshot()
        assert d1.get(1, 0) - d0.get(1, 0) == 1
        assert np.array_equal(parity, _host_parity(ec, data))

    def test_lead_dims_collapse_into_stripe_axis(self):
        """N-D batches (CLAY's (planes, S, k+nu, sc) fragment launches)
        collapse their lead dims into one sharded stripe axis; output
        keeps the caller's geometry and bytes stay exact."""
        ec = make_rs(4, 2)
        shard_dispatch.configure(min_batch=16)
        rng = np.random.default_rng(6)
        data = rng.integers(0, 256, (4, 8, 4, 4096), dtype=np.uint8)
        s0 = SHARDED_LAUNCHES.snapshot()
        parity = np.asarray(ec.encode_array(data))
        s1 = SHARDED_LAUNCHES.snapshot()
        assert s1["launches"] - s0["launches"] == 1
        assert s1["stripes"] - s0["stripes"] == 32
        assert parity.shape == (4, 8, 2, 4096)
        flat = data.reshape(-1, 4, 4096)
        want = _host_parity(ec, flat).reshape(4, 8, 2, 4096)
        assert np.array_equal(parity, want)

    def test_small_bytes_never_shard(self):
        """Batches under PACKED_MIN_BYTES stay on the shared small-input
        kernel even when the stripe count crosses the threshold."""
        ec = make_rs(4, 2)
        shard_dispatch.configure(min_batch=16)
        data = _batch(32, 4, 64, seed=5)  # 8 KiB total
        s0 = SHARDED_LAUNCHES.snapshot()
        parity = np.asarray(ec.encode_array(data))
        assert SHARDED_LAUNCHES.snapshot()["launches"] == s0["launches"]
        assert np.array_equal(parity, _host_parity(ec, data))


class TestShardedAggregatorPath:
    """The production route: concurrent submissions coalesce in the
    aggregator, the padded flush crosses the shard threshold, and the
    ONE resulting launch spans the mesh."""

    def test_encode_aggregator_flush_shards(self):
        ec = make_rs()
        shard_dispatch.configure(min_batch=16)
        agg = EncodeAggregator(window=8, max_bytes=1 << 30)
        datas = [_batch(8, 8, 4096, seed=10 + i) for i in range(8)]
        t0, s0 = LAUNCHES.snapshot(), SHARDED_LAUNCHES.snapshot()
        tickets = [agg.submit(ec, d) for d in datas]  # 8th submit flushes
        outs = [np.asarray(t) for t in tickets]
        t1, s1 = LAUNCHES.snapshot(), SHARDED_LAUNCHES.snapshot()
        assert t1["launches"] - t0["launches"] == 1, "window did not coalesce"
        assert s1["launches"] - s0["launches"] == 1, "flush did not shard"
        for d, out in zip(datas, outs):
            assert np.array_equal(out, _host_parity(ec, d))

    def test_decode_aggregator_flush_shards(self):
        """Recovery-shaped decodes (one erasure signature, many objects)
        coalesce into one sharded DECODE launch, reconstructions exact."""
        ec = make_rs()
        shard_dispatch.configure(min_batch=16)
        erasures = [0, 5, 9]
        idx = ec.decode_index(erasures)
        agg = DecodeAggregator(window=4, max_bytes=1 << 30)
        datas = [_batch(8, 8, 4096, seed=30 + i) for i in range(4)]
        fulls = [np.concatenate([d, _host_parity(ec, d)], axis=1) for d in datas]
        d0, s0 = DECODE_LAUNCHES.snapshot(), SHARDED_LAUNCHES.snapshot()
        tickets = [
            agg.submit(ec, erasures, full[:, idx, :].copy()) for full in fulls
        ]
        outs = [np.asarray(t) for t in tickets]
        d1, s1 = DECODE_LAUNCHES.snapshot(), SHARDED_LAUNCHES.snapshot()
        assert d1["launches"] - d0["launches"] == 1
        assert s1["launches"] - s0["launches"] == 1
        for full, out in zip(fulls, outs):
            assert np.array_equal(out, full[:, erasures, :])

    def test_direct_decode_array_shards_and_matches(self):
        ec = make_rs()
        shard_dispatch.configure(min_batch=16)
        erasures = [1, 9]
        idx = ec.decode_index(erasures)
        data = _batch(24, 8, 4096, seed=40)
        full = np.concatenate([data, _host_parity(ec, data)], axis=1)
        s0 = SHARDED_LAUNCHES.snapshot()
        rec = np.asarray(ec.decode_array(erasures, full[:, idx, :].copy()))
        assert SHARDED_LAUNCHES.snapshot()["launches"] - s0["launches"] == 1
        assert np.array_equal(rec, full[:, erasures, :])


class TestShardPolicy:
    def test_device_cap_respected(self):
        """ec_tpu_shard_devices=4 builds a 4-wide mesh even with 8
        visible devices."""
        ec = make_rs()
        shard_dispatch.configure(min_batch=16, devices=4)
        data = _batch(32, 8, 4096, seed=50)
        d0 = DEVICES_PER_LAUNCH.snapshot()
        parity = np.asarray(ec.encode_array(data))
        d1 = DEVICES_PER_LAUNCH.snapshot()
        assert d1.get(4, 0) - d0.get(4, 0) == 1
        assert np.array_equal(parity, _host_parity(ec, data))

    def test_fewer_stripes_than_shards_stays_single_device(self):
        """A mesh wider than the batch would place zero real stripes on
        some devices — the policy declines to shard."""
        ec = make_rs()
        shard_dispatch.configure(min_batch=2)
        data = _batch(4, 8, 8192, seed=51)  # >= PACKED_MIN_BYTES, 4 < 8
        s0 = SHARDED_LAUNCHES.snapshot()
        parity = np.asarray(ec.encode_array(data))
        assert SHARDED_LAUNCHES.snapshot()["launches"] == s0["launches"]
        assert np.array_equal(parity, _host_parity(ec, data))

    def test_settings_roundtrip(self):
        shard_dispatch.configure(min_batch=7, devices=3)
        assert shard_dispatch.settings() == (7, 3)
