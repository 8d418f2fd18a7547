"""Windowed launches under a backlog and the on-device RMW delta path
(ISSUE 18).

- Byte-identity of windowed launches behind a full in-flight ring vs the
  host oracle across RS(4,2)/RS(8,3) with ragged per-ticket batch sizes:
  every window that trips launches at once.
- The all-wedged fault matrix on windowed groups: a padded multi-ticket
  group must split byte-identically per ticket through the host oracle
  whatever way the device dies (dispatch fault, wedged timeout,
  pre-degraded).
- The RMW delta program vs the full-encode oracle (host and device
  forms), and the end-to-end delta write path on an EC-overwrites pool
  including ragged tails and armed `codec.launch` faults.
- Leak gate: the EC in-flight mempool reads zero after drain with the
  windowed backlog and the delta path both exercised.
"""

import time

import numpy as np
import pytest

from ceph_tpu.codec import ErasureCodeTpuRs
from ceph_tpu.codec.matrix_codec import EncodeAggregator
from ceph_tpu.common.fault_injector import global_injector
from ceph_tpu.common.mempool import ledger as hbm_ledger
from ceph_tpu.ops import dispatch as ec_dispatch
from ceph_tpu.ops.device_cache import device_chunk_cache
from ceph_tpu.ops.guard import device_guard
from ceph_tpu.ops.offload_runtime import drain_all_aggregators


@pytest.fixture(autouse=True)
def _clean_guard_and_injector():
    """Injector and guard state must never leak across tests: a stray
    DEGRADED flag would reroute every later launch through the host."""
    yield
    global_injector().clear()
    device_guard().mark_healthy()
    device_guard().configure(timeout_ms=20000, probe_interval_ms=2000)


def make_rs(k, m):
    ec = ErasureCodeTpuRs()
    ec.init({"k": str(k), "m": str(m)})
    return ec


def _backlogged_aggregator():
    """window=2 / depth=1: the first window trip launches and fills the
    ring, and every later trip launches behind it — each settling the
    oldest launch first."""
    return EncodeAggregator(
        window=2,
        max_bytes=1 << 30,
        inflight_max_bytes=1 << 30,
        pipeline_depth=1,
    )


def _submit_all(agg, ec, batches):
    tickets = [agg.submit(ec, h) for h in batches]
    agg.flush()
    return tickets


class TestWindowedBacklogByteIdentity:
    """Windowed launches behind a full ring are byte-identical to
    per-ticket host encodes, and each window trip launches at once."""

    @pytest.mark.parametrize("k,m", [(4, 2), (8, 3)])
    def test_backlogged_multiwindow_launch_byte_identical(self, k, m):
        ec = make_rs(k, m)
        rng = np.random.default_rng(21)
        agg = _backlogged_aggregator()
        # ragged per-ticket stripe counts: the pad machinery zero-fills
        # around these, and the settle slices must cut exactly at the
        # ticket boundaries inside each padded batch
        sizes = (1, 2, 3, 1, 2, 3, 5, 1, 2, 1)
        batches = [
            rng.integers(0, 256, (s, k, 2048), dtype=np.uint8) for s in sizes
        ]
        w0 = agg.perf.get("flush_window")
        tickets = _submit_all(agg, ec, batches)
        for h, t in zip(batches, tickets):
            assert np.array_equal(np.asarray(t), ec.encode_array_host(h))
        assert agg.perf.get("flush_window") - w0 == len(sizes) // 2, (
            "a tripped window did not launch at once"
        )


class TestWindowedWedgedFaultMatrix:
    """All-wedged fault matrix on windowed groups: however the device
    dies, a padded multi-ticket group completes on the host oracle and
    splits byte-identically per ticket."""

    @pytest.mark.parametrize(
        "mode", ["dispatch_fault", "wedged_timeout", "pre_degraded"]
    )
    def test_windowed_group_host_fallback_byte_identical(self, mode):
        ec = make_rs(4, 2)
        rng = np.random.default_rng(31)
        agg = _backlogged_aggregator()
        sizes = (1, 3, 2, 2, 1, 1, 2, 3)
        batches = [
            rng.integers(0, 256, (s, 4, 2048), dtype=np.uint8) for s in sizes
        ]
        hf0 = agg.perf.get("host_fallbacks")
        w0 = agg.perf.get("flush_window")
        pad0 = agg.perf.get("pad_stripes")
        real = ec.encode_array
        if mode == "dispatch_fault":
            global_injector().inject("codec.launch", 5, hits=100)
        elif mode == "wedged_timeout":

            def wedge(arr):
                time.sleep(0.3)  # well past the 50 ms deadline below
                return real(arr)

            device_guard().configure(timeout_ms=50)
            ec.encode_array = wedge
        else:  # pre_degraded: the backend is already down, probe gated
            device_guard().configure(probe_interval_ms=10_000_000)
            device_guard().mark_degraded("test: all wedged")
            assert not device_guard().maybe_probe(
                lambda: (_ for _ in ()).throw(RuntimeError("still dead"))
            )
        try:
            tickets = _submit_all(agg, ec, batches)
            for h, t in zip(batches, tickets):
                assert np.array_equal(
                    np.asarray(t), ec.encode_array_host(h)
                ), mode
        finally:
            ec.encode_array = real
            global_injector().clear()
        assert agg.perf.get("host_fallbacks") > hf0, mode
        assert agg.perf.get("flush_window") - w0 == len(sizes) // 2, mode
        # (1,3) (2,2) (1,1) (2,3) -> 4, 4, 2, 5 stripes: the 5-stripe
        # window pads to 8, so the fallback split a padded group
        assert agg.perf.get("pad_stripes") - pad0 == 3, mode
        if mode != "pre_degraded":
            assert device_guard().degraded, mode


class TestDeltaProgramByteIdentity:
    """parity_new == parity_old ^ Encode(data_old ^ data_new): the delta
    program (host and device forms) against the full-encode oracle."""

    @pytest.mark.parametrize("k,m", [(4, 2), (8, 3)])
    @pytest.mark.parametrize("chunk", [512, 1536])  # 1536: ragged, non-pow2
    def test_delta_matches_full_encode(self, k, m, chunk):
        import jax.numpy as jnp

        ec = make_rs(k, m)
        rng = np.random.default_rng(41)
        stripes = 3
        old = rng.integers(0, 256, (stripes, k, chunk), dtype=np.uint8)
        new = old.copy()
        # ragged-tail mutations: only slices of some chunks change
        new[1, 2, 100 : min(700, chunk)] ^= 0x5A
        new[2, 0, : chunk // 3] ^= 0xFF
        new[0, k - 1, chunk // 2 :] ^= 0x11
        old_par = ec.encode_array_host(old)
        want = ec.encode_array_host(new)
        host = ec.encode_delta_host(old, new, old_par)
        assert np.array_equal(host, want)
        # device form, fed the cache's flat per-shard buffer layout
        old_bufs = [jnp.asarray(old[:, i, :].reshape(-1)) for i in range(k)]
        new_bufs = [jnp.asarray(new[:, i, :].reshape(-1)) for i in range(k)]
        par_bufs = [
            jnp.asarray(old_par[:, i, :].reshape(-1)) for i in range(m)
        ]
        dev = np.asarray(
            ec.encode_delta_device(old_bufs, new_bufs, par_bufs, chunk)
        )
        assert np.array_equal(dev, want)

    def test_delta_device_launch_is_counted_once(self):
        import jax.numpy as jnp

        ec = make_rs(4, 2)
        rng = np.random.default_rng(42)
        old = rng.integers(0, 256, (2, 4, 512), dtype=np.uint8)
        new = old ^ np.uint8(3)
        old_par = ec.encode_array_host(old)
        ec.encode_delta_device(  # warm
            [jnp.asarray(old[:, i, :].reshape(-1)) for i in range(4)],
            [jnp.asarray(new[:, i, :].reshape(-1)) for i in range(4)],
            [jnp.asarray(old_par[:, i, :].reshape(-1)) for i in range(2)],
            512,
        )
        before = ec_dispatch.LAUNCHES.snapshot()
        ec.encode_delta_device(
            [jnp.asarray(old[:, i, :].reshape(-1)) for i in range(4)],
            [jnp.asarray(new[:, i, :].reshape(-1)) for i in range(4)],
            [jnp.asarray(old_par[:, i, :].reshape(-1)) for i in range(2)],
            512,
        )
        after = ec_dispatch.LAUNCHES.snapshot()
        assert after["launches"] - before["launches"] == 1
        assert after["stripes"] - before["stripes"] == 2


class TestRmwDeltaEndToEnd:
    """The delta write path on an EC-overwrites pool: byte-identical to
    the host-computed expected object across ragged tails, interleaved
    with materialize fallbacks, and under armed codec.launch faults."""

    def _setup_cache(self):
        cc = device_chunk_cache()
        cc.configure(max_bytes=1 << 24)
        cc.clear()
        return cc

    def _teardown_cache(self, cc):
        from ceph_tpu.common.options import OPTIONS

        cc.clear()
        cc.configure(
            max_bytes=int(OPTIONS["ec_tpu_device_cache_bytes"].default)
        )

    @pytest.mark.parametrize("k,m", [(4, 2), (8, 3)])
    def test_delta_rmw_byte_identical_incl_ragged_tail(self, k, m):
        from test_ec_backend import (
            FLAG_EC_OVERWRITES,
            Cluster,
            ec_pool,
            payload,
        )

        cc = self._setup_cache()
        try:
            pool, profiles = ec_pool(k, m, flags=FLAG_EC_OVERWRITES)
            c = Cluster(pool, profiles)
            sw = pool.stripe_width
            base = payload(2 * sw + 1234)  # ragged tail stripe
            c.write("obj", 0, base)
            expect = bytearray(base)
            d0 = cc.perf_dump()["delta_updates"]
            # interior overwrite (delta), stripe-crossing overwrite
            # (generation skew -> materialize), ragged-tail overwrite
            # (delta again off the reseeded cache)
            for off, ln, seed in (
                (100, 600, 6),
                (sw - 50, 300, 7),
                (2 * sw + 1000, 200, 8),
            ):
                patch = payload(ln, seed=seed)
                c.write("obj", off, patch)
                expect[off : off + ln] = patch
                assert c.read("obj", 0, len(expect)) == bytes(expect), (
                    off,
                    ln,
                )
            assert cc.perf_dump()["delta_updates"] > d0, (
                "the RMW delta path never fired"
            )
        finally:
            self._teardown_cache(cc)

    def test_delta_rmw_byte_identical_under_codec_launch_faults(self):
        """hits=1 kills only the delta dispatch (falls back to the
        materialize encode); hits=2 kills that too (host oracle) — the
        committed bytes must be identical either way."""
        from test_ec_backend import (
            FLAG_EC_OVERWRITES,
            Cluster,
            ec_pool,
            payload,
        )

        cc = self._setup_cache()
        try:
            pool, profiles = ec_pool(4, 2, flags=FLAG_EC_OVERWRITES)
            c = Cluster(pool, profiles)
            sw = pool.stripe_width
            base = payload(2 * sw, seed=11)
            c.write("obj", 0, base)
            expect = bytearray(base)
            for hits, off, ln, seed in (
                (1, 40, 500, 12),
                (2, sw + 10, 700, 13),
            ):
                global_injector().inject("codec.launch", 5, hits=hits)
                patch = payload(ln, seed=seed)
                c.write("obj", off, patch)
                expect[off : off + ln] = patch
                global_injector().clear()
                device_guard().mark_healthy()
                assert c.read("obj", 0, len(expect)) == bytes(expect), hits
        finally:
            self._teardown_cache(cc)


class TestWindowedDeltaLeakGates:
    """The ISSUE 18 chaos invariant at tier-1 scope: with the windowed
    backlog and the delta path both exercised, the EC in-flight mempool
    reads zero after drain."""

    def test_inflight_ledger_reads_zero_after_drain(self):
        from test_ec_backend import (
            FLAG_EC_OVERWRITES,
            Cluster,
            ec_pool,
            payload,
        )

        cc = device_chunk_cache()
        cc.configure(max_bytes=1 << 24)
        cc.clear()
        try:
            # windowed backlog workload
            ec = make_rs(4, 2)
            rng = np.random.default_rng(51)
            agg = _backlogged_aggregator()
            batches = [
                rng.integers(0, 256, (s, 4, 2048), dtype=np.uint8)
                for s in (1, 2, 3, 2, 1, 2, 2, 3)
            ]
            for t in _submit_all(agg, ec, batches):
                np.asarray(t)
            assert agg.perf.get("flush_window") >= 1
            # delta workload
            pool, profiles = ec_pool(4, 2, flags=FLAG_EC_OVERWRITES)
            c = Cluster(pool, profiles)
            base = payload(2 * pool.stripe_width, seed=52)
            c.write("obj", 0, base)
            c.write("obj", 123, payload(456, seed=53))
            assert cc.perf_dump()["delta_updates"] >= 1
        finally:
            from ceph_tpu.common.options import OPTIONS

            cc.clear()
            cc.configure(
                max_bytes=int(OPTIONS["ec_tpu_device_cache_bytes"].default)
            )
        drain_all_aggregators()
        led = hbm_ledger()
        assert led.current_bytes("ec_pipeline_inflight") == 0, (
            led.reconcile()
        )
