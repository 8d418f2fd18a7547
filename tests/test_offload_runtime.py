"""Device-offload runtime (ISSUE 20): the service registry that names
every LaunchAggregator-backed offload service, the runtime's one public
home for the aggregation engine (`ops/offload_runtime`; the codec keeps
no re-export shim), the one launch path's static pad buckets and
in-flight pool drains, and the device crc32c service's byte-identity
against `utils/crc32c` across block sizes, ragged tails, fault
injection and the DEGRADED bypass."""

import numpy as np
import pytest

from ceph_tpu.common.fault_injector import global_injector
from ceph_tpu.ops.checksum_offload import (
    CSUM_OFFLOAD_MIN_BYTES,
    ChecksumAggregator,
    checksum_blocks,
    crc32c_device,
    crc32c_host_rows,
    default_csum_aggregator,
)
from ceph_tpu.ops.guard import device_guard
from ceph_tpu.utils.crc32c import crc32c


@pytest.fixture(autouse=True)
def _clean_state():
    yield
    global_injector().clear()
    device_guard().mark_healthy()
    device_guard().configure(timeout_ms=20000, probe_interval_ms=2000)


class TestServiceRegistry:
    def test_builtin_services_present_in_registration_order(self):
        from ceph_tpu.ops.offload_runtime import offload_services

        names = offload_services()
        # the EC trio registered first (the refactor's zero-behavior
        # seam), the ISSUE 20 services after them
        for want in ("encode", "decode", "verify", "compress", "csum"):
            assert want in names, names

    def test_service_resolves_the_default_aggregators(self):
        from ceph_tpu.codec.matrix_codec import default_encode_aggregator
        from ceph_tpu.compressor.device import default_compress_aggregator
        from ceph_tpu.ops.offload_runtime import service, service_aggregator

        assert service("csum").aggregator() is default_csum_aggregator()
        assert service("encode").aggregator() is default_encode_aggregator()
        assert service_aggregator("compress") is default_compress_aggregator()

    def test_register_is_idempotent(self):
        from ceph_tpu.ops.offload_runtime import (
            offload_services,
            register_service,
        )

        before = offload_services()
        register_service(
            "csum", default_csum_aggregator, lane="background",
            oracle="utils/crc32c.crc32c", doc="re-registration no-op",
        )
        assert offload_services() == before

    def test_perf_dump_is_flat_and_names_every_service(self):
        from ceph_tpu.ops.offload_runtime import (
            offload_perf_dump,
            offload_services,
        )

        dump = offload_perf_dump()
        names = offload_services()
        assert dump["services"] == len(names)
        for name in names:
            assert f"{name}.pending" in dump, sorted(dump)
        # flat values only — scalars plus the histogram payload shape
        # the prometheus exporter already renders; nothing nested deeper
        assert all(
            isinstance(v, (int, float))
            or (isinstance(v, dict) and "histogram" in v)
            for v in dump.values()
        )

    def test_service_lanes_match_their_qos_class(self):
        from ceph_tpu.ops.offload_runtime import service

        # checksums and compression must never head-of-line-block
        # client encodes: both ride the background lane
        assert service("csum").lane == "background"
        assert service("compress").lane == "background"
        assert service("csum").aggregator().SCHED_CLASS == "background"


class TestRefactorGuard:
    def test_engine_names_import_from_the_runtime(self):
        """The aggregation engine has one home: every name its callers
        use imports from ops/offload_runtime, and the codec module does
        not re-export the runtime-wide names (two import paths invite two
        registries)."""
        from ceph_tpu.codec import matrix_codec as mc
        from ceph_tpu.ops.offload_runtime import (
            _AGGREGATORS,
            AggTicket,
            LaunchAggregator,
            OffloadService,
            _AggGroup,
            drain_all_aggregators,
            offload_perf_dump,
            offload_services,
            register_service,
            service,
            service_aggregator,
        )

        assert issubclass(mc.EncodeAggregator, LaunchAggregator)
        assert isinstance(service("encode"), OffloadService)
        assert service_aggregator("encode") in _AGGREGATORS
        assert "encode" in offload_services()
        assert "encode.launches" in offload_perf_dump()
        for name in (AggTicket, _AggGroup, drain_all_aggregators,
                     register_service):
            assert callable(name)
        # the codec imports only what its aggregators subclass and
        # annotate with; the runtime-wide names stay in the runtime
        for name in ("_AGGREGATORS", "drain_all_aggregators",
                     "offload_perf_dump", "service_aggregator"):
            assert name not in vars(mc), name

    def test_every_service_aggregator_subclasses_the_runtime_base(self):
        from ceph_tpu.codec.matrix_codec import (
            DecodeAggregator,
            EncodeAggregator,
            VerifyAggregator,
        )
        from ceph_tpu.compressor.device import CompressAggregator
        from ceph_tpu.ops.offload_runtime import LaunchAggregator

        for cls in (EncodeAggregator, DecodeAggregator, VerifyAggregator,
                    ChecksumAggregator, CompressAggregator):
            assert issubclass(cls, LaunchAggregator), cls

    def test_drain_all_reaches_the_new_services(self):
        from ceph_tpu.ops.offload_runtime import drain_all_aggregators

        agg = default_csum_aggregator()
        blocks = np.arange(2 * 512, dtype=np.uint8).reshape(2, 512) % 251
        ticket = agg.submit_blocks(blocks)
        drain_all_aggregators()
        assert agg.pending() == 0
        assert np.array_equal(
            np.asarray(ticket.result()), crc32c_host_rows(blocks)
        )


def _rs(k=4, m=2):
    from ceph_tpu.codec import ErasureCodeTpuRs

    ec = ErasureCodeTpuRs()
    ec.init({"k": str(k), "m": str(m)})
    return ec


def _split(n: int) -> list[int]:
    """Ticket sizes summing to `n`: two tickets (one window) when n > 1."""
    return [n] if n == 1 else [n // 2, n - n // 2]


class TestOneLaunchPath:
    """The runtime's single launch path: windowed groups pad to the
    static `_pad_target` bucket and stay byte-identical to the host
    oracle on both sides of every bucket edge, and every service's
    in-flight HBM pool reads zero after `drain()` in both modes."""

    @pytest.mark.parametrize("stripes", [1, 3, 63, 64, 65, 129])
    def test_windowed_encode_static_bucket_byte_identical(self, stripes):
        from ceph_tpu.codec.matrix_codec import EncodeAggregator

        ec = _rs()
        rng = np.random.default_rng(stripes)
        agg = EncodeAggregator(window=2, max_bytes=1 << 30)
        batches = [
            rng.integers(0, 256, (s, 4, 256), dtype=np.uint8)
            for s in _split(stripes)
        ]
        tickets = [agg.submit(ec, b) for b in batches]
        agg.flush()
        for b, t in zip(batches, tickets):
            assert np.array_equal(np.asarray(t), ec.encode_array_host(b))
        assert agg.perf.get("launches") == 1
        assert agg.perf.get("pad_stripes") == (
            agg._pad_target(stripes) - stripes
        )

    @pytest.mark.parametrize("stripes", [1, 3, 63, 64, 65, 129])
    def test_windowed_decode_static_bucket_byte_identical(self, stripes):
        from ceph_tpu.codec.matrix_codec import DecodeAggregator

        ec = _rs()
        rng = np.random.default_rng(100 + stripes)
        erasures = [1, 4]
        idx = ec.decode_index(erasures)
        agg = DecodeAggregator(window=2, max_bytes=1 << 30)
        fulls = []
        for s in _split(stripes):
            data = rng.integers(0, 256, (s, 4, 256), dtype=np.uint8)
            fulls.append(
                np.concatenate([data, ec.encode_array_host(data)], axis=1)
            )
        tickets = [agg.submit(ec, erasures, f[:, idx, :]) for f in fulls]
        agg.flush()
        for f, t in zip(fulls, tickets):
            assert np.array_equal(np.asarray(t), f[:, erasures, :])
            assert np.array_equal(
                np.asarray(t), ec.decode_array_host(erasures, f[:, idx, :])
            )
        assert agg.perf.get("launches") == 1
        assert agg.perf.get("pad_stripes") == (
            agg._pad_target(stripes) - stripes
        )

    @pytest.mark.parametrize("window", [0, 4], ids=["immediate", "windowed"])
    @pytest.mark.parametrize("name", ["encode", "decode", "verify", "csum"])
    def test_inflight_pool_reads_zero_after_drain(self, name, window):
        from ceph_tpu.codec.matrix_codec import (
            DecodeAggregator,
            EncodeAggregator,
            VerifyAggregator,
        )
        from ceph_tpu.common.mempool import ledger

        ec = _rs()
        rng = np.random.default_rng(7)
        data = rng.integers(0, 256, (2, 4, 512), dtype=np.uint8)
        full = np.concatenate([data, ec.encode_array_host(data)], axis=1)
        erasures = [0, 5]
        cls, submit = {
            "encode": (EncodeAggregator, lambda a: a.submit(ec, data)),
            "decode": (
                DecodeAggregator,
                lambda a: a.submit(
                    ec, erasures, full[:, ec.decode_index(erasures), :]
                ),
            ),
            "verify": (VerifyAggregator, lambda a: a.submit(ec, full)),
            "csum": (
                ChecksumAggregator,
                lambda a: a.submit_blocks(data.reshape(2, -1)),
            ),
        }[name]
        agg = cls(window=window, pipeline_depth=8)
        led = ledger()
        tickets = [submit(agg) for _ in range(4)]
        # launched and unsettled: the pool holds the outputs in flight
        assert led.current_bytes(agg.MEM_POOL) > 0, name
        agg.drain()
        assert led.current_bytes(agg.MEM_POOL) == 0, led.reconcile()
        assert not agg._live and agg.pending() == 0
        for t in tickets:
            t.result()


class TestDeviceCrc32c:
    @pytest.mark.parametrize("L", [1, 4, 63, 64, 512, 1000, 4096])
    def test_device_digests_byte_identical_across_lengths(self, L):
        rng = np.random.default_rng(L)
        blocks = rng.integers(0, 256, (5, L), dtype=np.uint8)
        got = np.asarray(crc32c_device(blocks))
        assert np.array_equal(got, crc32c_host_rows(blocks)), L

    def test_host_rows_is_the_utils_oracle(self):
        rng = np.random.default_rng(7)
        blocks = rng.integers(0, 256, (3, 200), dtype=np.uint8)
        want = [crc32c(row.tobytes()) for row in blocks]
        assert list(crc32c_host_rows(blocks)) == want

    def test_checksum_blocks_matches_host_below_and_above_threshold(self):
        rng = np.random.default_rng(11)
        small = [rng.bytes(100) for _ in range(3)]  # host loop
        assert checksum_blocks(small) == [crc32c(c) for c in small]
        # ragged population: three length groups, one above threshold
        big = [rng.bytes(4096) for _ in range(6)]
        mixed = big + [rng.bytes(1000), b"", rng.bytes(1000)]
        assert sum(len(c) for c in mixed) >= CSUM_OFFLOAD_MIN_BYTES
        assert checksum_blocks(mixed) == [crc32c(c) for c in mixed]

    def test_fault_injected_launch_falls_back_byte_identical(self):
        agg = ChecksumAggregator(window=4)
        rng = np.random.default_rng(13)
        blocks = rng.integers(0, 256, (4, 4096), dtype=np.uint8)
        global_injector().inject("codec.launch", 5, hits=1)
        fb0 = agg.perf.get("host_fallbacks")
        ticket = agg.submit_blocks(blocks)
        assert np.array_equal(
            np.asarray(ticket.result()), crc32c_host_rows(blocks)
        )
        assert agg.perf.get("host_fallbacks") == fb0 + 1
        assert device_guard().degraded  # the failed launch marked it

    def test_degraded_bypass_stays_byte_identical(self):
        device_guard().configure(probe_interval_ms=10 * 60 * 1000)
        device_guard().mark_degraded("test: forced")
        try:
            rng = np.random.default_rng(17)
            chunks = [rng.bytes(4096) for _ in range(8)]
            assert checksum_blocks(chunks) == [crc32c(c) for c in chunks]
        finally:
            device_guard().mark_healthy()

    def test_matrix_cache_is_bounded(self):
        from ceph_tpu.ops import checksum_offload as co

        for L in range(1, 2 * co._MATRIX_CACHE_CAP):
            co._contribution_matrix(L)
            co._zero_const(L)
        assert len(co._HOST_MATRICES) <= co._MATRIX_CACHE_CAP
        assert len(co._CONSTS) <= co._MATRIX_CACHE_CAP
