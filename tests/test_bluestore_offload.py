"""BlueStore device-offload integration (ISSUE 20): per-block checksums
routed through the ChecksumAggregator (`bluestore_csum_offload`), the
EC-transaction csum fusion seam (`Op.csums`), and the identical-content
overwrite skip.  Every path must stay byte-identical to the host
`utils/crc32c` baseline — the device digests ARE the stored csums, so a
divergence would surface as EIO on the next read."""

import os

import numpy as np
import pytest

from ceph_tpu.common.fault_injector import global_injector
from ceph_tpu.ops.guard import device_guard
from ceph_tpu.os import BlueStore, StoreError, Transaction
from ceph_tpu.os.bluestore import BLOCK
from ceph_tpu.utils.crc32c import crc32c


@pytest.fixture(autouse=True)
def _clean_state():
    yield
    global_injector().clear()
    device_guard().mark_healthy()
    device_guard().configure(timeout_ms=20000, probe_interval_ms=2000)


def mko(path=None, **kw):
    s = BlueStore(str(path) if path else None, csum_offload=True, **kw)
    s.mount()
    if "c" not in s.list_collections():
        s.queue_transaction(Transaction().create_collection("c"))
    return s


class TestOffloadWriteRead:
    @pytest.mark.parametrize(
        "nbytes",
        [100, BLOCK, BLOCK + 1, 4 * BLOCK, 8 * BLOCK + 1000, 10000],
    )
    def test_round_trip_across_sizes_and_ragged_tails(self, nbytes):
        s = mko()
        data = os.urandom(nbytes)
        s.queue_transaction(Transaction().write("c", "o", 0, data))
        assert s.read("c", "o") == data
        # the csums it stored are the host oracle's, block by block
        on = s._peek_onode("c", "o")
        for bidx, (poff, crc, clen) in on.blocks.items():
            stored = s._block_read(poff, clen if clen else BLOCK)
            if not clen:
                stored = stored.ljust(BLOCK, b"\x00")
            assert crc32c(stored) == crc, bidx
        s.umount()

    def test_offload_batches_the_write_path(self):
        """A large aligned write must reach the csum service (launches
        advance) and still verify on read back through the same path."""
        from ceph_tpu.ops.checksum_offload import default_csum_aggregator

        agg = default_csum_aggregator()
        s = mko()
        l0 = agg.perf.get("launches")
        data = os.urandom(16 * BLOCK)  # over CSUM_OFFLOAD_MIN_BYTES
        s.queue_transaction(Transaction().write("c", "big", 0, data))
        assert agg.perf.get("launches") > l0
        assert s.read("c", "big") == data
        s.umount()

    def test_fault_injected_write_and_read_stay_identical(self):
        s = mko()
        data = os.urandom(8 * BLOCK)
        global_injector().inject("codec.launch", 5, hits=2)
        s.queue_transaction(Transaction().write("c", "o", 0, data))
        assert s.read("c", "o") == data  # read-verify under faults too
        on = s._peek_onode("c", "o")
        for bidx, (poff, crc, clen) in on.blocks.items():
            assert crc32c(s._block_read(poff, BLOCK).ljust(BLOCK, b"\x00")) \
                == crc, bidx
        s.umount()

    def test_degraded_bypass_stays_identical(self):
        device_guard().configure(probe_interval_ms=10 * 60 * 1000)
        device_guard().mark_degraded("test: forced")
        s = mko()
        data = os.urandom(8 * BLOCK)
        s.queue_transaction(Transaction().write("c", "o", 0, data))
        assert s.read("c", "o") == data
        s.umount()

    def test_corrupt_block_is_still_eio_with_offload(self, tmp_path):
        s = mko(tmp_path / "b")
        data = os.urandom(4 * BLOCK)
        s.queue_transaction(Transaction().write("c", "o", 0, data))
        poff, _crc, _clen = s._peek_onode("c", "o").blocks[2]
        s.umount()
        with open(tmp_path / "b" / "block", "r+b") as f:
            f.seek(poff + 5)
            b = f.read(1)
            f.seek(poff + 5)
            f.write(bytes([b[0] ^ 0xFF]))
        s2 = mko(tmp_path / "b")
        with pytest.raises(StoreError) as ei:
            s2.read("c", "o")
        assert ei.value.errno == -5
        assert "block 2" in str(ei.value)  # the batched verify names it
        s2.umount()

    def test_set_csum_offload_toggles_live(self):
        s = BlueStore(None)
        s.mount()
        s.queue_transaction(Transaction().create_collection("c"))
        assert not s._csum_offload
        s.set_csum_offload(True)
        assert s._csum_offload
        data = os.urandom(8 * BLOCK)
        s.queue_transaction(Transaction().write("c", "o", 0, data))
        s.set_csum_offload(False)
        assert s.read("c", "o") == data  # host verify of offload csums
        s.umount()


class TestCsumSkip:
    # 4 blocks take the deferred path, 20 (over DEFERRED_MAX) the COW path
    @pytest.mark.parametrize("nblocks", [4, 20])
    def test_identical_overwrite_skips_recompute(self, nblocks):
        s = mko()
        data = os.urandom(nblocks * BLOCK)
        s.queue_transaction(Transaction().write("c", "o", 0, data))
        skips0 = s.csum_compute_skips
        # same content again: every whole block below size skips
        s.queue_transaction(Transaction().write("c", "o", 0, data))
        assert s.csum_compute_skips == skips0 + nblocks
        assert s.read("c", "o") == data
        s.umount()

    def test_changed_block_is_not_skipped(self):
        s = mko()
        data = bytearray(os.urandom(4 * BLOCK))
        s.queue_transaction(Transaction().write("c", "o", 0, bytes(data)))
        skips0 = s.csum_compute_skips
        data[BLOCK + 7] ^= 0xFF
        s.queue_transaction(Transaction().write("c", "o", 0, bytes(data)))
        # blocks 0, 2, 3 identical -> skipped; block 1 changed -> not
        assert s.csum_compute_skips == skips0 + 3
        assert s.read("c", "o") == bytes(data)
        s.umount()

    def test_tail_straddling_block_never_skips(self):
        """A block straddling o.size holds stale stored bytes past the
        logical tail; an identical-content overwrite that also EXTENDS
        the object would expose them if the old csum were reused."""
        s = mko()
        data = os.urandom(2 * BLOCK + 1000)  # block 2 straddles size
        s.queue_transaction(Transaction().write("c", "o", 0, data))
        skips0 = s.csum_compute_skips
        # rewrite the same bytes over the straddling block
        s.queue_transaction(
            Transaction().write("c", "o", 2 * BLOCK, data[2 * BLOCK:])
        )
        assert s.csum_compute_skips == skips0  # no skip for the tail
        # now extend past it: the recomputed csum covers the zeroed tail
        s.queue_transaction(
            Transaction().write("c", "o", 3 * BLOCK, b"x" * 10)
        )
        want = data + b"\x00" * (3 * BLOCK - len(data)) + b"x" * 10
        assert s.read("c", "o") == want
        s.umount()


class TestEcFusion:
    def test_fused_csums_are_trusted_for_aligned_raw_stores(self):
        s = mko()
        data = os.urandom(3 * BLOCK)
        pre = [crc32c(data[i * BLOCK:(i + 1) * BLOCK]) for i in range(3)]
        fused0 = s.csum_fused_blocks
        s.queue_transaction(Transaction().write("c", "o", 0, data, csums=pre))
        assert s.csum_fused_blocks == fused0 + 3
        assert s.read("c", "o") == data
        s.umount()

    def test_ticket_like_csums_resolve_via_result(self):
        class FakeTicket:
            def __init__(self, vals):
                self._vals = np.asarray(vals, dtype=np.uint32)

            def result(self):
                return self._vals

        s = mko()
        data = os.urandom(2 * BLOCK)
        pre = FakeTicket(
            [crc32c(data[:BLOCK]), crc32c(data[BLOCK:])]
        )
        fused0 = s.csum_fused_blocks
        s.queue_transaction(Transaction().write("c", "o", 0, data, csums=pre))
        assert s.csum_fused_blocks == fused0 + 2
        assert s.read("c", "o") == data
        s.umount()

    def test_wrong_fused_digest_surfaces_as_eio(self):
        """The fused digest IS the stored csum: a wrong one must fail
        the next read loudly, never silently pass."""
        s = mko()
        data = os.urandom(BLOCK)
        s.queue_transaction(
            Transaction().write("c", "o", 0, data, csums=[0xDEADBEEF])
        )
        with pytest.raises(StoreError) as ei:
            s.read("c", "o")
        assert ei.value.errno == -5
        s.umount()

    def test_unaligned_writes_never_trust_fused_digests(self):
        s = mko()
        data = os.urandom(BLOCK + 100)  # ragged: csums must be ignored
        fused0 = s.csum_fused_blocks
        s.queue_transaction(
            Transaction().write("c", "o", 0, data, csums=[0xBAD, 0xBAD])
        )
        assert s.csum_fused_blocks == fused0
        assert s.read("c", "o") == data  # real csums computed + verified
        s.umount()

    def test_wire_encode_drops_csums(self):
        """`Op.csums` is a process-local fusion seam, not wire state: a
        transaction that crosses the messenger re-computes csums on the
        applying store, so a stale fused digest can never ride a
        sub-write to a remote shard."""
        data = os.urandom(2 * BLOCK)
        t = Transaction().write("c", "o", 0, data, csums=[0xBAD, 0xBAD])
        t2 = Transaction.frombytes(t.tobytes())
        assert t2.ops[0].csums is None
        s = mko()
        s.queue_transaction(t2)
        assert s.read("c", "o") == data  # honest csums, verified clean
        s.umount()
