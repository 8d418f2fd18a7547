"""BlueStore-specific tests: allocator, WAL replay, per-block checksums,
torn-commit atomicity, COW vs deferred write classification.

Models the crash/corruption tiers of the reference's
src/test/objectstore/store_test.cc (BlueStore sections) — the generic
store matrix runs in test_objectstore.py.
"""

import asyncio
import os
import random
import threading

import pytest

from ceph_tpu.common import tracer
from ceph_tpu.os import BlueStore, StoreError, Transaction, make_store
from ceph_tpu.os.bluestore import BLOCK, DEFERRED_MAX, INITIAL_BLOCKS, SimulatedCrash


def mk(path):
    s = BlueStore(str(path))
    s.mount()
    return s


class TestPersistence:
    def test_everything_survives_remount(self, tmp_path):
        s = mk(tmp_path / "b")
        t = Transaction().create_collection("c")
        t.write("c", "o", 0, b"hello world" * 500)
        t.setattr("c", "o", "k", b"v")
        t.omap_setkeys("c", "o", {"m": b"n"})
        s.queue_transaction(t)
        s.umount()

        s2 = mk(tmp_path / "b")
        assert s2.read("c", "o") == b"hello world" * 500
        assert s2.getattr("c", "o", "k") == b"v"
        assert s2.omap_get("c", "o") == {"m": b"n"}
        assert s2.count_objects("c") == 1
        s2.umount()

    def test_freelist_rebuild_reuses_removed_space(self, tmp_path):
        s = mk(tmp_path / "b")
        s.queue_transaction(Transaction().create_collection("c"))
        t = Transaction()
        t.write("c", "big", 0, b"x" * (100 * BLOCK))
        s.queue_transaction(t)
        free_with_obj = s.alloc.num_free()
        s.queue_transaction(Transaction().remove("c", "big"))
        assert s.alloc.num_free() == free_with_obj + 100
        s.umount()
        # Mount rebuilds the free list from onodes: the removed object's
        # blocks are free again (FreelistManager rebuild semantics).
        s2 = mk(tmp_path / "b")
        assert s2.alloc.num_free() >= free_with_obj + 100
        s2.umount()

    def test_device_grows_past_initial_capacity(self, tmp_path):
        s = mk(tmp_path / "b")
        s.queue_transaction(Transaction().create_collection("c"))
        big = os.urandom((INITIAL_BLOCKS + 50) * BLOCK)
        t = Transaction()
        t.write("c", "huge", 0, big)
        s.queue_transaction(t)
        assert s.read("c", "huge") == big
        s.umount()
        s2 = mk(tmp_path / "b")
        assert s2.read("c", "huge") == big
        s2.umount()


class TestWritePaths:
    def test_small_overwrite_is_deferred_in_place(self, tmp_path):
        s = mk(tmp_path / "b")
        s.queue_transaction(Transaction().create_collection("c"))
        t = Transaction()
        t.write("c", "o", 0, b"A" * (4 * BLOCK))
        s.queue_transaction(t)
        before = dict(s._peek_onode("c", "o").blocks)
        t = Transaction()
        t.write("c", "o", 100, b"B" * 200)  # small overwrite -> WAL, no move
        s.queue_transaction(t)
        after = s._peek_onode("c", "o").blocks
        assert {b: pc[0] for b, pc in after.items()} == {
            b: pc[0] for b, pc in before.items()
        }
        assert after[0][1] != before[0][1]  # crc updated
        data = s.read("c", "o")
        assert data[100:300] == b"B" * 200 and data[:100] == b"A" * 100
        s.umount()

    def test_large_overwrite_is_cow(self, tmp_path):
        s = mk(tmp_path / "b")
        s.queue_transaction(Transaction().create_collection("c"))
        n = DEFERRED_MAX // BLOCK + 4
        t = Transaction()
        t.write("c", "o", 0, b"A" * (n * BLOCK))
        s.queue_transaction(t)
        before = {b: pc[0] for b, pc in s._peek_onode("c", "o").blocks.items()}
        t = Transaction()
        t.write("c", "o", 0, b"B" * (n * BLOCK))  # big overwrite -> new blocks
        s.queue_transaction(t)
        after = {b: pc[0] for b, pc in s._peek_onode("c", "o").blocks.items()}
        assert all(before[b] != after[b] for b in before)
        assert s.read("c", "o") == b"B" * (n * BLOCK)
        s.umount()

    def test_write_then_clone_same_txn_sees_staged_bytes(self, tmp_path):
        s = mk(tmp_path / "b")
        s.queue_transaction(Transaction().create_collection("c"))
        t = Transaction()
        t.write("c", "src", 0, b"fresh" * 1000)
        t.clone("c", "src", "dst")
        s.queue_transaction(t)
        assert s.read("c", "dst") == b"fresh" * 1000
        s.umount()

    def test_remove_is_idempotent(self, tmp_path):
        # Recovery's push handler removes unconditionally before recreate.
        s = mk(tmp_path / "b")
        s.queue_transaction(Transaction().create_collection("c"))
        s.queue_transaction(Transaction().remove("c", "never-existed"))
        s.umount()

    def test_remove_then_recreate_in_one_txn_starts_empty(self, tmp_path):
        """The staged deletion must not resurrect the old onode from the KV
        DB (the _apply_pushes replace-stale-copy pattern)."""
        s = mk(tmp_path / "b")
        s.queue_transaction(Transaction().create_collection("c"))
        t = Transaction()
        t.write("c", "o", 0, b"X" * 9000)
        s.queue_transaction(t)
        free_before = s.alloc.num_free()
        t = Transaction()
        t.remove("c", "o")
        t.touch("c", "o")
        t.write("c", "o", 0, b"new")
        s.queue_transaction(t)
        assert s.read("c", "o") == b"new"
        assert s.stat("c", "o") == 3
        assert s.count_objects("c") == 1
        # old blocks freed, new ones allocated: net free grows
        assert s.alloc.num_free() > free_before
        s.umount()
        s2 = mk(tmp_path / "b")
        assert s2.read("c", "o") == b"new"
        s2.umount()

    def test_truncate_scrubs_kept_partial_block_cross_block(self, tmp_path):
        """Stale pre-truncate bytes must never reappear, even when the
        extension write lands in a DIFFERENT block."""
        s = mk(tmp_path / "b")
        s.queue_transaction(Transaction().create_collection("c"))
        t = Transaction()
        t.write("c", "o", 0, b"X" * 100)
        s.queue_transaction(t)
        s.queue_transaction(Transaction().truncate("c", "o", 10))
        t = Transaction()
        t.write("c", "o", 2 * BLOCK, b"Y")  # extend via another block
        s.queue_transaction(t)
        data = s.read("c", "o")
        assert data[:10] == b"X" * 10
        assert data[10:100] == b"\x00" * 90  # not stale Xs
        assert data[2 * BLOCK] == ord("Y")
        s.umount()
        s2 = mk(tmp_path / "b")  # and it holds across remount
        assert s2.read("c", "o")[10:100] == b"\x00" * 90
        s2.umount()

    def test_extend_after_truncate_zero_fills_gap(self, tmp_path):
        s = mk(tmp_path / "b")
        s.queue_transaction(Transaction().create_collection("c"))
        t = Transaction()
        t.write("c", "o", 0, b"Z" * 3000)
        s.queue_transaction(t)
        s.queue_transaction(Transaction().truncate("c", "o", 1000))
        t = Transaction()
        t.write("c", "o", 2000, b"E" * 100)
        s.queue_transaction(t)
        data = s.read("c", "o")
        # bytes beyond the truncate point must come back as zeros, not the
        # stale "Z"s still present in the physical block
        assert data[:1000] == b"Z" * 1000
        assert data[1000:2000] == b"\x00" * 1000
        assert data[2000:] == b"E" * 100
        s.umount()


def _payload(seed, n):
    return random.Random(seed).randbytes(n)


def _corrupt(path, poff):
    with open(path / "block", "r+b") as f:
        f.seek(poff + 17)
        byte = f.read(1)
        f.seek(poff + 17)
        f.write(bytes([byte[0] ^ 0xFF]))


def _layer_bytes(name):
    return tracer.layer_totals(threading.get_ident()).get(name, {}).get("bytes", 0)


class TestWholeBlockWrites:
    """Blocks a write covers entirely are written from the payload with
    no read-back; only partial head and tail blocks read-modify-write."""

    # 4 blocks over mapped ones ride the deferred WAL; 20 go COW
    PATHS = pytest.mark.parametrize("nblocks,cow", [(4, False), (20, True)],
                                    ids=["deferred", "cow"])

    @PATHS
    def test_aligned_overwrite_reads_no_block_back(self, tmp_path, nblocks, cow):
        s = mk(tmp_path / "b")
        s.queue_transaction(Transaction().create_collection("c"))
        s.queue_transaction(
            Transaction().write("c", "o", 0, _payload(1, 24 * BLOCK)))
        before = {b: e[0] for b, e in s._peek_onode("c", "o").blocks.items()}
        reads = []
        real_read = s._block_read
        s._block_read = lambda poff, n: reads.append(poff) or real_read(poff, n)
        new = _payload(2, nblocks * BLOCK)
        s.queue_transaction(Transaction().write("c", "o", 4 * BLOCK, new))
        assert reads == []
        s._block_read = real_read
        after = {b: e[0] for b, e in s._peek_onode("c", "o").blocks.items()}
        moved = [b for b in before if before[b] != after[b]]
        assert moved == (list(range(4, 4 + nblocks)) if cow else [])
        want = _payload(1, 24 * BLOCK)
        want = want[: 4 * BLOCK] + new + want[(4 + nblocks) * BLOCK:]
        assert s.read("c", "o") == want
        s.umount()
        s2 = mk(tmp_path / "b")
        assert s2.read("c", "o") == want
        s2.umount()

    @PATHS
    def test_unaligned_write_keeps_surrounding_bytes(self, tmp_path, nblocks, cow):
        """Partial head, whole middle, partial tail: the bytes around the
        write survive on both paths."""
        s = mk(tmp_path / "b")
        s.queue_transaction(Transaction().create_collection("c"))
        old = _payload(3, 24 * BLOCK)
        s.queue_transaction(Transaction().write("c", "o", 0, old))
        off = 3 * BLOCK + 100
        new = _payload(4, nblocks * BLOCK)  # ends 100 B into its last block
        before = {b: e[0] for b, e in s._peek_onode("c", "o").blocks.items()}
        s.queue_transaction(Transaction().write("c", "o", off, new))
        after = {b: e[0] for b, e in s._peek_onode("c", "o").blocks.items()}
        moved = [b for b in before if before[b] != after[b]]
        assert moved == (list(range(3, 4 + nblocks)) if cow else [])
        want = old[:off] + new + old[off + len(new):]
        assert s.read("c", "o") == want
        s.umount()
        s2 = mk(tmp_path / "b")
        assert s2.read("c", "o") == want
        s2.umount()

    @pytest.mark.parametrize("same", [False, True], ids=["new-bytes", "same-bytes"])
    @PATHS
    def test_whole_block_overwrite_over_corrupt_block(
        self, tmp_path, nblocks, cow, same
    ):
        """A whole-block overwrite never reads the old block, so a copy
        rotted on disk does not fail it.  With the same bytes the rotted
        block is still a skip candidate (its digest matches): its stored
        copy no longer compares equal, so it is rewritten, not skipped."""
        s = mk(tmp_path / "b")
        s.queue_transaction(Transaction().create_collection("c"))
        old = _payload(5, nblocks * BLOCK)
        s.queue_transaction(Transaction().write("c", "o", 0, old))
        poff = s._peek_onode("c", "o").blocks[1][0]
        s.umount()
        _corrupt(tmp_path / "b", poff)
        s = mk(tmp_path / "b")
        with pytest.raises(StoreError):
            s.read("c", "o")
        new = old if same else _payload(6, nblocks * BLOCK)
        skips0 = s.csum_compute_skips
        s.queue_transaction(Transaction().write("c", "o", 0, new))
        assert s.csum_compute_skips - skips0 == (nblocks - 1 if same else 0)
        assert s.read("c", "o") == new
        s.umount()
        s2 = mk(tmp_path / "b")
        assert s2.read("c", "o") == new
        s2.umount()

    def test_cow_run_of_fresh_blocks_is_one_block_file_write(self, tmp_path):
        s = mk(tmp_path / "b")
        s.queue_transaction(Transaction().create_collection("c"))
        writes = []
        real_write = s._block_write
        s._block_write = lambda poff, d: writes.append((poff, len(d))) or real_write(poff, d)
        data = _payload(7, 30 * BLOCK + 5)
        s.queue_transaction(Transaction().write("c", "o", 0, data))
        blocks = s._peek_onode("c", "o").blocks
        assert writes == [(blocks[0][0], 31 * BLOCK)]
        assert [blocks[b][0] for b in range(31)] == [
            blocks[0][0] + b * BLOCK for b in range(31)
        ]
        assert s.read("c", "o") == data
        s.umount()

    def test_cow_runs_end_at_compressed_blocks(self, tmp_path):
        """A compressed form is shorter than its slot: the run it is in
        ends with it, and every block reads back after a remount."""
        s = BlueStore(str(tmp_path / "b"), compression="zlib")
        s.mount()
        s.queue_transaction(Transaction().create_collection("c"))
        writes = []
        real_write = s._block_write
        s._block_write = lambda poff, d: writes.append(poff) or real_write(poff, d)
        # random (stored raw) and zero (stored compressed) blocks in turn
        data = b"".join(
            _payload(b, BLOCK) if b % 2 == 0 else bytes(BLOCK) for b in range(20)
        )
        s.queue_transaction(Transaction().write("c", "o", 0, data))
        clens = [s._peek_onode("c", "o").blocks[b][2] for b in range(20)]
        assert [c > 0 for c in clens] == [b % 2 == 1 for b in range(20)]
        assert len(writes) == 10
        s.umount()
        s2 = BlueStore(str(tmp_path / "b"), compression="zlib")
        s2.mount()
        assert s2.read("c", "o") == data
        s2.umount()

    @pytest.mark.parametrize(
        "off,length,whole,readback",
        [(0, 128 * BLOCK, 128 * BLOCK, 0), (0, BLOCK + 100, BLOCK, BLOCK)],
        ids=["512KiB-aligned", "4KiB+100B"],
    )
    def test_engagement_counters(self, off, length, whole, readback):
        s = BlueStore()
        s.mount()
        s.queue_transaction(Transaction().create_collection("c"))
        s.queue_transaction(
            Transaction().write("c", "o", 0, _payload(8, 128 * BLOCK)))
        w0 = _layer_bytes("store.write.whole_blocks")
        r0 = _layer_bytes("store.write.readback_blocks")
        s.queue_transaction(
            Transaction().write("c", "o", off, _payload(9, length)))
        totals = tracer.layer_totals(threading.get_ident())
        assert "store.write.whole_blocks" in totals
        assert _layer_bytes("store.write.whole_blocks") - w0 == whole
        assert _layer_bytes("store.write.readback_blocks") - r0 == readback
        s.umount()


class TestChecksums:
    def test_corrupt_extent_surfaces_as_eio(self, tmp_path):
        s = mk(tmp_path / "b")
        s.queue_transaction(Transaction().create_collection("c"))
        t = Transaction()
        t.write("c", "o", 0, b"precious" * 2048)
        s.queue_transaction(t)
        poff = s._peek_onode("c", "o").blocks[1][0]
        s.umount()

        # Flip one byte inside the second block on "disk".
        with open(tmp_path / "b" / "block", "r+b") as f:
            f.seek(poff + 17)
            byte = f.read(1)
            f.seek(poff + 17)
            f.write(bytes([byte[0] ^ 0xFF]))

        s2 = mk(tmp_path / "b")
        with pytest.raises(StoreError) as ei:
            s2.read("c", "o")
        assert ei.value.errno == -5  # EIO, not silent corruption
        # The undamaged first block is still readable.
        assert s2.read("c", "o", 0, 100) == (b"precious" * 2048)[:100]
        s2.umount()


class TestCrashWindows:
    def test_wal_replay_after_crash_between_commit_and_apply(self, tmp_path):
        s = mk(tmp_path / "b")
        s.queue_transaction(Transaction().create_collection("c"))
        t = Transaction()
        t.write("c", "o", 0, b"A" * (2 * BLOCK))
        s.queue_transaction(t)

        s._crash_point = "after_commit"
        t = Transaction()
        t.write("c", "o", 10, b"NEW")  # deferred path
        with pytest.raises(SimulatedCrash):
            s.queue_transaction(t)
        s._block_f.close()
        s.db.close()  # drop without applying the WAL

        s2 = mk(tmp_path / "b")  # mount replays the WAL
        data = s2.read("c", "o")
        assert data[10:13] == b"NEW" and data[:10] == b"A" * 10
        # replay consumed the records
        assert list(s2.db.iterate("W")) == []
        s2.umount()

    def test_torn_kv_batch_discards_whole_txn(self, tmp_path):
        n = DEFERRED_MAX + BLOCK  # force the COW path: the KV log's tail is
        # then exactly the metadata batch (no trailing WAL-cleanup records)
        s = mk(tmp_path / "b")
        s.queue_transaction(Transaction().create_collection("c"))
        t = Transaction()
        t.write("c", "o", 0, b"old" * n)
        t.setattr("c", "o", "ver", b"1")
        s.queue_transaction(t)
        t = Transaction()
        t.write("c", "o", 0, b"new" * n)
        t.setattr("c", "o", "ver", b"2")
        s.queue_transaction(t)
        s.umount()

        # Tear the tail of the KV log: the last committed batch loses its
        # crc -> replay must drop it entirely (no half-applied metadata).
        kv = tmp_path / "b" / "kv"
        with open(kv, "r+b") as f:
            f.truncate(os.path.getsize(kv) - 3)

        s2 = mk(tmp_path / "b")
        assert s2.read("c", "o") == b"old" * n
        assert s2.getattr("c", "o", "ver") == b"1"
        s2.umount()

    def test_unreferenced_direct_writes_are_invisible(self, tmp_path):
        """A crash after direct data writes but before the KV commit leaves
        only unreferenced blocks — old object state intact."""
        s = mk(tmp_path / "b")
        s.queue_transaction(Transaction().create_collection("c"))
        t = Transaction()
        t.write("c", "o", 0, b"old" * 2000)
        s.queue_transaction(t)
        old_free = s.alloc.num_free()

        real_apply = s.db.apply_batch

        def die(_ops):
            raise SimulatedCrash("before_commit")

        s.db.apply_batch = die
        t = Transaction()
        t.write("c", "o", 0, os.urandom(DEFERRED_MAX + BLOCK))  # COW path
        with pytest.raises(SimulatedCrash):
            s.queue_transaction(t)
        s.db.apply_batch = real_apply
        s._block_f.close()
        s.db.close()

        s2 = mk(tmp_path / "b")
        assert s2.read("c", "o") == b"old" * 2000
        # the orphaned blocks were reclaimed by the free-list rebuild
        assert s2.alloc.num_free() >= old_free
        s2.umount()


class TestOsdIntegration:
    def test_store_factory_selects_backend(self, tmp_path):
        from ceph_tpu.common.config import Config
        from ceph_tpu.os import FileStore, MemStore

        assert isinstance(make_store(Config(env=False)), MemStore)
        c = Config({"osd_objectstore": "bluestore", "osd_data": str(tmp_path / "d")}, env=False)
        assert isinstance(make_store(c), BlueStore)
        c = Config({"osd_objectstore": "filestore", "osd_data": str(tmp_path / "f")}, env=False)
        assert isinstance(make_store(c), FileStore)
        with pytest.raises(ValueError):
            make_store(Config({"osd_objectstore": "filestore"}, env=False))

    def test_ec_cluster_on_bluestore_survives_osd_restart(self, tmp_path):
        """EC I/O over OSDs configured with osd_objectstore=bluestore: data
        survives an OSD restart from its on-disk store and recovery
        converges — BlueStore as the OSD's store end to end."""
        from ceph_tpu.client import Rados
        from ceph_tpu.common.config import Config
        from ceph_tpu.mon import MonMap, Monitor
        from ceph_tpu.osd.osd import OSD

        from test_cluster import wait_until
        from test_mon import free_port_addrs

        def bconf(i):
            return Config(
                {
                    "name": f"osd.{i}",
                    "osd_heartbeat_interval": 0.1,
                    "osd_heartbeat_grace": 0.6,
                    "osd_objectstore": "bluestore",
                    "osd_data": str(tmp_path / f"osd{i}"),
                },
                env=False,
            )

        async def run():
            monmap = MonMap(addrs=free_port_addrs(1))
            mons = [Monitor(n, monmap, election_timeout=0.3) for n in monmap.addrs]
            for m in mons:
                await m.start()
                await m.wait_for_quorum()
            osds = [OSD(i, monmap, conf=bconf(i)) for i in range(3)]
            for o in osds:
                assert isinstance(o.store, BlueStore)
                await o.start()
            for o in osds:
                await o.wait_for_up()

            client = Rados(monmap)
            await client.connect()
            rv, rs, _ = await client.mon_command(
                {
                    "prefix": "osd erasure-code-profile set",
                    "name": "bs21",
                    "profile": ["k=2", "m=1", "plugin=tpu"],
                }
            )
            assert rv == 0, rs
            await client.pool_create("bsec", "erasure", profile="bs21", pg_num=2)
            ioctx = await client.open_ioctx("bsec")
            payload = bytes((i * 13 + 5) % 256 for i in range(3 * 8192 + 77))
            await ioctx.write_full("obj", payload)
            assert await ioctx.read("obj") == payload

            # Restart osd.2 from its on-disk BlueStore directory.
            await osds[2].stop()
            revived = OSD(2, monmap, conf=bconf(2))
            await revived.start()
            await revived.wait_for_up()
            osds[2] = revived

            def clean():
                return all(
                    pg.is_clean
                    for o in osds
                    if o._running
                    for pg in o.pgs.values()
                    if pg.peering.is_primary()
                )

            await wait_until(clean, 10.0, "recovery after bluestore restart")
            assert await ioctx.read("obj") == payload

            await client.shutdown()
            for o in osds:
                if o._running:
                    await o.stop()
            for m in mons:
                await m.stop()
            await asyncio.sleep(0.05)

        asyncio.run(run())


class TestBluestoreTool:
    """ceph-bluestore-tool analog: offline fsck + show-label
    (BlueStore::_fsck; tools/bluestore_tool.py)."""

    def _populate(self, path):
        s = mk(path)
        txn = Transaction().create_collection("1.0s0")
        for i in range(4):
            txn.touch("1.0s0", f"o{i}")
            txn.write("1.0s0", f"o{i}", 0, bytes([i]) * (BLOCK * 2))
        s.queue_transaction(txn)
        s.umount()

    def test_fsck_clean_and_show_label(self, tmp_path, capsys):
        from ceph_tpu.tools.bluestore_tool import main as bst_main

        self._populate(tmp_path / "b")
        assert bst_main(["--path", str(tmp_path / "b"), "--op", "fsck",
                         "--deep"]) == 0
        out = capsys.readouterr().out
        assert "4 onodes" in out and "0 error(s)" in out
        assert bst_main(["--path", str(tmp_path / "b"), "--op",
                         "show-label"]) == 0
        import json as _json

        label = _json.loads(capsys.readouterr().out)
        assert label["objects"] == 4 and label["block_size"] == BLOCK

    def test_deep_fsck_catches_bitrot(self, tmp_path, capsys):
        """Flip bytes in the block device: shallow fsck stays clean
        (structure intact), deep fsck pins the csum mismatch to the
        onode — the fsck/deep-fsck split of the reference."""
        from ceph_tpu.os.bluestore import _ONODE, Onode
        from ceph_tpu.tools.bluestore_tool import main as bst_main

        self._populate(tmp_path / "b")
        # find a data block of o2 and corrupt it on "disk"
        s = mk(tmp_path / "b")
        blob = s.db.get(_ONODE, "1.0s0\x00o2")
        poff = Onode.decode(blob).blocks[0][0]
        s.umount()
        with open(tmp_path / "b" / "block", "r+b") as f:
            f.seek(poff)
            f.write(b"BITROT")
        assert bst_main(["--path", str(tmp_path / "b"), "--op", "fsck"]) == 0
        capsys.readouterr()
        assert bst_main(["--path", str(tmp_path / "b"), "--op", "fsck",
                         "--deep"]) == 1
        out = capsys.readouterr().out
        assert "1 error(s)" in out and "1.0s0/o2" in out

    def test_objectstore_tool_reads_bluestore(self, tmp_path, capsys):
        """ceph-objectstore-tool --type bluestore: list/dump work against
        a BlueStore data path (the reference tool's backend selection)."""
        from ceph_tpu.tools.objectstore_tool import main as ost_main

        self._populate(tmp_path / "b")
        assert ost_main([
            "--data-path", str(tmp_path / "b"), "--type", "bluestore",
            "--op", "list",
        ]) == 0
        out = capsys.readouterr().out
        assert '["1.0s0", "o0"]' in out
        assert ost_main([
            "--data-path", str(tmp_path / "b"), "--type", "bluestore",
            "--op", "dump", "--coll", "1.0s0", "--oid", "o1",
        ]) == 0
        import json as _json

        dump = _json.loads(capsys.readouterr().out)
        assert dump["size"] == BLOCK * 2
