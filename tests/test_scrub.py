"""Scrub tests over a live cluster (src/osd/scrubber mirror).

Models qa's scrub/repair behaviors: a clean deep scrub reports zero
errors; silent shard corruption (flipped bytes on one OSD's store — the
reference's EIO/corruption injection in test-erasure-eio.sh) is caught
by the hinfo digest check; repair marks the shard missing and recovery
rebuilds it byte-identically.
"""

import asyncio

from ceph_tpu.client import Rados
from ceph_tpu.osd.pg_backend import shard_coll
from ceph_tpu.os.transaction import Transaction

from test_cluster import start_cluster, stop_cluster, wait_until


async def make_ec_cluster(objects: dict[str, bytes]):
    monmap, mons, osds = await start_cluster(1, 4)
    client = Rados(monmap)
    await client.connect()
    rv, rs, _ = await client.mon_command(
        {
            "prefix": "osd erasure-code-profile set",
            "name": "sk2m1",
            "profile": ["k=2", "m=1", "plugin=tpu"],
        }
    )
    assert rv == 0, rs
    await client.pool_create("spool", "erasure", profile="sk2m1", pg_num=1)
    ioctx = await client.open_ioctx("spool")
    for oid, data in objects.items():
        await ioctx.write_full(oid, data)
    return monmap, mons, osds, client, ioctx


def find_primary_pg(osds, pool_name="spool"):
    for o in osds:
        for pg in o.pgs.values():
            if pg.pool.name == pool_name and pg.peering.is_primary():
                return o, pg
    raise AssertionError("no primary pg")


async def run_scrub(pg, deep=False, repair=False, timeout=5.0):
    done = asyncio.get_event_loop().create_future()
    assert pg.scrub(deep=deep, repair=repair, on_done=lambda r: done.set_result(r))
    return await asyncio.wait_for(done, timeout)


class TestScrub:
    def test_clean_deep_scrub(self):
        async def run():
            objs = {f"s{i}": bytes([i + 1]) * (4096 * (i + 1)) for i in range(5)}
            monmap, mons, osds, client, ioctx = await make_ec_cluster(objs)
            osd, pg = find_primary_pg(osds)
            res = await run_scrub(pg, deep=True)
            assert res.clean, res.inconsistent
            assert res.objects_scrubbed == len(objs)
            await client.shutdown()
            await stop_cluster(mons, osds)

        asyncio.run(run())

    def test_detects_and_repairs_corruption(self):
        async def run():
            payload = bytes(range(256)) * 64  # 16 KiB = 2 stripes
            monmap, mons, osds, client, ioctx = await make_ec_cluster(
                {"victim": payload}
            )
            osd, pg = find_primary_pg(osds)
            # Corrupt a non-primary shard's chunk bytes directly on disk
            # (the scrub must catch what the write path never sees).
            acting = pg.acting()
            bad_shard = 1
            bad_osd = next(o for o in osds if o.whoami == acting[bad_shard])
            coll = shard_coll(pg.pgid, bad_shard)
            good = bad_osd.store.read(coll, "victim", 0, 0)
            corrupted = bytes([good[0] ^ 0xFF]) + good[1:]
            bad_osd.store.queue_transaction(
                Transaction().write(coll, "victim", 0, corrupted)
            )

            res = await run_scrub(pg, deep=True)
            assert not res.clean
            assert "victim" in res.inconsistent
            assert acting[bad_shard] in res.inconsistent["victim"]

            # Shallow scrub does NOT read data: corruption stays hidden
            res_shallow = await run_scrub(pg, deep=False)
            assert res_shallow.clean

            # Repair: mark missing + recover, then the shard is clean again
            res2 = await run_scrub(pg, deep=True, repair=True)
            assert res2.repaired == 1
            await wait_until(lambda: pg.is_clean, 5.0, "repair recovery")
            assert bad_osd.store.read(coll, "victim", 0, 0) == good
            res3 = await run_scrub(pg, deep=True)
            assert res3.clean, res3.inconsistent
            # and the object still reads back correctly
            assert await ioctx.read("victim") == payload
            await client.shutdown()
            await stop_cluster(mons, osds)

        asyncio.run(run())

    def test_chunked_scrub_covers_many_objects(self):
        async def run():
            objs = {f"m{i:03d}": bytes([i % 256]) * 4096 for i in range(60)}
            monmap, mons, osds, client, ioctx = await make_ec_cluster(objs)
            osd, pg = find_primary_pg(osds)
            res = await run_scrub(pg, deep=True, timeout=15.0)
            assert res.objects_scrubbed == 60  # > CHUNK_MAX forces chunking
            assert res.clean
            await client.shutdown()
            await stop_cluster(mons, osds)

        asyncio.run(run())


class TestScrubRobustness:
    def test_scrub_aborts_when_shard_dies(self):
        """A crashed replica mid-gather aborts the scrub via the tick
        timeout instead of wedging the scrubber forever."""

        async def run():
            objs = {"a": b"A" * 8192}
            monmap, mons, osds, client, ioctx = await make_ec_cluster(objs)
            osd, pg = find_primary_pg(osds)
            pg.scrubber.gather_timeout = 0.3
            # Kill a replica, then scrub before the mon notices it's down.
            victim = next(o for o in osds if o.whoami != osd.whoami
                          and o.whoami in pg.acting())
            await victim.stop()
            done = asyncio.get_event_loop().create_future()
            assert pg.scrub(deep=True, on_done=lambda r: done.set_result(r))
            res = await asyncio.wait_for(done, 10.0)
            assert res.aborted and not res.clean
            assert not pg.scrubber.active  # can scrub again later
            await client.shutdown()
            await stop_cluster(mons, osds)

        asyncio.run(run())

    def test_scrub_aborts_when_primary_drops_the_pg(self):
        """A primary that leaves the acting set drops the PG; the scrub
        it was running ends aborted instead of never reporting."""

        async def run():
            objs = {"a": b"A" * 8192}
            monmap, mons, osds, client, ioctx = await make_ec_cluster(objs)
            osd, pg = find_primary_pg(osds)
            pg.scrubber.gather_timeout = 60.0  # only the drop may end it
            victim = next(o for o in osds if o.whoami != osd.whoami
                          and o.whoami in pg.acting())
            await victim.stop()  # the gather can never complete
            done = asyncio.get_event_loop().create_future()
            assert pg.scrub(deep=True, on_done=lambda r: done.set_result(r))
            real = osd.osdmap.pg_to_up_acting_osds

            def without_me(pool, ps):
                up, upp, acting, actp = real(pool, ps)
                acting = [a for a in acting if a != osd.whoami]
                return up, upp, acting, actp

            osd.osdmap.pg_to_up_acting_osds = without_me
            osd._advance_pgs()
            res = await asyncio.wait_for(done, 5.0)
            assert res.aborted and not res.clean
            await client.shutdown()
            await stop_cluster(mons, osds)

        asyncio.run(run())

    def test_replicated_repair_pulls_good_copy(self):
        """A corrupt PRIMARY copy in a size-3 replicated pool is repaired
        from a replica, not re-pushed (majority picks the good copy)."""
        from test_cluster import start_cluster

        async def run():
            monmap, mons, osds = await start_cluster(1, 3)
            client = Rados(monmap)
            await client.connect()
            await client.pool_create("rp3", "replicated", size=3, pg_num=1)
            ioctx = await client.open_ioctx("rp3")
            payload = b"good-bytes" * 100
            await ioctx.write_full("robj", payload)

            await wait_until(
                lambda: sum(
                    1 for o in osds
                    for coll in o.store.list_collections()
                    if o.store.exists(coll, "robj")
                ) == 3,
                3.0,
                "3 replicas",
            )
            osd, pg = find_primary_pg(osds, "rp3")
            coll = shard_coll(pg.pgid, -1)
            # corrupt the PRIMARY's copy
            bad = b"EVIL" + payload[4:]
            osd.store.queue_transaction(Transaction().write(coll, "robj", 0, bad))

            res = await run_scrub(pg, deep=True, repair=True)
            assert not res.clean
            assert osd.whoami in res.inconsistent["robj"]
            await wait_until(lambda: pg.is_clean, 5.0, "repair recovery")
            assert osd.store.read(coll, "robj", 0, 0) == payload
            assert await ioctx.read("robj") == payload
            res2 = await run_scrub(pg, deep=True)
            assert res2.clean
            await client.shutdown()
            await stop_cluster(mons, osds)

        asyncio.run(run())

    def test_deep_scrub_detects_and_repairs_omap_divergence(self):
        """Deep scrub covers omap (be_deep_scrub omap_digest): a replica
        whose omap silently diverges is flagged and repair restores it
        (recovery pushes carry omap since round 5)."""
        from test_cluster import start_cluster

        async def run():
            monmap, mons, osds = await start_cluster(1, 3)
            client = Rados(monmap)
            await client.connect()
            await client.pool_create("rpo", "replicated", size=3, pg_num=1)
            ioctx = await client.open_ioctx("rpo")
            await ioctx.write_full("oobj", b"bytes")
            good = {"k1": b"v1", "k2": b"v2"}
            await ioctx.omap_set("oobj", good)
            await wait_until(
                lambda: sum(
                    1 for o in osds
                    for coll in o.store.list_collections()
                    if o.store.exists(coll, "oobj")
                ) == 3,
                3.0,
                "3 replicas",
            )
            osd, pg = find_primary_pg(osds, "rpo")
            coll = shard_coll(pg.pgid, -1)
            # a NON-primary replica's omap diverges (majority must win)
            victim = next(o for o in osds if o is not osd and any(
                o.store.exists(c, "oobj") for c in o.store.list_collections()
            ))
            victim.store.queue_transaction(
                Transaction().omap_setkeys(coll, "oobj", {"k1": b"EVIL"})
            )
            # shallow scrub cannot see it; deep flags exactly the victim
            res_shallow = await run_scrub(pg, deep=False)
            assert res_shallow.clean
            res = await run_scrub(pg, deep=True)
            assert not res.clean
            assert list(res.inconsistent["oobj"]) == [victim.whoami]
            assert "omap" in res.inconsistent["oobj"][victim.whoami]
            res2 = await run_scrub(pg, deep=True, repair=True)
            assert res2.repaired == 1
            await wait_until(lambda: pg.is_clean, 5.0, "repair recovery")
            assert victim.store.omap_get(coll, "oobj") == good
            res3 = await run_scrub(pg, deep=True)
            assert res3.clean
            await client.shutdown()
            await stop_cluster(mons, osds)

        asyncio.run(run())


class TestScrubOffloadAndHealth:
    """ISSUE 9: TPU-offloaded deep-scrub verify + the scrub-errors →
    health pipeline (OSD status blob → mgr digest → mon HEALTH_ERR)."""

    def test_deep_scrub_verifies_on_device_in_aggregated_launches(self):
        """A multi-object deep scrub routes parity verification through
        the VerifyAggregator: VERIFY_LAUNCHES advances by ~one launch
        per chunk, covering every object (the acceptance criterion's
        one-launch-many-objects witness)."""
        from ceph_tpu.ops import dispatch as ec_dispatch

        async def run():
            objs = {f"v{i}": bytes([i + 1]) * 8192 for i in range(8)}
            monmap, mons, osds, client, ioctx = await make_ec_cluster(objs)
            osd, pg = find_primary_pg(osds)
            v0 = ec_dispatch.VERIFY_LAUNCHES.snapshot()
            res = await run_scrub(pg, deep=True, timeout=15.0)
            assert res.clean and res.objects_scrubbed == 8
            after = ec_dispatch.VERIFY_LAUNCHES.snapshot()
            launches = after["launches"] - v0["launches"]
            stripes = after["stripes"] - v0["stripes"]
            assert launches >= 1, "deep scrub never reached the verify kernel"
            assert launches < 8, (
                f"verify did not aggregate: {launches} launches for 8 objects"
            )
            # every object's stripes rode the launches (one stripe each
            # at 8 KiB / k=2 / 4 KiB chunks, plus padding)
            assert stripes >= 8, (launches, stripes)
            await client.shutdown()
            await stop_cluster(mons, osds)

        asyncio.run(run())

    def test_parity_verify_catches_hinfo_consistent_corruption(self):
        """The case the offload exists for: a shard whose hinfo was
        rewritten consistently with its corrupt bytes passes the
        digest-vs-hinfo check, but the parity equation still breaks —
        only the device recompute can see it."""
        from ceph_tpu.osd.ec_transaction import HINFO_ATTR
        from ceph_tpu.stripe import HashInfo
        from ceph_tpu.utils.crc32c import crc32c

        async def run():
            payload = bytes(range(256)) * 32  # 8 KiB
            monmap, mons, osds, client, ioctx = await make_ec_cluster(
                {"sneaky": payload}
            )
            osd, pg = find_primary_pg(osds)
            acting = pg.acting()
            bad_shard = 1
            bad_osd = next(o for o in osds if o.whoami == acting[bad_shard])
            coll = shard_coll(pg.pgid, bad_shard)
            good = bad_osd.store.read(coll, "sneaky", 0, 0)
            corrupted = bytes([good[0] ^ 0xFF]) + good[1:]
            # forge the hinfo so the digest check passes on the corrupt
            # bytes — a digest-only scrub is blind to this
            hinfo = HashInfo.decode(
                bad_osd.store.getattr(coll, "sneaky", HINFO_ATTR)
            )
            hinfo.cumulative_shard_hashes[bad_shard] = crc32c(
                corrupted, HashInfo.SEED
            )
            bad_osd.store.queue_transaction(
                Transaction()
                .write(coll, "sneaky", 0, corrupted)
                .setattr(coll, "sneaky", HINFO_ATTR, hinfo.encode())
            )
            res = await run_scrub(pg, deep=True)
            assert not res.clean, "hinfo-consistent corruption slipped through"
            assert "sneaky" in res.inconsistent, res.inconsistent
            reasons = " ".join(res.inconsistent["sneaky"].values())
            assert "parity recompute mismatch" in reasons, reasons
            assert "sneaky" in res.unrepairable
            # auto-repair must REFUSE an unlocalized mismatch: rebuilding
            # parity from the (corrupt) data shard would cement the
            # damage and silently clear the health check.  The corrupt
            # bytes stay on disk and the object stays inconsistent.
            res2 = await run_scrub(pg, deep=True, repair=True)
            assert res2.repaired == 0, res2
            assert bad_osd.store.read(coll, "sneaky", 0, 0) == corrupted
            res3 = await run_scrub(pg, deep=True)
            assert not res3.clean, "refused repair must keep flagging"
            await client.shutdown()
            await stop_cluster(mons, osds)

        asyncio.run(run())

    def test_scrub_errors_reach_mon_health_and_clear_on_repair(self):
        """Satellite: ScrubResult errors flow OSD status blob → mgr
        digest → mon OSD_SCRUB_ERRORS / PG_DAMAGED HEALTH_ERR with
        per-PG detail — and clear after repair + recovery + a clean
        re-scrub."""
        from ceph_tpu.common.health import overall_status
        from ceph_tpu.mgr import Mgr

        async def run():
            payload = bytes(range(256)) * 64
            monmap, mons, osds, client, ioctx = await make_ec_cluster(
                {"victim": payload}
            )
            mgr = Mgr("x", monmap)
            mgr.beacon_interval = 0.1
            await mgr.start()
            await mgr.wait_for_active()
            osd, pg = find_primary_pg(osds)
            acting = pg.acting()
            bad_shard = 1
            bad_osd = next(o for o in osds if o.whoami == acting[bad_shard])
            coll = shard_coll(pg.pgid, bad_shard)
            good = bad_osd.store.read(coll, "victim", 0, 0)
            bad_osd.store.queue_transaction(
                Transaction().write(
                    coll, "victim", 0, bytes([good[0] ^ 0xFF]) + good[1:]
                )
            )
            res = await run_scrub(pg, deep=True)
            assert not res.clean

            def damage_raised():
                checks, details = mons[0].health_checks()
                return (
                    "OSD_SCRUB_ERRORS" in checks
                    and "PG_DAMAGED" in checks
                    and any("victim" in line
                            for line in details.get("PG_DAMAGED", []))
                )

            await wait_until(damage_raised, 10.0,
                             "scrub errors to reach mon health")
            checks, _ = mons[0].health_checks()
            assert overall_status(checks) == "HEALTH_ERR"
            assert "scrub errors" in checks["OSD_SCRUB_ERRORS"]
            assert "inconsistent" in checks["PG_DAMAGED"]
            # the mgr-side checks agree (prometheus healthcheck gauge)
            assert (
                mgr.health_checks()
                .get("OSD_SCRUB_ERRORS", {})
                .get("severity")
                == "HEALTH_ERR"
            )

            # repair: recovery rebuilds the shard; the repaired result
            # suppresses the check, and the confirming clean scrub (plus
            # a fresh mgr report cycle) keeps it clear
            res2 = await run_scrub(pg, deep=True, repair=True)
            assert res2.repaired == 1
            await wait_until(lambda: pg.is_clean, 5.0, "repair recovery")
            res3 = await run_scrub(pg, deep=True)
            assert res3.clean

            def damage_cleared():
                checks, _ = mons[0].health_checks()
                return (
                    "OSD_SCRUB_ERRORS" not in checks
                    and "PG_DAMAGED" not in checks
                )

            await wait_until(damage_cleared, 10.0,
                             "health to clear after repair")
            await client.shutdown()
            await mgr.stop()
            await stop_cluster(mons, osds)

        asyncio.run(run())

    def test_authority_pick_is_deterministic(self):
        """Satellite: modal-metadata ties break by highest version, then
        lowest shard — never by set iteration order."""
        from ceph_tpu.osd.scrubber import PgScrubber

        class _Pool:
            type = 0

        class _PG:
            pool = _Pool()
            pgid = "t"

            class peering:
                @staticmethod
                def osds_missing(oid):
                    return set()

        scrubber = PgScrubber(_PG())
        scrubber._deep = False
        # two-way tie at count 1: (size 10, v 3) on shard 0 vs
        # (size 10, v 7) on shard 1 — highest version must win, so the
        # shard 0 copy is the odd one out, on EVERY run
        for _ in range(8):
            scrubber._maps = {
                100: {"o": {"size": 10, "oi_size": 10, "version": 3}},
                101: {"o": {"size": 10, "oi_size": 10, "version": 7}},
                102: {"o": {"size": 10, "oi_size": 10, "version": 7}},
            }
            bad = scrubber._compare_ec_object("o", [100, 101, 102])
            assert list(bad) == [100], bad
            # exact tie in count AND version: lowest shard is authority
            scrubber._maps = {
                100: {"o": {"size": 10, "oi_size": 10, "version": 5}},
                101: {"o": {"size": 12, "oi_size": 12, "version": 5}},
            }
            bad = scrubber._compare_ec_object("o", [100, 101])
            assert list(bad) == [101], bad
