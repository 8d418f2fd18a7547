"""Full-cluster integration tests — the tier-3 standalone analog.

Models qa/standalone/erasure-code/test-erasure-code.sh (SURVEY.md §4.3):
boot real mon+OSD daemons on localhost loopback sockets, create pools
through mon commands, and exercise put/get round trips, failure
detection, degraded reads, and recovery — the whole §3.1/§3.2 call stack
over real (TCP) messengers instead of a pumped queue.
"""

import asyncio

import pytest

from ceph_tpu.client import Rados, RadosError
from ceph_tpu.common.config import Config
from ceph_tpu.mon import MonMap, Monitor
from ceph_tpu.osd.osd import OSD

from test_mon import free_port_addrs


def fast_conf(whoami: int) -> Config:
    return Config(
        {
            "name": f"osd.{whoami}",
            "osd_heartbeat_interval": 0.1,
            "osd_heartbeat_grace": 0.6,
        },
        env=False,
    )


async def start_cluster(n_mons: int, n_osds: int):
    monmap = MonMap(addrs=free_port_addrs(n_mons))
    mons = [Monitor(name, monmap, election_timeout=0.3) for name in monmap.addrs]
    for m in mons:
        await m.start()
    for m in mons:
        await m.wait_for_quorum()
    osds = [OSD(i, monmap, conf=fast_conf(i)) for i in range(n_osds)]
    for o in osds:
        await o.start()
    for o in osds:
        await o.wait_for_up()
    return monmap, mons, osds


async def stop_cluster(mons, osds):
    for o in osds:
        if o._running:
            await o.stop()
    for m in mons:
        await m.stop()
    await asyncio.sleep(0.05)


async def wait_until(pred, timeout: float, what: str = "") -> None:
    deadline = asyncio.get_event_loop().time() + timeout
    while not pred():
        if asyncio.get_event_loop().time() > deadline:
            raise TimeoutError(f"timed out waiting for {what}")
        await asyncio.sleep(0.02)


class TestReplicatedCluster:
    def test_put_get_roundtrip(self):
        async def run():
            monmap, mons, osds = await start_cluster(1, 3)
            client = Rados(monmap)
            await client.connect()
            await client.pool_create("rbdpool", "replicated", size=3, pg_num=4)
            ioctx = await client.open_ioctx("rbdpool")

            payload = bytes(range(256)) * 16
            await ioctx.write_full("obj1", payload)
            assert await ioctx.read("obj1") == payload
            assert await ioctx.stat("obj1") == len(payload)

            await ioctx.append("obj1", b"tail")
            assert await ioctx.read("obj1") == payload + b"tail"

            await ioctx.setxattr("obj1", "user.k", b"v1")
            assert await ioctx.getxattr("obj1", "user.k") == b"v1"

            await ioctx.remove("obj1")
            with pytest.raises(RadosError):
                await ioctx.stat("obj1")

            await client.shutdown()
            await stop_cluster(mons, osds)

        asyncio.run(run())

    def test_replica_consistency(self):
        """Every replica OSD holds the object bytes (fan-out committed)."""

        async def run():
            monmap, mons, osds = await start_cluster(1, 3)
            client = Rados(monmap)
            await client.connect()
            await client.pool_create("rp", "replicated", size=3, pg_num=2)
            ioctx = await client.open_ioctx("rp")
            await ioctx.write_full("rep-obj", b"replicated-bytes")

            def replicas_have_it():
                holders = 0
                for o in osds:
                    for coll in o.store.list_collections():
                        try:
                            if b"replicated-bytes" in o.store.read(
                                coll, "rep-obj", 0, 0
                            ):
                                holders += 1
                                break
                        except Exception:
                            continue
                return holders == 3

            await wait_until(replicas_have_it, 3.0, "3 replicas")
            await client.shutdown()
            await stop_cluster(mons, osds)

        asyncio.run(run())


class TestErasureCodedCluster:
    def test_ec_pool_put_get(self):
        async def run():
            monmap, mons, osds = await start_cluster(1, 4)
            client = Rados(monmap)
            await client.connect()
            rv, rs, _ = await client.mon_command(
                {
                    "prefix": "osd erasure-code-profile set",
                    "name": "k2m1",
                    "profile": ["k=2", "m=1", "plugin=tpu"],
                }
            )
            assert rv == 0, rs
            await client.pool_create("ecpool", "erasure", profile="k2m1", pg_num=4)
            ioctx = await client.open_ioctx("ecpool")

            # Multi-stripe object: 3 stripes of 2x4K + a partial tail.
            payload = bytes((i * 7 + 3) % 256 for i in range(3 * 8192 + 1000))
            await ioctx.write_full("big", payload)
            assert await ioctx.read("big") == payload
            assert await ioctx.stat("big") == len(payload)
            # ranged read crossing a stripe boundary
            assert await ioctx.read("big", 5000, 7000) == payload[7000:12000]

            await client.shutdown()
            await stop_cluster(mons, osds)

        asyncio.run(run())

    def test_osd_failure_degraded_read_and_recovery(self):
        """Kill an OSD: heartbeat quorum marks it down, EC reads
        reconstruct, and the restarted OSD recovers via peering+push —
        the §3.2 decode path end to end over the wire."""

        async def run():
            monmap, mons, osds = await start_cluster(1, 4)
            client = Rados(monmap)
            await client.connect()
            rv, rs, _ = await client.mon_command(
                {
                    "prefix": "osd erasure-code-profile set",
                    "name": "k2m1f",
                    "profile": ["k=2", "m=1", "plugin=tpu"],
                }
            )
            assert rv == 0, rs
            await client.pool_create("ecf", "erasure", profile="k2m1f", pg_num=2)
            ioctx = await client.open_ioctx("ecf")

            objs = {f"o{i}": bytes([i]) * (8192 + 100 * i) for i in range(4)}
            for oid, data in objs.items():
                await ioctx.write_full(oid, data)

            # Kill osd.3; survivors report it, mon needs 2 reporters.
            victim = osds[3]
            victim_store = victim.store
            await victim.stop()
            await wait_until(
                lambda: not mons[0].osdmon.osdmap.is_up(3),
                8.0,
                "mon marking osd.3 down",
            )

            # Degraded reads: every object still fully readable (k=2 of 3).
            for oid, data in objs.items():
                assert await ioctx.read(oid) == data, f"degraded read {oid}"

            # Write while degraded (a new object lands on remaining shards).
            await ioctx.write_full("during", b"D" * 8192)
            assert await ioctx.read("during") == b"D" * 8192

            # Restart osd.3 on its old store; peering computes the missing
            # set from the log delta and recovery pushes rebuilt shards.
            revived = OSD(3, monmap, conf=fast_conf(3), store=victim_store)
            await revived.start()
            await revived.wait_for_up()
            osds[3] = revived

            def all_recovered():
                return all(
                    pg.is_clean
                    for o in osds
                    if o._running
                    for pg in o.pgs.values()
                    if pg.peering.is_primary()
                )

            await wait_until(all_recovered, 10.0, "recovery to clean")
            for oid, data in objs.items():
                assert await ioctx.read(oid) == data
            assert await ioctx.read("during") == b"D" * 8192

            await client.shutdown()
            await stop_cluster(mons, osds)

        asyncio.run(run())

    def test_shared_loop_stall_marks_no_osd_down(self):
        """Every daemon of an in-process cluster shares one event loop;
        a stall of it (a device compile) silences all peers at once.
        The OSDs must blame the stall, not each other: no markdown."""
        import time

        async def run():
            monmap, mons, osds = await start_cluster(1, 3)
            await asyncio.sleep(0.5)  # heartbeats established
            epoch = mons[0].osdmon.osdmap.epoch
            time.sleep(2.0)  # blocks the loop: > 3x the 0.6 s grace
            await asyncio.sleep(1.5)  # let every OSD tick past the stall
            assert all(mons[0].osdmon.osdmap.is_up(o.whoami) for o in osds)
            assert mons[0].osdmon.osdmap.epoch == epoch
            await stop_cluster(mons, osds)

        asyncio.run(run())


class TestAdminSocketIntrospection:
    def test_daemon_dumps_perf_config_and_traces(self, tmp_path):
        """The OSD admin socket (AdminSocket::init) serves perf counters,
        config, in-flight ops, and the EC data-path trace spans."""

        async def run():
            from ceph_tpu.common.admin_socket import admin_command
            from ceph_tpu.mon import MonMap, Monitor

            monmap = MonMap(addrs=free_port_addrs(1))
            mons = [Monitor(n, monmap, election_timeout=0.3) for n in monmap.addrs]
            for m in mons:
                await m.start()
                await m.wait_for_quorum()

            def conf(i):
                return Config(
                    {
                        "name": f"osd.{i}",
                        "osd_heartbeat_interval": 0.1,
                        "osd_heartbeat_grace": 0.6,
                        "admin_socket": str(tmp_path / f"osd.{i}.asok"),
                        "jaeger_tracing_enable": True,
                    },
                    env=False,
                )

            from ceph_tpu.osd.osd import OSD

            osds = [OSD(i, monmap, conf=conf(i)) for i in range(3)]
            for o in osds:
                await o.start()
            for o in osds:
                await o.wait_for_up()

            client = Rados(monmap)
            await client.connect()
            rv, rs, _ = await client.mon_command(
                {
                    "prefix": "osd erasure-code-profile set",
                    "name": "ask21",
                    "profile": ["k=2", "m=1", "plugin=tpu"],
                }
            )
            assert rv == 0, rs
            await client.pool_create("asok", "erasure", profile="ask21", pg_num=1)
            ioctx = await client.open_ioctx("asok")
            await ioctx.write_full("traced", b"T" * 8192)
            assert await ioctx.read("traced") == b"T" * 8192

            # find the PG's primary OSD: its tracer holds the write span
            primary = next(
                o
                for o in osds
                if any(p.peering.is_primary() for p in o.pgs.values())
            )
            sock = str(tmp_path / f"osd.{primary.whoami}.asok")

            # run the blocking unix-socket client off the event loop
            loop = asyncio.get_event_loop()
            dump = await loop.run_in_executor(
                None, lambda: admin_command(sock, "dump_tracer")
            )
            names = [s["name"] for s in dump["spans"]]
            assert "ec:write" in names and "ec:read" in names

            perf = await loop.run_in_executor(
                None, lambda: admin_command(sock, "perf dump")
            )
            assert perf["op"] >= 2

            cfg = await loop.run_in_executor(
                None, lambda: admin_command(sock, "config show")
            )
            assert cfg["jaeger_tracing_enable"] is True

            ops = await loop.run_in_executor(
                None, lambda: admin_command(sock, "dump_ops_in_flight")
            )
            assert ops["num_ops"] == 0  # everything committed

            await client.shutdown()
            await stop_cluster(mons, osds)

        asyncio.run(run())


class TestOpTracking:
    def test_historic_ops_dumped(self):
        """The OpTracker surfaces completed client ops (descriptions,
        events, durations) — dump_historic_ops' data source."""

        async def run():
            monmap, mons, osds = await start_cluster(1, 3)
            client = Rados(monmap)
            await client.connect()
            await client.pool_create("trackp", "replicated", pg_num=2)
            io = await client.open_ioctx("trackp")
            await io.write_full("tracked", b"x" * 512)
            assert await io.read("tracked") == b"x" * 512
            dumps = [o.op_tracker.dump_historic() for o in osds]
            ops = [op for d in dumps for op in d["ops"]]
            assert any("tracked" in op["description"] for op in ops)
            assert all(op["duration"] is not None for op in ops)
            assert any(
                e["event"] == "dequeued"
                for op in ops
                for e in op["type_data"]["events"]
            )
            await client.shutdown()
            await stop_cluster(mons, osds)

        asyncio.run(run())


class TestLostUnfound:
    def test_mark_unfound_lost_releases_blocked_ops(self):
        """The lost/unfound escape hatch (PrimaryLogPG
        mark_all_unfound_lost; qa ec_lost_unfound analog): an object
        missing with NO live source blocks every op touching it; the
        operator's mark_unfound_lost strikes it from the missing sets,
        deletes remnants, and blocked ops re-run to ENOENT.

        The unfound condition is FORGED on the primary (missing entries
        injected on every acting member) — producing it organically needs
        a multi-failure choreography the thrash tier doesn't model; the
        machinery under test (predicate, command, waiter release, delete
        fan-out) is the real path either way."""

        async def run():
            from ceph_tpu.osd.pg_log import Eversion

            monmap, mons, osds = await start_cluster(1, 3)
            client = Rados(monmap)
            await client.connect()
            await client.pool_create("lostp", "replicated", pg_num=2)
            io = await client.open_ioctx("lostp")
            await io.write_full("doomed", b"gone soon")
            assert await io.read("doomed") == b"gone soon"

            # find the primary PG and forge "missing everywhere"
            pool_id = client.objecter.osdmap.get_pool("lostp").id
            primary_pg = None
            for o in osds:
                for (pid, ps), pg in o.pgs.items():
                    if pid == pool_id and pg.peering.is_primary() and (
                        pg._object_exists("doomed")
                    ):
                        primary_pg = pg
                        break
            assert primary_pg is not None
            # destroy every replica's bytes UNDER the op path, then mark
            # the object missing everywhere: recovery now has no source
            from ceph_tpu.os.transaction import Transaction as StoreTxn
            from ceph_tpu.osd.pg_backend import shard_coll

            coll = shard_coll(primary_pg.pgid, -1)
            for o in osds:
                if o.store.exists(coll, "doomed"):
                    o.store.queue_transaction(StoreTxn().remove(coll, "doomed"))
            need = Eversion(1, 999)
            primary_pg.peering.missing.add("doomed", need)
            for m in primary_pg.peering.peer_missing.values():
                m.add("doomed", need)
            assert primary_pg.list_unfound() == ["doomed"]

            # ops on the object now queue behind (never-completing) recovery
            read_task = asyncio.get_event_loop().create_task(
                io.read("doomed")
            )
            await asyncio.sleep(0.3)
            assert not read_task.done(), "op should block on the unfound object"

            # blocked-op introspection names the stuck object + queue
            blocked = primary_pg.blocked_ops_summary()
            assert blocked.get("waiting_for_degraded", {}).get("doomed") == 1
            lost = primary_pg.mark_unfound_lost("delete")
            assert lost == ["doomed"]
            with pytest.raises(RadosError) as ei:
                await read_task
            assert ei.value.errno == -2  # ENOENT after the lost-delete
            assert primary_pg.list_unfound() == []
            # revert mode is explicitly unsupported
            with pytest.raises(ValueError):
                primary_pg.mark_unfound_lost("revert")
            await client.shutdown()
            await stop_cluster(mons, osds)

        asyncio.run(run())
