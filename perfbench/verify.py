"""What decides `correct`: the window's results against the reference.

- client: every read in the window was byte-exact (traffic.Generator
  compares each as it lands), and a sample of the targets drawn from the
  seed reads back, once the window has closed, as the last acknowledged
  write left it;
- ECBackend, codec, kernel and store: every shard of those sampled
  objects, read straight off each OSD's store (stopped OSDs included),
  equals the plain encoding of the object by the configuration's shard
  reference (`reference.py`, plain RS(k,m), unless the configuration
  names another);
- device: the window launched on the device, nothing fell back to the
  host oracle, and the device guard never went DEGRADED.

Each number is printed beside its limit; an exact comparison has the
limit 0.
"""

from __future__ import annotations

import re

import numpy as np


def shard_copies(cluster, pool_id: int, oid: str) -> dict[int, list[bytes]]:
    """Every copy of every shard of `oid` on every OSD's store, by shard
    index, found from the collection names alone (`<pool>.<ps>s<shard>`)."""
    rx = re.compile(rf"^{pool_id}\.\d+s(\d+)$")
    out: dict[int, list[bytes]] = {}
    for osd in cluster.osds:
        store = osd.store
        for coll in store.list_collections():
            m = rx.match(coll)
            if m and store.exists(coll, oid):
                out.setdefault(int(m.group(1)), []).append(store.read(coll, oid))
    return out


def lost_shards(cluster, pool_id: int, victims: list[int], oids) -> dict[str, int]:
    """oid -> the shard index the stopped OSDs held for it."""
    rx = re.compile(rf"^{pool_id}\.\d+s(\d+)$")
    want, out = set(oids), {}
    for v in victims:
        store = cluster.osds[v].store
        for coll in store.list_collections():
            m = rx.match(coll)
            if m:
                for oid in store.list_objects(coll):
                    if oid in want:
                        out[oid] = int(m.group(1))
    return out


def shard_mismatches(ref, cfg: dict, copies: dict[int, list[bytes]],
                     payload: bytes) -> int:
    """Shards missing or differing from the encoding by reference `ref`."""
    want = ref.object_shards(payload, cfg)
    bad = 0
    for s, expect in enumerate(want):
        have = copies.get(s, [])
        bad += (not have) + sum(c != expect for c in have)
    return bad


def sample(seed: int, population: list, n: int) -> list:
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 0x5EED])
    idx = rng.choice(len(population), size=min(n, len(population)), replace=False)
    return [population[i] for i in sorted(idx)]


async def differs(read, want: bytes) -> bool:
    """A read that fails, or returns other bytes, is a mismatch."""
    try:
        return await read != want
    except Exception:  # the program's read error is the finding, not a crash
        return True


async def check(cluster, ref, cfg: dict, tr: dict, gen, run, start: dict,
                pool_id: int) -> dict:
    from perfbench.harness import delta, launch_totals

    n = int(tr["check_objects"])
    read_bad, shard_bad, checked = len(gen.mismatched), 0, 0
    if gen.rbd:
        obj = 1 << cfg["image_order"]
        names = gen.object_names()
        touched = sorted({(o.target * gen.align) // obj for o in run.ops if o.ok})
        for objno in sample(run.seed, touched or list(range(len(names))), n):
            want = gen.expected_range(objno * obj, obj)
            read_bad += await differs(gen.image.read(objno * obj, obj), want)
            shard_bad += shard_mismatches(
                ref, cfg, shard_copies(cluster, pool_id, names[objno]), want)
            checked += 1
    else:
        touched = sorted({o.target for o in run.ops if o.ok} & set(gen.state))
        for t in sample(run.seed, touched or sorted(gen.state), n):
            want = gen.expected_object(t)
            read_bad += await differs(gen.io.read(gen.oid(t)), want)
            shard_bad += shard_mismatches(
                ref, cfg, shard_copies(cluster, pool_id, gen.oid(t)), want)
            checked += 1
    dev = delta(launch_totals(), start)
    run.notes["objects_checked"] = checked
    return {
        "failed_ops": {"value": sum(not o.ok for o in run.ops), "limit": 0, "ok_if": "<="},
        "setup_failures": {"value": gen.setup_failures, "limit": 0, "ok_if": "<="},
        "read_mismatches": {"value": read_bad, "limit": 0, "ok_if": "<="},
        "shard_mismatches": {"value": shard_bad, "limit": 0, "ok_if": "<="},
        "host_fallbacks": {"value": dev["fallback_launches"] + dev["service_host_fallbacks"],
                           "limit": 0, "ok_if": "<="},
        "guard_degraded": {"value": dev["guard_degraded"], "limit": 0, "ok_if": "<="},
        "window_launches": {"value": run.counters["all_launches"], "limit": 1, "ok_if": ">="},
        "objects_checked": {"value": checked, "limit": 1, "ok_if": ">="},
    }


def passed(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] if c["ok_if"] == "<=" else c["value"] >= c["limit"]
               for c in compared.values())
