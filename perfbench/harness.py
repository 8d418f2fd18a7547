"""One run of one cell: bring up the cluster, load it, measure, check.

A run is one process and one in-process `DevCluster` (the system under
test) built from the cell's configuration file.  Set-up writes the
cell's prefill, stops the configured OSDs and warms every launch shape
with the traffic's own ops; then `traffic.drive` loads the cluster
through librados or librbd for exactly the window, and `verify` holds
what the window produced against the plain reference.
"""

from __future__ import annotations

import asyncio
import contextlib
import gc
import importlib
import json
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# The erasure-code profile keys every configuration states; its optional
# "profile" object adds others (d, scalar_mds, technique, l, ...).
PROFILE_KEYS = ("k", "m", "plugin", "crush-failure-domain", "stripe_unit")
REFERENCE_API = ("chunk_count", "stripe_width", "object_shards", "encode_bytes",
                 "decode_bytes")


@dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    ref: object  # the configuration's shard reference module (perfbench/reference.py)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench: dict | None = None) -> Cell:
    """The cell `name` of BENCHMARK.json with its configuration and
    traffic files, and the metrics it reports."""
    bench = bench or load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    cfg = load_json(ROOT / conf["file"])
    traffic = load_json(BENCH / "traffic" / f"{entry['traffic']}.json")

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return Cell(name, int(entry["chips"]), cfg, traffic,
                mine(bench["end_to_end"]), mine(bench["per_layer"]),
                shard_reference(conf["name"], cfg))


def shard_reference(config: str, cfg: dict):
    """The configuration's shard reference module, `perfbench.<reference>`
    (default `reference`, plain RS), checked to have the whole interface
    of perfbench/reference.py; and its extra profile keys checked not to
    restate the five every configuration gives."""
    profile = cfg.get("profile", {})
    if not isinstance(profile, dict):
        raise ValueError(f"config {config!r}: \"profile\" is not an object")
    again = [key for key in profile if key in PROFILE_KEYS]
    if again:
        raise ValueError(f"config {config!r}: \"profile\" repeats {again}, "
                         f"which the configuration states as {PROFILE_KEYS}")
    name = cfg.get("reference", "reference")
    try:
        ref = importlib.import_module(f"perfbench.{name}")
    except ImportError as e:
        raise ValueError(f"config {config!r}: reference {name!r}: {e}") from e
    missing = [f for f in REFERENCE_API if not callable(getattr(ref, f, None))]
    if missing:
        raise ValueError(f"config {config!r}: reference {name!r} lacks {missing}")
    return ref


def profile_args(cfg: dict) -> list[str]:
    """`key=value` of the erasure-code profile: the five keys, then the
    configuration's "profile" object in file order."""
    return [f"{key}={cfg[key]}" for key in PROFILE_KEYS] + [
        f"{key}={value}" for key, value in cfg.get("profile", {}).items()]


def process_age_s() -> float:
    """Seconds since this process was started (Linux), so that set-up
    counts the interpreter's start and imports too."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


def find_devices(chips: int, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def launch_totals() -> dict[str, int]:
    """Process-wide device-launch counters of the coding path."""
    from ceph_tpu.ops import dispatch
    from ceph_tpu.ops.device_cache import device_chunk_cache
    from ceph_tpu.ops.guard import device_guard
    from ceph_tpu.ops.offload_runtime import offload_services, service_aggregator

    out = {}
    for key, counter in (("all", dispatch.LAUNCHES),
                         ("decode", dispatch.DECODE_LAUNCHES),
                         ("verify", dispatch.VERIFY_LAUNCHES),
                         ("fallback", dispatch.FALLBACK_LAUNCHES)):
        snap = counter.snapshot()
        out[f"{key}_launches"] = snap["launches"]
        out[f"{key}_stripes"] = snap["stripes"]
        out[f"{key}_bytes"] = snap["bytes"]
    out["service_host_fallbacks"] = sum(
        int(service_aggregator(n).perf.get("host_fallbacks") or 0)
        for n in offload_services())
    out["guard_degraded"] = device_guard().degraded_total
    out["cache_hits"] = device_chunk_cache().perf_dump()["hits"]
    return out


def read_path_state(cluster) -> dict:
    """What changes an EC read's survivor set, and so its decode program:
    hedged sub-reads and peers flagged laggy."""
    live = [o for o in cluster.osds if o._running]
    return {"hedge_reads": sum(int(o.perf.dump().get("ec_hedge_reads", 0)) for o in live),
            "laggy_peers": sorted({p for o in live for p in o.laggy_peers()})}


@contextlib.contextmanager
def gc_pauses(out: list):
    """Record (generation, seconds) of each garbage collection: a full
    collection stops the one loop that serves every daemon."""
    t0 = {}

    def cb(phase, info):
        if phase == "start":
            t0["t"] = time.perf_counter()
        elif "t" in t0:
            out.append((info["generation"], time.perf_counter() - t0.pop("t")))

    gc.callbacks.append(cb)
    try:
        yield
    finally:
        gc.callbacks.remove(cb)


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


@dataclass
class Run:
    """What one run measured, handed to the metric readers."""

    cell: Cell
    seed: int
    seconds: float
    setup_s: float = 0.0
    ops: list = field(default_factory=list)  # traffic.OpRecord
    window: tuple[float, float] = (0.0, 0.0)
    counters: dict = field(default_factory=dict)  # launch deltas in the window
    host: dict = field(default_factory=dict)
    trace: object = None  # devtrace.TraceSummary
    work: dict = field(default_factory=dict)
    peaks: dict = field(default_factory=dict)
    compared: dict = field(default_factory=dict)
    notes: dict = field(default_factory=dict)

    def window_ops(self) -> list:
        """Ops that completed in the window, acknowledged and byte-exact."""
        lo, hi = self.window
        return [o for o in self.ops if o.ok and o.exact and lo <= o.done <= hi]


async def start_cluster(cfg: dict):
    """DevCluster + client + pool (+ image) for a configuration."""
    from ceph_tpu.client import Rados
    from ceph_tpu.tools.vstart import DevCluster

    cluster = DevCluster(
        n_mons=cfg["mons"], n_osds=cfg["osds"], with_mgr=cfg["mgrs"] > 0,
        conf_overrides={"osd_objectstore": cfg["osd_objectstore"],
                        "osd_data": cfg["osd_data"], **cfg.get("conf", {})},
    )
    await cluster.start()
    for mon in cluster.mons:
        mon.conf.set("mon_osd_down_out_interval", cfg["mon_osd_down_out_interval"])
    client = Rados(cluster.monmap, name="client.bench")
    await client.connect()
    rv, rs, _ = await client.mon_command({
        "prefix": "osd erasure-code-profile set",
        "name": f"{cfg['name']}_profile",
        "profile": profile_args(cfg),
    })
    if rv != 0:
        raise RuntimeError(f"erasure-code profile: {rs}")
    extra = {"allow_ec_overwrites": True} if cfg["allow_ec_overwrites"] else {}
    await client.pool_create(cfg["pool"], "erasure",
                             profile=f"{cfg['name']}_profile",
                             pg_num=cfg["pg_num"], **extra)
    io = await client.open_ioctx(cfg["pool"])
    image = None
    if cfg["api"] == "rbd":
        from ceph_tpu.rbd import RBD

        rbd = RBD(io)
        await rbd.create(cfg["image"], cfg["image_size"], order=cfg["image_order"])
        image = await rbd.open(cfg["image"])
    await wait_clean(cluster, cfg, timeout=120.0)
    return cluster, client, io, image


def primaries(cluster, pool: str):
    return [pg for o in cluster.osds if o._running for pg in o.pgs.values()
            if pg.pool.name == pool and pg.peering.is_primary()]


async def wait_until(pred, timeout: float, what: str) -> None:
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() > deadline:
            raise RuntimeError(f"timed out after {timeout} s waiting for {what}")
        await asyncio.sleep(0.05)


async def wait_clean(cluster, cfg: dict, timeout: float) -> None:
    def clean():
        pgs = primaries(cluster, cfg["pool"])
        return len(pgs) == cfg["pg_num"] and all(pg.is_clean for pg in pgs)

    await wait_until(clean, timeout, f"{cfg['pg_num']} PGs active+clean")


async def stop_osds(cluster, cfg: dict, n: int) -> list[int]:
    """Stop the last `n` OSDs, wait for the mon to mark them down and for
    every PG to be active again without them (degraded)."""
    victims = list(range(len(cluster.osds) - n, len(cluster.osds)))
    mon = cluster.mons[0]
    for v in victims:
        await cluster.osds[v].stop()
    await wait_until(lambda: not any(mon.osdmon.osdmap.is_up(v) for v in victims),
                     60.0, f"mon marking osd {victims} down")

    def peered():
        pgs = primaries(cluster, cfg["pool"])
        return len(pgs) == cfg["pg_num"] and all(
            pg.peering.is_active() and not set(victims) & set(pg.acting())
            for pg in pgs)

    await wait_until(peered, 60.0, f"{cfg['pg_num']} PGs active without {victims}")
    return victims


def memory_peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks)) if peaks else 0


def progress(msg: str) -> None:
    print(f"[perfbench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


async def run_cell_async(cell: Cell, seed: int, seconds: float, trace: bool,
                         devs, t_start: float, window_fault=None) -> Run:
    from ceph_tpu.utils.compile_cache import CompileClock

    from perfbench import devtrace, traffic, verify, work

    run = Run(cell, seed, seconds)
    clock = CompileClock()
    cfg, tr = cell.cfg, cell.traffic
    start = launch_totals()
    cluster, client, io, image = await start_cluster(cfg)
    try:
        gen = traffic.Generator(cfg, tr, seed, io, image)
        if tr.get("prefill"):
            progress(f"prefill of {gen.prefill_bytes() / 2**20:.0f} MiB")
            await gen.prefill()
        victims = []
        if tr.get("down_osds"):
            progress(f"stopping {tr['down_osds']} OSD(s)")
            victims = await stop_osds(cluster, cfg, tr["down_osds"])
            gen.lost = verify.lost_shards(cluster, io.pool_id, victims,
                                           gen.object_names())
        progress(f"warm-up: {tr['warmup_ops']} ops")
        await gen.warmup()
        c0 = clock.read()
        rp0 = read_path_state(cluster)
        before = launch_totals()
        tracer = devtrace.Tracer() if trace else None
        run.setup_s = time.perf_counter() - t_start
        progress(f"window of {seconds} s (set-up {run.setup_s:.1f} s)")
        host0 = os.times()
        pauses: list = []
        with tracer or contextlib.nullcontext(), window_fault or contextlib.nullcontext(), \
                gc_pauses(pauses):
            run.ops, run.window, run.host["loop_lags"] = await gen.drive(seconds)
        host1 = os.times()
        run.notes["gc_count_max_s"] = {
            f"gen{g}": [sum(q == g for q, _ in pauses),
                        max([d for q, d in pauses if q == g], default=0.0)]
            for g in (0, 1, 2)}
        run.counters = delta(launch_totals(), before)
        run.notes["window_compile_s"] = clock.read() - c0
        rp1 = read_path_state(cluster)
        run.notes["hedge_reads"] = rp1["hedge_reads"] - rp0["hedge_reads"]
        run.notes["laggy_peers"] = [rp0["laggy_peers"], rp1["laggy_peers"]]
        wall = run.window[1] - run.window[0]
        run.host["wall_s"] = wall
        run.host["cpu_s"] = (host1.user + host1.system) - (host0.user + host0.system)
        run.notes["drained_s"] = time.perf_counter() - run.window[1]
        run.notes["memory_peak_bytes"] = memory_peak_bytes(devs)
        if tracer is not None:
            run.trace = tracer.summary()
        run.work = work.window_work(cell, run.ops, run.counters)
        progress("checking")
        run.compared = await verify.check(cluster, cell.ref, cfg, tr, gen, run,
                                          start, io.pool_id)
    finally:
        await client.shutdown()
        await cluster.stop()
    return run


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, t_start: float | None = None,
             window_fault=None) -> tuple[Run, list]:
    """One run of `cell`.  `window_fault`, a context manager, breaks the
    system under test for the window alone (perfbench/control.py)."""
    t_start = time.perf_counter() if t_start is None else t_start
    devs = find_devices(cell.chips, require_tpu)
    kind = devs[0].device_kind
    peaks = load_json(BENCH / "peaks.json")
    if require_tpu and kind not in peaks:
        raise NoDevice(f"device kind {kind!r} is not in perfbench/peaks.json")
    from ceph_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run = asyncio.run(run_cell_async(cell, seed, seconds, trace, devs, t_start,
                                     window_fault))
    run.peaks = peaks.get(kind, {})
    return run, devs
