"""Device-resident chunk cache — keep hot EC chunks in HBM (ISSUE 11).

The third lever of the per-chip-gap tentpole: a repeated degraded read
(and the read leg of a degraded RMW cycle — both flow through
``ECBackend.objects_read_and_reconstruct``) re-reconstructs the same
missing chunks launch after launch, paying the H2D staging of the whole
survivor batch every time.  This cache holds recently encoded/decoded
chunk buffers ON DEVICE, keyed by ``(object, shard, generation, offset)``,
so the next read of the same (object, generation) serves the missing
chunks with a single D2H copy — no H2D, no kernel, no launch at all.

Coherence model:

- ``generation`` is the object's version at put/get time (the producer
  passes it); a write bumps the version, so stale entries simply miss.
- Overwrites additionally ``invalidate_object`` eagerly at encode
  dispatch — the moment the bytes actually change — so dead bytes free
  immediately.  NOT at submit: the write's own RMW read leg runs between
  the two and reads exactly the committed pre-write bytes, so it may
  serve them from the cache (``ECBackend`` captures the pre-write
  generation at submit and threads it through the read).
- A DEGRADED backend transition (``ops/guard.py mark_degraded``) clears
  the cache and gates ``put``: a wedged runtime cannot be trusted to
  serve buffers, and the byte-identical host path needs no cache.
- Keys are opaque to this module — ``ECBackend`` namespaces them with a
  never-reused per-backend token, so one process hosting many clusters
  (the test harnesses) can never cross-serve bytes.

Bounded by ``ec_tpu_device_cache_bytes`` (LRU, runtime-mutable through
the OSD config-observer pattern); hit/miss/evict counters export through
``ops/dispatch.perf_dump()`` (asok ``perf dump`` ``ec_dispatch.cache_*``
→ ``ceph_tpu_ec_dispatch_cache_*`` Prometheus families).  A served hit
commits a ``cache_hit``-flagged flight record whose only span is the
D2H copy — the "skips H2D" acceptance criterion is a visible property
of the timeline, not an inference.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict

import numpy as np

from ceph_tpu.common.lockdep import make_lock
from ceph_tpu.common.tracer import section
from ceph_tpu.common.mempool import ledger as _hbm_ledger


class _Entry:
    __slots__ = ("buf", "nbytes", "generation", "off", "mem")

    def __init__(self, buf, nbytes: int, generation, off: int, mem=None):
        self.buf = buf
        self.nbytes = int(nbytes)
        self.generation = generation
        self.off = int(off)
        # HBM ledger handle (ISSUE 13): one per resident entry,
        # buffer-finalized so a dropped cache instance cannot leak
        # ledger bytes past its buffers' death
        self.mem = mem


class DeviceChunkCache:
    """Bounded per-backend LRU of device-resident chunk buffers."""

    def __init__(self, max_bytes: int | None = None):
        if max_bytes is None:
            from ceph_tpu.common.options import OPTIONS

            max_bytes = int(OPTIONS["ec_tpu_device_cache_bytes"].default)
        self._lock = make_lock("device_cache")
        # (obj, shard, off) -> _Entry; generation checked on get so a
        # stale-generation entry is replaced in place by the next put
        self._entries: "OrderedDict[tuple, _Entry]" = OrderedDict()
        # obj -> {keys} index so the per-write invalidate_object hook is
        # O(entries-for-that-object), not a scan of the whole cache
        self._by_obj: dict[object, set[tuple]] = {}
        self._bytes = 0
        self.max_bytes = int(max_bytes)
        self.hits = 0
        self.misses = 0
        self.insertions = 0
        self.evictions = 0
        self.invalidations = 0
        self.served_bytes = 0
        self.put_failures = 0
        self.delta_updates = 0

    # -- configuration -------------------------------------------------------

    def configure(self, max_bytes: int | None = None) -> None:
        """Apply live config (`ec_tpu_device_cache_bytes`); shrinking
        evicts LRU-first, 0 disables and drops everything.

        `resident_bytes` is RECOMPUTED from the entry index before the
        eviction loop, not trusted from the decremented counter: the
        cap-shrink observer is exactly where accumulated counter drift
        would evict too little (a stale-high counter over-evicts, which
        merely wastes cache; a stale-LOW counter leaves the cache over
        the new cap forever) — and the HBM ledger reconciliation exists
        to expose precisely that drift class."""
        if max_bytes is None:
            return
        with self._lock:
            self.max_bytes = int(max_bytes)
            self._bytes = sum(e.nbytes for e in self._entries.values())
            self._evict_to_fit_locked(0)

    @property
    def enabled(self) -> bool:
        return self.max_bytes > 0

    # -- producer side -------------------------------------------------------

    def put(self, obj, shard: int, generation, data, off: int = 0) -> bool:
        """Commit one chunk's bytes to the device and cache the buffer.
        ``data`` is host bytes/ndarray (flattened) or an already-committed
        device array.  No-ops while the backend is DEGRADED (a wedged
        runtime must not be handed fresh work) or when the item alone
        exceeds the bound."""
        if not self.enabled or generation is None:
            return False
        from .guard import DeviceTimeout, device_guard

        if device_guard().degraded:
            return False
        if isinstance(data, (bytes, bytearray, memoryview)):
            arr = np.frombuffer(data, dtype=np.uint8)
        else:
            arr = np.asarray(data, dtype=np.uint8).reshape(-1)
        nbytes = arr.nbytes
        if nbytes == 0 or nbytes > self.max_bytes:
            return False
        try:
            import jax

            # deadline-guarded like every other device wait: a wedged
            # runtime can HANG device_put, and the producer sits on the
            # decode-materialize path
            buf = device_guard().call(
                lambda: jax.device_put(arr), what="cache put"
            )
        except DeviceTimeout as e:
            # the commit wedged: degrade (which clears this cache) so
            # every path stops trusting the runtime, and fail the put
            device_guard().mark_degraded(f"cache put: {e}")
            return False
        except Exception:
            # a broken runtime must never fail the producer — but the
            # refusal is counted (`cache.put_failures` on the perf dump),
            # not invisible
            self.put_failures += 1
            return False
        with self._lock:
            key = (obj, int(shard), int(off))
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old.nbytes
                self._by_obj[obj].discard(key)
                if old.mem is not None:
                    old.mem.free()
            self._evict_to_fit_locked(nbytes)
            self._entries[key] = _Entry(
                buf, nbytes, generation, off,
                mem=_hbm_ledger().alloc("device_cache", nbytes, buf=buf),
            )
            self._by_obj.setdefault(obj, set()).add(key)
            self._bytes += nbytes
            self.insertions += 1
        return True

    def _evict_lru_one_locked(self) -> int:
        """Evict the single LRU entry (counter + ledger + index
        bookkeeping in ONE place); returns its bytes."""
        key, entry = self._entries.popitem(last=False)
        self._bytes -= entry.nbytes
        if entry.mem is not None:
            entry.mem.free()
        keys = self._by_obj.get(key[0])
        if keys is not None:
            keys.discard(key)
            if not keys:
                del self._by_obj[key[0]]
        self.evictions += 1
        return entry.nbytes

    def _evict_to_fit_locked(self, incoming: int) -> None:
        while self._entries and self._bytes + incoming > self.max_bytes:
            self._evict_lru_one_locked()

    def trim_for_pressure(self, nbytes: int) -> int:
        """Evict LRU-first until at least `nbytes` were released (or
        the cache is empty); returns the bytes freed.  The HBM pressure
        layer's stage-1 action (common/mempool.py): cached chunks are
        rebuildable pure optimization — the cheapest resident bytes to
        give back."""
        freed = 0
        with self._lock:
            while self._entries and freed < nbytes:
                freed += self._evict_lru_one_locked()
        return freed

    def replace(self, obj, shard: int, generation, buf, off: int = 0) -> bool:
        """Commit an ALREADY-DEVICE-RESIDENT buffer under a new
        generation — the RMW delta path's parity/data commit (ISSUE 18):
        the delta kernel's output never leaves HBM, so there is no host
        array to ``put``; the generation bumps in place and only the
        ledger re-accounts.  Counts on ``delta_updates``."""
        if not self.enabled or generation is None:
            return False
        from .guard import device_guard

        if device_guard().degraded:
            return False
        nbytes = int(buf.nbytes)
        if nbytes == 0 or nbytes > self.max_bytes:
            return False
        with self._lock:
            key = (obj, int(shard), int(off))
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old.nbytes
                self._by_obj[obj].discard(key)
                if old.mem is not None:
                    old.mem.free()
            self._evict_to_fit_locked(nbytes)
            self._entries[key] = _Entry(
                buf, nbytes, generation, off,
                mem=_hbm_ledger().alloc("device_cache", nbytes, buf=buf),
            )
            self._by_obj.setdefault(obj, set()).add(key)
            self._bytes += nbytes
            self.insertions += 1
            self.delta_updates += 1
        return True

    # -- consumer side -------------------------------------------------------

    def get_resident_many(
        self, obj, shards, generation, off: int = 0,
        length: int | None = None,
    ) -> dict | None:
        """All-or-nothing consult returning the DEVICE buffers — no D2H,
        no flight record: the RMW delta read leg (ISSUE 18).  The caller
        composes these into ONE delta launch whose flight record shows
        h2d_s == d2h_s == 0; a partial hit returns None (the materialize
        path re-encodes anyway, so serving half would be pure waste).
        The returned buffers stay valid even if a subsequent put/replace
        supersedes their keys (the arrays are refcounted)."""
        shards = list(shards)
        if not shards or not self.enabled:
            return None
        with self._lock:
            out = {}
            for s in shards:
                entry = self._entries.get((obj, int(s), int(off)))
                if (
                    entry is None
                    or entry.generation != generation
                    or (length is not None and entry.nbytes < length)
                ):
                    self.misses += len(shards)
                    return None
                out[int(s)] = entry.buf
            for s in shards:
                self._entries.move_to_end((obj, int(s), int(off)))
            self.hits += len(shards)
        return out

    def get(self, obj, shard: int, generation, off: int = 0,
            length: int | None = None):
        """The cached device buffer for (obj, shard, generation, off), or
        None.  ``length`` (bytes) must fit inside the stored buffer."""
        with self._lock:
            key = (obj, int(shard), int(off))
            entry = self._entries.get(key)
            if (
                entry is None
                or entry.generation != generation
                or (length is not None and entry.nbytes < length)
            ):
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return entry.buf

    def fetch_many(
        self, obj, shards, generation, off: int = 0,
        length: int | None = None, kind: str = "decode", stripes: int = 0,
    ) -> dict[int, np.ndarray] | None:
        """Serve a whole missing-chunk set from HBM, or None when ANY
        chunk misses (an all-or-nothing consult: a partial hit still
        needs the decode launch, so serving half would be pure waste).

        On a full hit the D2H copies are timed and committed as ONE
        ``cache_hit``-flagged flight record with h2d_s = kernel_s = 0 —
        the timeline proof that this path skipped the H2D leg entirely.
        """
        shards = list(shards)
        if not shards or not self.enabled:
            return None
        with self._lock:
            entries = []
            for s in shards:
                entry = self._entries.get((obj, int(s), int(off)))
                if (
                    entry is None
                    or entry.generation != generation
                    or (length is not None and entry.nbytes < length)
                ):
                    self.misses += len(shards)
                    return None
                entries.append(entry)
            for s in shards:
                self._entries.move_to_end((obj, int(s), int(off)))
        from .guard import device_guard

        def _copy_out():
            res: dict[int, np.ndarray] = {}
            n = 0
            with section("device.d2h") as d2h:
                for s, entry in zip(shards, entries):
                    host = np.asarray(entry.buf)
                    if length is not None and host.nbytes > length:
                        host = host[:length]
                    res[int(s)] = host
                    n += host.nbytes
                d2h.nbytes = n
            return res, n

        t0 = time.monotonic()
        try:
            # deadline-guarded like every other device wait: on a wedged
            # runtime np.asarray blocks forever, and this consult sits on
            # the degraded-read path the guard exists to protect
            out, nbytes = device_guard().call(_copy_out, what="cache fetch")
        except Exception as e:
            # the D2H hung or failed: degrade (which clears this cache)
            # and report a MISS so the caller's decode launch takes the
            # guarded host-fallback path instead of hanging here
            device_guard().mark_degraded(f"cache fetch: {e}")
            with self._lock:
                self.misses += len(shards)
            return None
        d2h_s = time.monotonic() - t0
        with self._lock:
            self.hits += len(shards)
            self.served_bytes += nbytes
        self._record_hit(kind, stripes or len(shards), nbytes, d2h_s)
        return out

    @staticmethod
    def _record_hit(kind: str, stripes: int, nbytes: int, d2h_s: float) -> None:
        """Flight record for a cache-served read: no queue wait, no H2D,
        no kernel — only the D2H copy of the resident chunks."""
        from .flight_recorder import flight_recorder, new_record

        rec = new_record(kind, group="#cache", stripes=stripes,
                         batch=stripes, nbytes=nbytes)
        now = time.monotonic()
        rec["dispatch_ts"] = now - d2h_s
        rec["submit_ts"] = rec["dispatch_ts"]
        rec["complete_ts"] = rec["dispatch_ts"]
        rec["d2h_s"] = d2h_s
        rec["flags"]["cache_hit"] = True
        flight_recorder().commit(rec)

    # -- invalidation --------------------------------------------------------

    def invalidate_object(self, obj) -> int:
        """Drop every entry of one object (any shard/offset): the
        overwrite hook.  Returns how many entries died."""
        with self._lock:
            doomed = self._by_obj.pop(obj, None)
            if not doomed:
                return 0
            for key in doomed:
                entry = self._entries.pop(key)
                self._bytes -= entry.nbytes
                if entry.mem is not None:
                    entry.mem.free()
            self.invalidations += len(doomed)
            return len(doomed)

    def clear(self) -> None:
        """Drop everything (the DEGRADED-transition hook): buffers on a
        wedged runtime are unreachable, and the host path needs none."""
        with self._lock:
            self.invalidations += len(self._entries)
            for entry in self._entries.values():
                if entry.mem is not None:
                    entry.mem.free()
            self._entries.clear()
            self._by_obj.clear()
            self._bytes = 0

    # -- introspection -------------------------------------------------------

    def perf_dump(self) -> dict[str, int]:
        """JSON-safe counters for the `ec_dispatch.cache_*` slice.
        `resident_bytes`/`entries` are gauges (they fall on eviction and
        invalidation); the rest are monotonic counters."""
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "insertions": self.insertions,
                "evictions": self.evictions,
                "invalidations": self.invalidations,
                "put_failures": self.put_failures,
                "delta_updates": self.delta_updates,
                "served_bytes": self.served_bytes,
                "resident_bytes": self._bytes,
                "entries": len(self._entries),
            }


_CACHE: DeviceChunkCache | None = None


def device_chunk_cache() -> DeviceChunkCache:
    """The process-wide (per-backend: one device runtime per process)
    cache, built lazily from option defaults like the device guard and
    the default aggregators; daemons with a live Config re-bound it
    through their runtime observers."""
    global _CACHE
    if _CACHE is None:
        _CACHE = DeviceChunkCache()
    return _CACHE
