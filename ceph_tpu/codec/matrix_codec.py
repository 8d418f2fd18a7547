"""Generic GF(2^8) matrix-codec machinery shared by every matrix technique.

Any systematic code defined by a (k+m, k) distribution matrix (RS, Cauchy,
jerasure variants, SHEC, LRC layers) gets its chunk-level and device-level
paths from this mixin; concrete codecs supply geometry + `build_matrix()`.

Caching mirrors the reference's two-level table cache
(/root/reference/src/erasure-code/isa/ErasureCodeIsaTableCache.{h,cc}):
encode plans per matrix, decode plans in a signature-keyed LRU (capacity 2516,
"sufficient up to (12,4)", ErasureCodeIsaTableCache.h:48) — but a cached
"table" here is a device bit-matrix operand for the shared XOR-matmul kernel,
so any erasure pattern reuses one compiled kernel per shape.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Mapping

import jax.numpy as jnp
import numpy as np

try:  # jax.core.Tracer is being removed from the public surface (jax >= 0.6)
    from jax.core import Tracer as _JaxTracer
except (ImportError, AttributeError):
    from jax._src.core import Tracer as _JaxTracer

from ceph_tpu.common.lockdep import make_lock as _lockdep_make_lock
from ceph_tpu.common.mempool import track_buffer as _hbm_track
from ceph_tpu.gf import expand_matrix, isa_decode_matrix
from ceph_tpu.ops.dispatch import record_launch
from ceph_tpu.ops.packed_gf import (
    PACKED_MIN_BYTES,
    PackedPlan,
    PackedVerifyPlan,
    packed_verify_host,
)
from ceph_tpu.ops.pallas_gf import CodingPlan
from ceph_tpu.ops.xor_mm import xor_matmul, xor_reduce

from .base import EIO
from .interface import EcError

DECODE_LRU_CAPACITY = 2516

# Host-oracle decode-plan memo (decode_array_host): pure-numpy expanded
# bit-matrices keyed by (distribution matrix, erasure pattern), bounded
# like the device-side decode LRU but kept fully separate from the
# jnp-backed PLAN_CACHE — degraded mode must never touch the runtime.
_HOST_DECODE_CAPACITY = 256
_HOST_DECODE_PLANS: "OrderedDict[tuple, np.ndarray]" = OrderedDict()
_HOST_DECODE_LOCK = _lockdep_make_lock("host_decode")


def _trace_local(x) -> bool:
    """True when `x` was created inside a jax.jit/vmap trace.  Trace-local
    values must NEVER enter the process-wide cache: a cached tracer
    poisons every later eager call with UnexpectedTracerError (first hit
    by bench.py's jitted serial chain warming the encode cache)."""
    return isinstance(x, _JaxTracer)

_PLATFORM: str | None = None


def _on_tpu() -> bool:
    """True when the default jax backend is a TPU (cached; backend init is
    expensive and the answer cannot change within a process).  A failed
    backend query raises: answering "cpu" would silently drop the Pallas
    kernel on a chip whose runtime did not come up."""
    global _PLATFORM
    if _PLATFORM is None:
        import jax

        _PLATFORM = jax.devices()[0].platform
    return _PLATFORM == "tpu"


class _DeviceCoder:
    """One cached coding operator: the fused Pallas kernel on TPU for
    lane-aligned chunks, the packed-bitplane jnp kernel for bulk work
    everywhere else, the bitsliced matmul for tiny one-off matrices.

    This is the dispatch the reference does by linking `ec_encode_data` to
    the best SIMD flavor at plugin load (isa/ErasureCodeIsa.cc:83-91): the
    production `encode_chunks`/`decode_chunks` path and the bulk device path
    both land on the fast kernel — the benchmark measures what ships.

    The small-input cutoff exists because the packed plan bakes its XOR
    schedule into the compiled program (one compile per matrix), while
    xor_matmul takes the bit-matrix as a runtime operand (one compile per
    shape, any matrix): decode paths that invert a fresh matrix per
    erasure pattern on small chunks stay on the shared kernel.
    """

    __slots__ = ("bm", "plan", "packed", "decode")

    def __init__(
        self,
        bm: jnp.ndarray,
        plan: CodingPlan | None,
        packed: PackedPlan,
        decode: bool = False,
    ):
        self.bm = bm
        self.plan = plan
        self.packed = packed
        # decode-kind coders (built from PLAN_CACHE.decode_coder/lru_coder)
        # also count their dispatches on ops.dispatch.DECODE_LAUNCHES
        self.decode = decode

    def shard_mesh_for(self, shape):
        """Mesh for a sharded dispatch at this input shape, or None for
        the single-device path.  Batched (..., k, L) inputs of at least
        PACKED_MIN_BYTES shard — lead dims collapse into one stripe axis
        (CLAY's (planes, S, k+nu, sc) fragment launches included); the
        threshold/width policy lives in parallel/dispatch.py (the
        ec_tpu_shard_* knobs)."""
        if len(shape) < 3 or int(np.prod(shape)) < PACKED_MIN_BYTES:
            return None
        from ceph_tpu.parallel import dispatch as shard_dispatch

        return shard_dispatch.shard_mesh(int(np.prod(shape[:-2])))

    def _shard_mesh(self, data):
        """shard_mesh_for, guarded against trace-local values: a batch
        traced inside an outer jit (bench.py's serial chain) must stay on
        the in-trace kernel — a device_put of a tracer poisons the
        trace."""
        if _trace_local(data):
            return None
        return self.shard_mesh_for(data.shape)

    def __call__(self, data: jnp.ndarray, out=None) -> jnp.ndarray:
        mesh = self._shard_mesh(data)
        if mesh is not None:
            # sharded dispatch mode (ISSUE 6): place the batch with a
            # NamedSharding over `stripe` and run the fused kernel
            # per-device via shard_map — one launch, the whole mesh
            from ceph_tpu.parallel.sharded import sharded_coder_code

            lead = data.shape[:-2]
            if len(lead) > 1:
                # collapse lead dims into the stripe axis (CLAY batched
                # fragments); a host reshape of the contiguous batch is a
                # view.  Donation skipped: the pooled buffer has the
                # caller's lead geometry, not the flattened one.
                flat = data.reshape(-1, *data.shape[-2:])
                res = sharded_coder_code(self, flat, mesh)
                return res.reshape(*lead, *res.shape[-2:])
            return sharded_coder_code(self, data, mesh, out=out)
        if self.plan is not None and data.shape[-1] % 128 == 0:
            return self.plan(data)
        if int(np.prod(data.shape)) >= PACKED_MIN_BYTES:
            return self.packed(data, out=out)
        lead = data.shape[:-2]
        record_launch(
            int(np.prod(lead)) if lead else 1,
            int(np.prod(data.shape)),
            decode=self.decode,
        )
        return xor_matmul(self.bm, data)


class _GlobalPlanCache:
    """Process-wide encode/decode plan cache keyed by matrix content."""

    def __init__(self) -> None:
        from ceph_tpu.common.lockdep import make_lock

        self._lock = make_lock("plan_cache")
        self._encode: dict[bytes, jnp.ndarray] = {}
        self._encode_coders: dict[bytes, _DeviceCoder] = {}
        self._decode: OrderedDict[tuple[bytes, str], tuple[jnp.ndarray, list[int]]] = (
            OrderedDict()
        )
        self._decode_coders: OrderedDict[tuple, _DeviceCoder] = OrderedDict()
        # verify plans per parity matrix (ISSUE 9): one compiled
        # compare-only kernel per encode matrix, unbounded like the
        # encode tables (the matrix population is the same)
        self._verify_plans: dict[tuple, PackedVerifyPlan] = {}
        # coder lookup hit/miss totals; the perf-smoke tier-1 test asserts
        # a steady-state hit rate so a regression to per-call plan builds
        # fails fast instead of only dilating the bench number
        self._hits = 0
        self._misses = 0

    def _make_coder(
        self, gf_rows: np.ndarray, bm: jnp.ndarray, decode: bool = False
    ) -> _DeviceCoder:
        plan = CodingPlan(gf_rows, decode=decode) if _on_tpu() else None
        return _DeviceCoder(bm, plan, PackedPlan(gf_rows, decode=decode), decode=decode)

    def stats(self) -> dict[str, int]:
        """Coder-cache hit/miss totals (encode + decode lookups)."""
        with self._lock:
            return {"hits": self._hits, "misses": self._misses}

    def reset_stats(self) -> None:
        with self._lock:
            self._hits = 0
            self._misses = 0

    def _lru_put_coder(self, key, coder: _DeviceCoder) -> None:
        self._decode_coders[key] = coder
        self._decode_coders.move_to_end(key)
        while len(self._decode_coders) > DECODE_LRU_CAPACITY:
            self._decode_coders.popitem(last=False)

    def encode_bit_matrix(self, coding_rows: np.ndarray) -> jnp.ndarray:
        """Per-geometry encode matrices: one entry per codec instance's
        matrix, unbounded like the reference's per-(k,m) encode tables."""
        key = (coding_rows.shape, coding_rows.tobytes())
        with self._lock:
            bm = self._encode.get(key)
        if bm is not None:
            return bm
        bm = jnp.asarray(expand_matrix(coding_rows), dtype=jnp.uint8)
        if _trace_local(bm):
            return bm
        _hbm_track(bm, "scratch")
        with self._lock:
            self._encode.setdefault(key, bm)
            return self._encode[key]

    def encode_coder(self, coding_rows: np.ndarray) -> _DeviceCoder:
        """Cached coding operator for an encode matrix (TPU plan + jnp bm)."""
        key = (coding_rows.shape, coding_rows.tobytes())
        with self._lock:
            coder = self._encode_coders.get(key)
            if coder is not None:
                self._hits += 1
            else:
                self._misses += 1
        if coder is not None:
            return coder
        coder = self._make_coder(coding_rows, self.encode_bit_matrix(coding_rows))
        if _trace_local(coder.bm):
            return coder
        with self._lock:
            return self._encode_coders.setdefault(key, coder)

    def verify_coder(self, coding_rows: np.ndarray) -> PackedVerifyPlan:
        """Cached compare-only verify plan for an encode matrix's parity
        rows (ISSUE 9 deep-scrub kernel)."""
        key = (coding_rows.shape, coding_rows.tobytes())
        with self._lock:
            plan = self._verify_plans.get(key)
            if plan is not None:
                self._hits += 1
                return plan
            self._misses += 1
        plan = PackedVerifyPlan(coding_rows)
        with self._lock:
            return self._verify_plans.setdefault(key, plan)

    def lru_coder(self, matrix: np.ndarray) -> _DeviceCoder:
        """Coding operator for a decode-time matrix, bounded by the decode
        LRU (SHEC's searched inverses and other raw-matrix decode paths)."""
        key = (matrix.shape, matrix.tobytes(), "#raw")
        with self._lock:
            coder = self._decode_coders.get(key)
            if coder is not None:
                self._hits += 1
                self._decode_coders.move_to_end(key)
                return coder
            self._misses += 1
        coder = self._make_coder(matrix, self.lru_bit_matrix(matrix), decode=True)
        if _trace_local(coder.bm):
            return coder
        with self._lock:
            self._lru_put_coder(key, coder)
        return coder

    def lru_bit_matrix(self, matrix: np.ndarray) -> jnp.ndarray:
        """Bit-matrix for a decode-time matrix, bounded by the decode LRU.

        For codecs whose decode matrices vary per erasure pattern but don't
        go through decode_plan (SHEC's searched inverses) — stored alongside
        the signature-keyed plans so total decode-table memory stays within
        DECODE_LRU_CAPACITY, as the reference's cache guarantees.
        """
        key = (matrix.shape, matrix.tobytes(), "#raw")
        with self._lock:
            cached = self._decode.get(key)
            if cached is not None:
                self._decode.move_to_end(key)
                return cached[0]
        bm = jnp.asarray(expand_matrix(matrix), dtype=jnp.uint8)
        if _trace_local(bm):
            return bm
        _hbm_track(bm, "scratch")
        with self._lock:
            self._decode[key] = (bm, [])
            self._decode.move_to_end(key)
            while len(self._decode) > DECODE_LRU_CAPACITY:
                self._decode.popitem(last=False)
        return bm

    def gf2_decode_plan(
        self, bitmatrix: np.ndarray, k: int, w: int, erasures: list[int]
    ) -> tuple[np.ndarray, list[int]]:
        """Decode plan for a packetized GF(2) bit-matrix RAID-6 code
        (liberation family): (decode matrix (len(erasures)*w, k*w),
        decode_index).  Shares the one decode LRU so total decode-table
        memory stays within DECODE_LRU_CAPACITY."""
        from ceph_tpu.gf.gf2 import gf2_inv, gf2_matmul

        n = k + bitmatrix.shape[0] // w
        erased = set(erasures)
        decode_index = [c for c in range(n) if c not in erased][:k]
        if len(decode_index) < k:
            raise EcError(EIO, f"not enough survivors for erasures {erasures}")
        key = (bitmatrix.shape, bitmatrix.tobytes(), "#gf2", tuple(erasures))
        with self._lock:
            cached = self._decode.get(key)
            if cached is not None:
                self._decode.move_to_end(key)
                return cached
        # full generator: data identity rows then the coding rows (the
        # bitmatrix already carries both the P-identity and Q blocks)
        full = np.zeros((n * w, k * w), dtype=np.uint8)
        full[: k * w] = np.eye(k * w, dtype=np.uint8)
        full[k * w :] = bitmatrix
        survivors = np.vstack([full[c * w : (c + 1) * w] for c in decode_index])
        inv = gf2_inv(survivors)
        if inv is None:
            raise EcError(EIO, f"singular decode matrix for erasures {erasures}")
        erased_rows = np.vstack([full[c * w : (c + 1) * w] for c in erasures])
        plan = (gf2_matmul(erased_rows, inv), decode_index)
        with self._lock:
            self._decode[key] = plan
            self._decode.move_to_end(key)
            while len(self._decode) > DECODE_LRU_CAPACITY:
                self._decode.popitem(last=False)
        return plan

    def decode_plan(
        self, dist_matrix: np.ndarray, erasures: list[int], k: int
    ) -> tuple[jnp.ndarray, list[int]]:
        bitmat, decode_index, _ = self._decode_entry(dist_matrix, erasures, k)
        return bitmat, decode_index

    def _decode_entry(
        self, dist_matrix: np.ndarray, erasures: list[int], k: int, key=None
    ) -> tuple[jnp.ndarray, list[int], np.ndarray]:
        """(bit-matrix, decode_index, GF decode matrix) for an erasure
        pattern, LRU-cached.  The GF matrix rides along so a coder rebuild
        after a coder-LRU eviction is a cheap re-arrangement, not a second
        Gaussian inversion."""
        if key is None:
            key = self._decode_key(dist_matrix, erasures, k)
        with self._lock:
            cached = self._decode.get(key)
            if cached is not None:
                self._decode.move_to_end(key)
                return cached
        plan = isa_decode_matrix(dist_matrix, erasures, k)
        if plan is None:
            raise EcError(EIO, f"singular decode matrix for erasures {erasures}")
        c, decode_index = plan
        bitmat = jnp.asarray(expand_matrix(c), dtype=jnp.uint8)
        if _trace_local(bitmat):
            return bitmat, decode_index, c
        _hbm_track(bitmat, "scratch")
        with self._lock:
            self._decode[key] = (bitmat, decode_index, c)
            self._decode.move_to_end(key)
            while len(self._decode) > DECODE_LRU_CAPACITY:
                self._decode.popitem(last=False)
        return bitmat, decode_index, c

    def _decode_key(
        self, dist_matrix: np.ndarray, erasures: list[int], k: int
    ) -> tuple:
        """Reference signature format, ErasureCodeIsa.cc:233-248 (the
        survivor part uses the first-k-non-erased rows, matching decode_plan's
        key derivation even when isa_decode_matrix picks different rows)."""
        km = dist_matrix.shape[0]
        erased = set(erasures)
        survivors: list[int] = []
        r = 0
        for _ in range(k):
            while r in erased:
                r += 1
            if r >= km:
                raise EcError(EIO, f"not enough survivors for erasures {erasures}")
            survivors.append(r)
            r += 1
        sig = "".join(f"+{r}" for r in survivors) + "".join(
            f"-{e}" for e in erasures
        )
        return (dist_matrix.shape, dist_matrix.tobytes(), sig)

    def decode_coder(
        self, dist_matrix: np.ndarray, erasures: list[int], k: int
    ) -> tuple[_DeviceCoder, list[int]]:
        """Cached coding operator + survivor index for an erasure pattern."""
        key = self._decode_key(dist_matrix, erasures, k)
        bitmat, decode_index, c = self._decode_entry(dist_matrix, erasures, k, key)
        with self._lock:
            coder = self._decode_coders.get(key)
            if coder is not None:
                self._hits += 1
                self._decode_coders.move_to_end(key)
                return coder, decode_index
            self._misses += 1
        coder = self._make_coder(c, bitmat, decode=True)  # built outside the lock
        if _trace_local(coder.bm):
            return coder, decode_index
        with self._lock:
            self._lru_put_coder(key, coder)
        return coder, decode_index


PLAN_CACHE = _GlobalPlanCache()


def _coder_donatable(coder: _DeviceCoder, data_shape) -> bool:
    """Will a dispatch through `coder` at this (already >= packed-size)
    input shape actually consume a donated out= buffer?  Mirrors the
    _DeviceCoder dispatch exactly: the packed jnp kernel donates; the
    Pallas plan ignores `out`; a SHARDED launch donates only on the
    packed path with no remainder padding (a padded launch's output
    shape differs from the pooled logical-shape buffer)."""
    mesh = coder.shard_mesh_for(tuple(data_shape))
    if mesh is not None:
        if len(data_shape) != 3:
            return False  # flattened-lead launches skip donation
        if coder.plan is not None and data_shape[-1] % 128 == 0:
            return False
        from ceph_tpu.parallel.sharded import _stripe_shards

        return data_shape[0] % _stripe_shards(mesh) == 0
    return not (coder.plan is not None and data_shape[-1] % 128 == 0)


# The aggregation engine (ISSUE 20): AggTicket / DonationPool /
# _PadBuckets / _AggGroup / LaunchAggregator and the process-wide
# aggregator set moved to the service-agnostic offload runtime.  They
# are re-exported here verbatim — every existing import path
# (`from ceph_tpu.codec.matrix_codec import LaunchAggregator, ...`)
# keeps working, and the EC aggregators below are now plain service
# subclasses of the shared engine.
from ceph_tpu.ops.offload_runtime import (  # noqa: F401  (re-exports)
    _AGGREGATORS,
    AggTicket,
    DonationPool,
    LaunchAggregator,
    _AggGroup,
    _next_pow2,
    _PadBuckets,
    drain_all_aggregators,
    drop_donation_retention,
)



class EncodeAggregator(LaunchAggregator):
    """Cross-write launch aggregation: concurrent stripe encodes of one
    (matrix, chunk-size) geometry coalesce into one padded device launch
    (knobs `ec_tpu_aggregate_window` / `ec_tpu_aggregate_max_bytes`)."""

    PERF_NAME = "ec_aggregator"
    WHAT = "encode"

    def submit(self, ec: "MatrixCodecMixin", shaped: np.ndarray) -> AggTicket:
        """Queue one (stripes, k, L) uint8 encode; returns its ticket."""
        return self._submit(
            (ec.distribution_matrix().tobytes(), shaped.shape[-1]), ec, None, shaped
        )

    def _dispatch(self, g: _AggGroup, data: np.ndarray, donate):
        return g.ec.encode_array(data, out=donate)

    def _dispatch_host(self, g: _AggGroup, data: np.ndarray) -> np.ndarray:
        return g.ec.encode_array_host(data)

    def _out_shape(self, g: _AggGroup, data_shape) -> tuple:
        return (
            data_shape[0],
            g.ec.get_chunk_count() - data_shape[1],
            data_shape[2],
        )

    def _donate_ok(self, g: _AggGroup, data_shape) -> bool:
        check = getattr(g.ec, "encode_donatable", None)
        return bool(check(data_shape)) if check is not None else False


class DecodeAggregator(LaunchAggregator):
    """Cross-op DECODE launch aggregation — the recovery/degraded-read
    twin of EncodeAggregator (knobs `ec_tpu_decode_aggregate_window` /
    `ec_tpu_decode_aggregate_max_bytes`).

    Submissions are (stripes, k, L) survivor batches in decode_index
    order, keyed by the cached decode-plan signature + chunk length: the
    common case during recovery/backfill is ONE erasure pattern repeating
    across every object in the PG, so per-object decodes coalesce into
    one padded launch exactly like concurrent writes do on the encode
    side.  Tickets resolve to (stripes, len(erasures), L) reconstructed
    chunks, rows in erasure order; a failed launch is sticky on its group
    and reported at every co-rider's reap."""

    PERF_NAME = "ec_decode_aggregator"
    WHAT = "decode"
    SCHED_CLASS = "recovery"

    def submit(
        self, ec: "MatrixCodecMixin", erasures: list[int], survivors: np.ndarray
    ) -> AggTicket:
        """Queue one (stripes, k, L) uint8 survivor batch (decode_index
        order); returns its ticket.  Co-riders share a group only when
        their decode-plan signature matches, so every ticket in a group
        agrees on the erasure row order."""
        erasures = list(erasures)
        key = PLAN_CACHE._decode_key(
            ec.distribution_matrix(), erasures, ec.k
        ) + (survivors.shape[-1],)
        return self._submit(key, ec, tuple(erasures), survivors)

    def _dispatch(self, g: _AggGroup, data: np.ndarray, donate):
        return g.ec.decode_array(list(g.ctx), data, out=donate)

    def _dispatch_host(self, g: _AggGroup, data: np.ndarray) -> np.ndarray:
        return g.ec.decode_array_host(list(g.ctx), data)

    def _out_shape(self, g: _AggGroup, data_shape) -> tuple:
        return (data_shape[0], len(g.ctx), data_shape[2])

    def _donate_ok(self, g: _AggGroup, data_shape) -> bool:
        check = getattr(g.ec, "decode_donatable", None)
        return bool(check(list(g.ctx), data_shape)) if check is not None else False


class VerifyAggregator(LaunchAggregator):
    """Cross-object VERIFY launch aggregation (ISSUE 9): deep-scrub
    parity recomputes from one (matrix, chunk-length) geometry coalesce
    into one compare-only device launch (knobs
    `ec_tpu_verify_aggregate_window` / `ec_tpu_verify_aggregate_max_bytes`).

    Submissions are (stripes, k+m, L) full-codeword batches — data rows
    in encode order followed by the stored parity rows — and tickets
    resolve to a (stripes,) uint8 per-stripe mismatch bitmap (bit j set
    = parity row j inconsistent).  Padding stripes are all-zero
    codewords, whose recomputed parity is zero = their stored parity,
    so a padded launch's bitmap is exact.  Launches dispatch under the
    `background` QoS lane: a scrub chunk's verify never preempts a
    queued client encode, and the host-oracle fallback keeps scrub
    byte-identical while the backend is DEGRADED."""

    PERF_NAME = "ec_verify_aggregator"
    WHAT = "verify"
    SCHED_CLASS = "background"
    MEM_POOL = "verify"

    def submit(self, ec: "MatrixCodecMixin", codewords: np.ndarray) -> AggTicket:
        """Queue one (stripes, k+m, L) uint8 codeword batch; the ticket
        resolves to its (stripes,) mismatch bitmap."""
        return self._submit(
            (ec.distribution_matrix().tobytes(), "#verify",
             codewords.shape[-1]),
            ec, None, codewords,
        )

    def _dispatch(self, g: _AggGroup, data: np.ndarray, donate):
        return g.ec.verify_array(data)

    def _dispatch_host(self, g: _AggGroup, data: np.ndarray) -> np.ndarray:
        return g.ec.verify_array_host(data)

    def _out_shape(self, g: _AggGroup, data_shape) -> tuple:
        return (data_shape[0],)

    def _donate_ok(self, g: _AggGroup, data_shape) -> bool:
        return False  # the bitmap output is tiny; pooling buys nothing




_DEFAULT_AGGREGATOR: EncodeAggregator | None = None


def default_encode_aggregator() -> EncodeAggregator:
    """Process-wide aggregator shared by every ECBackend that isn't handed
    its own — the sharing is what coalesces encodes ACROSS PGs.  Built
    from the option-table defaults (common/options.py); daemons with a
    live Config can construct and inject their own."""
    global _DEFAULT_AGGREGATOR
    if _DEFAULT_AGGREGATOR is None:
        from ceph_tpu.common.options import OPTIONS

        _DEFAULT_AGGREGATOR = EncodeAggregator(
            window=int(OPTIONS["ec_tpu_aggregate_window"].default),
            max_bytes=int(OPTIONS["ec_tpu_aggregate_max_bytes"].default),
        )
    return _DEFAULT_AGGREGATOR


_DEFAULT_DECODE_AGGREGATOR: DecodeAggregator | None = None


def default_decode_aggregator() -> DecodeAggregator:
    """Process-wide decode aggregator shared by every ECBackend that isn't
    handed its own, so recovery/degraded-read decodes coalesce ACROSS PGs
    on one OSD (the backfill case: one erasure pattern, many objects)."""
    global _DEFAULT_DECODE_AGGREGATOR
    if _DEFAULT_DECODE_AGGREGATOR is None:
        from ceph_tpu.common.options import OPTIONS

        _DEFAULT_DECODE_AGGREGATOR = DecodeAggregator(
            window=int(OPTIONS["ec_tpu_decode_aggregate_window"].default),
            max_bytes=int(OPTIONS["ec_tpu_decode_aggregate_max_bytes"].default),
        )
    return _DEFAULT_DECODE_AGGREGATOR


_DEFAULT_VERIFY_AGGREGATOR: VerifyAggregator | None = None


def default_verify_aggregator() -> VerifyAggregator:
    """Process-wide verify aggregator shared by every scrubber on one
    OSD, so concurrent deep scrubs of different PGs coalesce their
    parity recomputes into shared compare-only launches.  The default
    window is open (unlike encode/decode): scrub is a throughput
    workload with no commit barrier, so batching is pure win — the
    scrubber's per-chunk reap is the flush."""
    global _DEFAULT_VERIFY_AGGREGATOR
    if _DEFAULT_VERIFY_AGGREGATOR is None:
        from ceph_tpu.common.options import OPTIONS

        _DEFAULT_VERIFY_AGGREGATOR = VerifyAggregator(
            window=int(OPTIONS["ec_tpu_verify_aggregate_window"].default),
            max_bytes=int(OPTIONS["ec_tpu_verify_aggregate_max_bytes"].default),
        )
    return _DEFAULT_VERIFY_AGGREGATOR


# The EC trio are the offload runtime's first three service entries
# (ISSUE 20): same singletons, same knobs, same perf names — the
# registry only ADDS a uniform by-name surface (service_aggregator /
# offload_perf_dump) on top of the existing factories.
from ceph_tpu.ops.offload_runtime import register_service as _register_service

_register_service(
    "encode", default_encode_aggregator, lane="client",
    oracle="MatrixCodecMixin.encode_array_host",
    doc="EC stripe encode (parity generation)",
)
_register_service(
    "decode", default_decode_aggregator, lane="recovery",
    oracle="MatrixCodecMixin.decode_array_host",
    doc="EC reconstruct decode (recovery / degraded reads)",
)
_register_service(
    "verify", default_verify_aggregator, lane="background",
    oracle="MatrixCodecMixin.verify_array_host",
    doc="EC deep-scrub compare-only verify",
)


class EncodePipeline:
    """Asynchronous chunk-encode hand-off — the completion queue behind
    the synchronous `encode_chunks` interface (SURVEY §7's hard part).

    `submit` stages the host->device transfer and LAUNCHES the encode
    immediately (JAX dispatch is asynchronous: the call returns while the
    device works), so consecutive submissions overlap compute with the
    host-side gather of the next batch — the double-buffering the
    reference gets from queued librados AIO in front of `ec_encode_data`.
    Completions copy parity back into the caller's chunk buffers exactly
    like `encode_chunks`; `poll()` reaps only finished launches
    (non-blocking), `flush()` drains everything.  `depth` bounds
    device-side in-flight work the way an AIO queue depth does.
    """

    def __init__(self, codec: "MatrixCodecMixin", depth: int = 4):
        self.codec = codec
        self.depth = max(1, depth)
        self._tickets = 0
        # in-flight: (ticket, caller chunk dict, device parity array)
        self._inflight: list[tuple[int, Mapping[int, np.ndarray], object]] = []
        # tickets completed inside submit's backpressure path: the next
        # poll()/flush() reports them — a completed ticket is NEVER lost
        self._reaped: list[int] = []

    def submit(self, chunks: Mapping[int, np.ndarray]) -> int:
        """Launch one stripe's encode; returns its ticket.  Blocks only
        when `depth` launches are already in flight (backpressure)."""
        parity_dev = self.codec.encode_array(self.codec._gather(chunks))
        self._tickets += 1
        self._inflight.append((self._tickets, chunks, parity_dev))
        while len(self._inflight) > self.depth:
            self._reaped += self._complete(*self._inflight.pop(0))
        return self._tickets

    def _complete(self, ticket: int, chunks, parity_dev) -> list[int]:
        parity = np.asarray(parity_dev)  # blocks until the launch finishes
        self.codec._scatter(chunks, parity)
        return [ticket]

    def poll(self) -> list[int]:
        """Reap FINISHED launches without blocking (completion queue)."""
        done, self._reaped = self._reaped, []
        while self._inflight:
            ticket, chunks, dev = self._inflight[0]
            ready = getattr(dev, "is_ready", None)
            # unknown readiness means NOT ready: popping would block in
            # _complete and silently defeat the non-blocking contract
            if ready is None or not ready():
                break  # still computing; keep submission order
            self._inflight.pop(0)
            done += self._complete(ticket, chunks, dev)
        return done

    def flush(self) -> list[int]:
        """Drain every in-flight encode (the barrier before a commit)."""
        done, self._reaped = self._reaped, []
        while self._inflight:
            done += self._complete(*self._inflight.pop(0))
        return done


class MatrixCodecMixin:
    """Chunk-level + device-level coding for matrix-defined codecs.

    Host contract: the concrete class provides `self.k`, `self.m`,
    `chunk_index()` (from ErasureCode) and `build_matrix() -> (k+m, k)`
    systematic uint8 distribution matrix.
    """

    _dist_matrix: np.ndarray | None = None

    def build_matrix(self) -> np.ndarray:  # pragma: no cover - abstract
        raise NotImplementedError

    def invalidate_matrix(self) -> None:
        """Drop the cached distribution matrix; call on (re)parse so a
        second init() with new geometry cannot serve the stale matrix."""
        self._dist_matrix = None

    def distribution_matrix(self) -> np.ndarray:
        if self._dist_matrix is None:
            mat = np.asarray(self.build_matrix(), dtype=np.uint8)
            k, m = self.k, self.m
            assert mat.shape == (k + m, k), mat.shape
            assert np.array_equal(mat[:k], np.eye(k, dtype=np.uint8)), (
                "distribution matrix must be systematic"
            )
            self._dist_matrix = mat
        return self._dist_matrix

    def _xor_row_available(self) -> bool:
        """True when parity row 0 is all ones (enables XOR fast paths)."""
        mat = self.distribution_matrix()
        return bool((mat[self.k] == 1).all())

    # -- device-native bulk paths ------------------------------------------

    def encode_array(self, data, out=None) -> jnp.ndarray:
        """(..., k, L) uint8 -> (..., m, L) parity, stays on device.

        Dispatches through the cached _DeviceCoder, so on a TPU backend this
        IS the fused Pallas kernel — the production analog of the reference
        plugin's `ec_encode_data` hot call (isa/ErasureCodeIsa.cc:83-91).

        `out`: optional dead device buffer of the parity's shape, donated
        into the packed kernel so recurring aggregated launches reuse the
        allocation (ignored on paths that cannot donate)."""
        mat = self.distribution_matrix()
        if self.m == 1 and self._xor_row_available():
            arr = jnp.asarray(data)
            lead = arr.shape[:-2]
            record_launch(int(np.prod(lead)) if lead else 1, int(np.prod(arr.shape)))
            return xor_reduce(arr)[..., None, :]
        # host batches pass through un-placed: the coder's sharded mode
        # does ONE sharded device_put (a premature jnp.asarray would
        # commit to device 0 and pay a second reshard copy)
        arr = data if isinstance(data, np.ndarray) else jnp.asarray(data)
        return PLAN_CACHE.encode_coder(mat[self.k :])(arr, out=out)

    def encode_donatable(self, data_shape) -> bool:
        """True when encode_array(data, out=...) at this input shape will
        actually consume a donated parity buffer — i.e. the dispatch lands
        on the packed jnp kernel.  The EncodeAggregator gates its donation
        pool on this so it never hoards dead device memory for paths
        (xor_reduce, Pallas, small-matmul) that ignore `out`."""
        mat = self.distribution_matrix()
        if self.m == 1 and self._xor_row_available():
            return False
        if int(np.prod(data_shape)) < PACKED_MIN_BYTES:
            return False
        coder = PLAN_CACHE.encode_coder(mat[self.k :])
        return _coder_donatable(coder, data_shape)

    def decode_array(self, erasures: list[int], survivors, out=None) -> jnp.ndarray:
        """survivors (..., k, L) in decode_index order -> (..., nerrs, L).

        The decode twin of encode_array: dispatches through the cached
        erasure-pattern _DeviceCoder (Pallas on TPU-aligned chunks, packed
        planes for bulk work, bitsliced matmul for small one-off
        patterns).  `out`: optional dead device buffer of the
        reconstruction's shape, donated into the packed kernel so
        recurring aggregated recovery launches reuse the allocation."""
        coder, _ = PLAN_CACHE.decode_coder(self.distribution_matrix(), erasures, self.k)
        arr = survivors if isinstance(survivors, np.ndarray) else jnp.asarray(survivors)
        return coder(arr, out=out)

    def decode_donatable(self, erasures: list[int], data_shape) -> bool:
        """True when decode_array(erasures, data, out=...) at this input
        shape will actually consume a donated output buffer — the decode
        twin of encode_donatable, gating the DecodeAggregator's pool."""
        if int(np.prod(data_shape)) < PACKED_MIN_BYTES:
            return False
        coder, _ = PLAN_CACHE.decode_coder(
            self.distribution_matrix(), list(erasures), self.k
        )
        return _coder_donatable(coder, data_shape)

    def verify_array(self, codewords) -> jnp.ndarray:
        """(..., k+m, L) uint8 full codewords (data rows in encode order,
        then the stored parity rows) -> (...,) uint8 per-stripe mismatch
        bitmap, bit j set iff stored parity row j differs from the
        recompute.  The deep-scrub compare-only path (ISSUE 9): one
        fused kernel per matrix, batch-shaped exactly like encode_array
        so scrub rides the same aggregation machinery."""
        mat = self.distribution_matrix()
        return PLAN_CACHE.verify_coder(mat[self.k :])(jnp.asarray(codewords))

    def verify_array_host(self, codewords) -> np.ndarray:
        """Byte-identical HOST oracle of verify_array (pure numpy end to
        end): the DEGRADED-mode fallback the VerifyAggregator re-runs
        scrub verifies on — same bit-matrix parity recompute, same
        bitmap packing, and it can never hang on a wedged runtime."""
        mat = self.distribution_matrix()
        return packed_verify_host(
            mat[self.k :], np.asarray(codewords, dtype=np.uint8)
        )

    def encode_array_host(self, data) -> np.ndarray:
        """Byte-identical HOST oracle of encode_array: pure numpy end to
        end, so a wedged device runtime can never hang it.  This is the
        DEGRADED-mode fallback the launch watchdog (ops/guard.py) re-runs
        aggregated encodes on — same xor fast path gate, and since
        ISSUE 11 the SAME reduced plane program the device kernel
        compiles (ops/packed_gf.packed_code_host), so the oracle is
        derived from the schedule rather than re-derived from the
        matrix — the two paths cannot drift, and the fallback runs the
        reduced XOR count too (plus an 8x smaller working set than the
        bit-plane expansion)."""
        mat = self.distribution_matrix()
        arr = np.asarray(data, dtype=np.uint8)
        if self.m == 1 and self._xor_row_available():
            return np.bitwise_xor.reduce(arr, axis=-2)[..., None, :]
        from ceph_tpu.ops.packed_gf import packed_code_host

        return packed_code_host(mat[self.k :], arr)

    def encode_delta_device(
        self, old_bufs, new_bufs, parity_bufs, chunk: int
    ) -> jnp.ndarray:
        """RMW parity delta, fully on device (ISSUE 18): k + k + m FLAT
        per-shard device buffers (the chunk cache's native layout) ->
        (stripes, m, chunk) NEW parity in ONE fused launch.  The code is
        GF(2)-linear, so parity_new = parity_old ^ Encode(old ^ new)
        with Encode the SAME reduced plane program `encode_array`'s
        packed path compiles — the delta path cannot drift byte-wise
        from a full re-encode.  Counts exactly one dispatch on the
        launch gauges (`devices_per_launch` stays consistent)."""
        from ceph_tpu.ops.packed_gf import _packed_delta_flat, best_program

        mat = self.distribution_matrix()
        prog = best_program(mat[self.k :])
        stripes = int(old_bufs[0].size) // int(chunk)
        nbytes = sum(
            int(b.size)
            for bufs in (old_bufs, new_bufs, parity_bufs)
            for b in bufs
        )
        record_launch(stripes, nbytes)
        return _packed_delta_flat(
            tuple(old_bufs), tuple(new_bufs), tuple(parity_bufs),
            sched=prog, k=self.k, m=self.m, chunk=int(chunk),
        )

    def encode_delta_host(
        self, old_data, new_data, old_parity
    ) -> np.ndarray:
        """Byte-identical HOST oracle of encode_delta_device (pure
        numpy): same chosen program via packed_delta_host, same xor
        composition — the anchor the delta-path byte-identity tests pin
        the device bytes against.  (S, k, L) old/new data + (S, m, L)
        old parity -> (S, m, L) new parity."""
        from ceph_tpu.ops.packed_gf import packed_delta_host

        mat = self.distribution_matrix()
        return packed_delta_host(
            mat[self.k :], old_data, new_data, old_parity
        )

    def decode_array_host(self, erasures: list[int], survivors) -> np.ndarray:
        """Byte-identical HOST oracle of decode_array (pure numpy): the
        decode plan is built with the same isa_decode_matrix Gaussian
        the cached coder was built from, so reconstruction through the
        fallback path matches the device result bit for bit.  Plans are
        memoized host-side (never through the jnp-backed PLAN_CACHE —
        a wedged runtime can hang any jnp call): degraded-mode recovery
        repeats ONE erasure pattern across many launches and must not
        pay the O(k^3) inversion each time."""
        from ceph_tpu.gf.bitslice import xor_matmul_host_batch

        dist = self.distribution_matrix()
        key = (dist.shape, dist.tobytes(), tuple(erasures))
        with _HOST_DECODE_LOCK:
            bm = _HOST_DECODE_PLANS.get(key)
            if bm is not None:
                _HOST_DECODE_PLANS.move_to_end(key)
        if bm is None:
            plan = isa_decode_matrix(dist, list(erasures), self.k)
            if plan is None:
                raise EcError(
                    EIO, f"singular decode matrix for erasures {erasures}"
                )
            c, _idx = plan
            bm = expand_matrix(c)
            with _HOST_DECODE_LOCK:
                _HOST_DECODE_PLANS[key] = bm
                _HOST_DECODE_PLANS.move_to_end(key)
                while len(_HOST_DECODE_PLANS) > _HOST_DECODE_CAPACITY:
                    _HOST_DECODE_PLANS.popitem(last=False)
        return xor_matmul_host_batch(bm, np.asarray(survivors, dtype=np.uint8))

    def decode_index(self, erasures: list[int]) -> list[int]:
        _, idx = PLAN_CACHE.decode_plan(self.distribution_matrix(), erasures, self.k)
        return idx

    # -- chunk-level interface ---------------------------------------------

    @staticmethod
    def _as_u8(buf) -> np.ndarray:
        """Normalize one chunk buffer to uint8 WITHOUT copying when
        avoidable: contiguous uint8 arrays (every ECBackend call site)
        pass through untouched, raw byte containers map zero-copy via
        frombuffer, and everything else goes through np.asarray — views
        stay views, so np.stack in the caller pays the gather's only
        copy (ascontiguousarray here would copy a second time)."""
        if type(buf) is np.ndarray and buf.dtype == np.uint8:
            return buf
        if isinstance(buf, (bytes, bytearray, memoryview)):
            return np.frombuffer(buf, dtype=np.uint8)
        return np.asarray(buf, dtype=np.uint8)

    def _gather(self, chunks: Mapping[int, np.ndarray]) -> np.ndarray:
        """Stack the k data chunks in encode order (shared by the sync
        interface and the EncodePipeline so the paths cannot drift)."""
        return np.stack(
            [self._as_u8(chunks[self.chunk_index(i)]) for i in range(self.k)]
        )

    def _scatter(self, chunks: Mapping[int, np.ndarray], parity: np.ndarray) -> None:
        for i in range(self.m):
            np.copyto(chunks[self.chunk_index(self.k + i)], parity[i])

    def encode_chunks(self, chunks: dict[int, np.ndarray]) -> None:
        self._scatter(chunks, np.asarray(self.encode_array(self._gather(chunks))))

    def _use_xor_decode(self, erasures: list[int]) -> bool:
        """Single-erasure XOR path: first k+1 chunks + all-ones parity row 0
        (generalizes ErasureCodeIsa.cc:196-216)."""
        return (
            len(erasures) == 1
            and erasures[0] < self.k + 1
            and self._xor_row_available()
        )

    def decode_chunks(
        self,
        want_to_read: set[int],
        chunks: Mapping[int, np.ndarray],
        decoded: dict[int, np.ndarray],
    ) -> None:
        k, m = self.k, self.m
        raw_of = self.chunk_index
        erasures = [i for i in range(k + m) if raw_of(i) not in chunks]
        if not erasures:
            return
        if len(erasures) > m:
            raise EcError(EIO, f"{len(erasures)} erasures > m={m}")
        if self._use_xor_decode(erasures):
            sources = [i for i in range(k + m) if raw_of(i) in chunks][:k]
            stack = np.stack([self._as_u8(decoded[raw_of(i)]) for i in sources])
            np.copyto(decoded[raw_of(erasures[0])], np.asarray(xor_reduce(stack)))
            return
        idx = self.decode_index(erasures)
        survivors = np.stack([self._as_u8(decoded[raw_of(i)]) for i in idx])
        rec = np.asarray(self.decode_array(erasures, survivors))
        for p, e in enumerate(erasures):
            np.copyto(decoded[raw_of(e)], rec[p])
