"""Device crc32c — the first post-EC offload-runtime service (ISSUE 20).

crc32c is GF(2)-affine: with a fixed message length L,

    crc(data) = crc(0^L)  XOR  (+) over set input bits of  C[i, t]

where C[i, t] is the final-register contribution of bit t of byte i —
the same linearity the XOR-program generators exploit for RS coding
(arXiv:2108.02692), so per-csum-block checksums compute as one packed
bit-matrix matmul on the MXU: transpose a (S, L) block batch so byte
position rides the contraction axis and the block index rides the lane
axis, apply the (32, 8L) contribution matrix through the shared
`xor_matmul` kernel, fold the four LE output byte-rows into uint32, and
XOR the zero-message constant.  One launch checksums every block of
every object that shared the aggregation window.

The host oracle is `utils/crc32c.crc32c` itself — not a reimplementation
— so the DEGRADED/fallback path is byte-identical by construction and
the device path is pinned byte-identical to it by tests across block
sizes and ragged tails.

Contribution matrix: the byte-step of the reflected-table update
``c' = T[(c ^ b) & 0xFF] ^ (c >> 8)`` is linear in (c, b) (T itself is a
linear LFSR map with T[0] = 0), so injecting bit t at byte i contributes
T[1 << t] propagated through the remaining L-1-i zero-input steps
A(c) = T[c & 0xFF] ^ (c >> 8).  One backward sweep C[L-1] = T[1 << t],
C[i-1] = A(C[i]) builds all L rows vectorized over the 8 bit columns;
the init/final 0xFFFFFFFF xors cancel in the delta and land in the
crc(0^L) constant.  Matrices are cached per L and placed on device once,
mempool-tracked under ``device_cache``.
"""

from __future__ import annotations

import numpy as np

from ceph_tpu.common.lockdep import make_lock as _lockdep_make_lock
from ceph_tpu.common.mempool import track_buffer as _hbm_track
from ceph_tpu.utils.crc32c import _TABLE, crc32c, crc32c_blocks

from .dispatch import record_launch
from .offload_runtime import (
    AggTicket,
    LaunchAggregator,
    _AggGroup,
    register_service,
)

# Below this many total bytes a batch skips the runtime entirely: the
# host table loop beats dispatch + window latency on small metadata
# writes (the packed_gf.PACKED_MIN_BYTES reasoning, applied to csum).
CSUM_OFFLOAD_MIN_BYTES = 16 * 1024

_MATRIX_LOCK = _lockdep_make_lock("csum_matrix_cache")
_HOST_MATRICES: dict[int, np.ndarray] = {}  # L -> (32, 8L) uint8
_DEVICE_MATRICES: dict[int, object] = {}    # L -> device operand
_CONSTS: dict[int, int] = {}                # L -> crc32c(b"\x00" * L)
# distinct Ls are bounded in practice (BLOCK plus the compressed-length
# tail population); a pathological length churn must not pin HBM
_MATRIX_CACHE_CAP = 64


def _contribution_matrix(L: int) -> np.ndarray:
    """(32, 8L) GF(2) matrix in xor_matmul's LSB-first convention:
    row 8r+s = bit s of output LE byte r, column 8i+t = bit t of input
    byte i."""
    with _MATRIX_LOCK:
        bm = _HOST_MATRICES.get(L)
        if bm is not None:
            return bm
    rows = np.empty((L, 8), dtype=np.uint32)
    c = _TABLE[np.left_shift(1, np.arange(8))].astype(np.uint32)
    rows[L - 1] = c
    for i in range(L - 1, 0, -1):
        c = _TABLE[c & 0xFF] ^ (c >> np.uint32(8))
        rows[i - 1] = c
    bits = (rows[:, :, None] >> np.arange(32, dtype=np.uint32)) & 1
    bm = np.ascontiguousarray(bits.reshape(L * 8, 32).T.astype(np.uint8))
    with _MATRIX_LOCK:
        if len(_HOST_MATRICES) >= _MATRIX_CACHE_CAP:
            _HOST_MATRICES.clear()
        _HOST_MATRICES[L] = bm
    return bm


def _zero_const(L: int) -> int:
    with _MATRIX_LOCK:
        const = _CONSTS.get(L)
    if const is None:
        const = crc32c(b"\x00" * L)
        with _MATRIX_LOCK:
            if len(_CONSTS) >= _MATRIX_CACHE_CAP:
                _CONSTS.clear()
            _CONSTS[L] = const
    return const


def _device_matrix(L: int):
    """The contribution matrix as a resident device operand (one H2D
    per L per process), ledger-tracked like every other HBM holder."""
    with _MATRIX_LOCK:
        dev = _DEVICE_MATRICES.get(L)
        if dev is not None:
            return dev
    import jax.numpy as jnp

    dev = _hbm_track(
        jnp.asarray(_contribution_matrix(L)), "device_cache",
        site="csum_matrix",
    )
    with _MATRIX_LOCK:
        if len(_DEVICE_MATRICES) >= _MATRIX_CACHE_CAP:
            _DEVICE_MATRICES.clear()
        _DEVICE_MATRICES[L] = dev
    return dev


def crc32c_device(blocks: np.ndarray):
    """One batched device launch: (S, L) uint8 blocks -> (S,) uint32
    crc32c digests (device array; np.asarray forces it)."""
    import jax.numpy as jnp

    from .xor_mm import xor_matmul

    S, L = blocks.shape
    bm = _device_matrix(L)
    # byte position -> contraction rows, block index -> lanes: the
    # whole batch is ONE (32, 8L) x (8L, S) MXU matmul
    out = xor_matmul(bm, jnp.asarray(blocks).T)  # (4, S) LE crc bytes
    crcs = (
        out[0].astype(jnp.uint32)
        | (out[1].astype(jnp.uint32) << 8)
        | (out[2].astype(jnp.uint32) << 16)
        | (out[3].astype(jnp.uint32) << 24)
    ) ^ jnp.uint32(_zero_const(L))
    record_launch(S, blocks.nbytes)
    return crcs


def crc32c_host_rows(blocks: np.ndarray) -> np.ndarray:
    """Byte-identical host oracle: `utils/crc32c.crc32c` per row."""
    blocks = np.ascontiguousarray(blocks, dtype=np.uint8)
    return np.fromiter(
        (crc32c(row.tobytes()) for row in blocks),
        dtype=np.uint32,
        count=blocks.shape[0],
    )


class ChecksumAggregator(LaunchAggregator):
    """Cross-block / cross-object crc32c launch aggregation: every
    same-length csum block submitted inside one window rides ONE device
    matmul (background lane — checksums must never head-of-line-block
    client encodes).  Tickets resolve to (stripes,) uint32 digests."""

    PERF_NAME = "csum_aggregator"
    WHAT = "csum"
    SCHED_CLASS = "background"
    MEM_POOL = "offload_inflight"

    def submit_blocks(self, blocks: np.ndarray) -> AggTicket:
        """Queue one (S, L) uint8 block batch; returns its ticket."""
        shaped = np.ascontiguousarray(blocks, dtype=np.uint8)
        if shaped.ndim != 2:
            raise ValueError(f"expected (S, L) blocks, got {shaped.shape}")
        return self._submit(
            ("#csum", shaped.shape[1]), None, None, shaped[:, None, :]
        )

    def _dispatch(self, g: _AggGroup, data: np.ndarray):
        S = data.shape[0]
        return crc32c_device(data.reshape(S, -1))

    def _dispatch_host(self, g: _AggGroup, data: np.ndarray) -> np.ndarray:
        return crc32c_host_rows(data.reshape(data.shape[0], -1))


_DEFAULT_CSUM_AGGREGATOR: ChecksumAggregator | None = None


def default_csum_aggregator() -> ChecksumAggregator:
    """Process-wide checksum aggregator shared by every BlueStore (and
    the EC-transaction fusion hook) in the process, so concurrent
    writers' csum blocks coalesce exactly like their encodes do."""
    global _DEFAULT_CSUM_AGGREGATOR
    if _DEFAULT_CSUM_AGGREGATOR is None:
        from ceph_tpu.common.options import OPTIONS

        _DEFAULT_CSUM_AGGREGATOR = ChecksumAggregator(
            window=int(OPTIONS["bluestore_csum_offload_window"].default),
            max_bytes=int(
                OPTIONS["bluestore_csum_offload_max_bytes"].default
            ),
        )
    return _DEFAULT_CSUM_AGGREGATOR


register_service(
    "csum", default_csum_aggregator, lane="background",
    oracle="utils/crc32c.crc32c",
    doc="BlueStore per-block crc32c as packed bit-matrix matmuls",
)


def checksum_blocks(
    chunks: list[bytes], offload: bool = True
) -> list[int]:
    """crc32c for each chunk, batched through the offload runtime when
    armed and profitable (chunks grouped by length — each length group
    is one submission riding the shared window), else the host loop.
    Returns digests in input order; the fallback matrix (device error,
    DEGRADED bypass, fault injection) yields identical values because
    the aggregator's host oracle IS `utils/crc32c`."""
    if not chunks:
        return []
    if not offload or sum(len(c) for c in chunks) < CSUM_OFFLOAD_MIN_BYTES:
        return crc32c_blocks(chunks)
    agg = default_csum_aggregator()
    by_len: dict[int, list[int]] = {}
    for i, c in enumerate(chunks):
        by_len.setdefault(len(c), []).append(i)
    out: list[int] = [0] * len(chunks)
    tickets = []
    for L, idxs in by_len.items():
        if L == 0:
            for i in idxs:
                out[i] = 0
            continue
        batch = np.frombuffer(
            b"".join(chunks[i] for i in idxs), dtype=np.uint8
        ).reshape(len(idxs), L)
        tickets.append((idxs, agg.submit_blocks(batch)))
    for idxs, ticket in tickets:
        crcs = ticket.result()
        for row, i in enumerate(idxs):
            out[i] = int(crcs[row])
    return out
