"""Device compressor plugin — the second offload-runtime rider (ISSUE 20).

A registry plugin (`bluestore_compression_algorithm = device`) whose
transform is chosen for the device, not for entropy coding: a byte-plane
transpose (stride 64 — each plane gathers byte p of every 64-byte row,
so columnar/record-structured block images concentrate their zero bytes
into whole planes) followed by zero-run elision at 64-byte cell
granularity over the transposed stream.  Both steps are pure data
movement + an any-nonzero reduce, so the batched form runs as ONE device
launch per aggregation window through the shared offload runtime
(`CompressAggregator`, background lane), and the host fallback computes
the *identical* stored form in numpy — byte-identity through the whole
fault/DEGRADED matrix is structural, not probabilistic.

Stored blob format (self-framing, verified on decompress):

    b"TZD1" | <u32 LE orig_len> | cell bitmap (LSB-first) | nonzero cells

BlueStore's required-ratio gate is unchanged: a block image is stored
in this form only when the blob beats
``bluestore_compression_required_ratio`` — high-entropy blocks fail the
ratio and land raw, exactly like zlib/zstd.
"""

from __future__ import annotations

import struct

import numpy as np

from .registry import Compressor

MAGIC = b"TZD1"
TR = 64    # transpose stride: plane p = byte p of each TR-byte row
CELL = 64  # zero-elision granularity over the transposed stream

# Below this many total bytes a batch skips the offload runtime (host
# transform directly): dispatch + window latency beats the win.
COMPRESS_OFFLOAD_MIN_BYTES = 32 * 1024


def _padded_len(n: int) -> int:
    return -(-max(n, 1) // TR) * TR


def transform_rows(rows: np.ndarray) -> np.ndarray:
    """The host-oracle device transform: (S, Lp) uint8 (Lp % 64 == 0)
    -> (S, Lp + Lp//CELL) uint8 — transposed bytes followed by the 0/1
    nonzero-cell flags.  The device kernel computes the same array."""
    S, Lp = rows.shape
    t = rows.reshape(S, Lp // TR, TR).transpose(0, 2, 1).reshape(S, Lp)
    flags = t.reshape(S, Lp // CELL, CELL).any(axis=2).astype(np.uint8)
    return np.concatenate([t, flags], axis=1)


def transform_rows_device(rows: np.ndarray):
    """One batched device launch of the transform; returns a device
    array shaped like `transform_rows` (np.asarray forces it)."""
    import jax.numpy as jnp

    from ceph_tpu.ops.dispatch import record_launch

    S, Lp = rows.shape
    d = jnp.asarray(rows)
    t = d.reshape(S, Lp // TR, TR).transpose(0, 2, 1).reshape(S, Lp)
    flags = (
        t.reshape(S, Lp // CELL, CELL).max(axis=2) > 0
    ).astype(jnp.uint8)
    record_launch(S, rows.nbytes)
    return jnp.concatenate([t, flags], axis=1)


def assemble_blob(transformed: np.ndarray, orig_len: int) -> bytes:
    """(Lp + Lp//CELL,) transform output row -> the stored blob."""
    Lp = _padded_len(orig_len)
    ncells = Lp // CELL
    t = transformed[:Lp]
    mask = transformed[Lp : Lp + ncells].astype(bool)
    bitmap = np.packbits(mask, bitorder="little").tobytes()
    payload = np.ascontiguousarray(t).reshape(ncells, CELL)[mask].tobytes()
    return MAGIC + struct.pack("<I", orig_len) + bitmap + payload


class DeviceCompressor(Compressor):
    name = "device"

    def compress(self, data: bytes) -> bytes:
        data = bytes(data)
        Lp = _padded_len(len(data))
        row = np.zeros((1, Lp), dtype=np.uint8)
        row[0, : len(data)] = np.frombuffer(data, dtype=np.uint8)
        return assemble_blob(transform_rows(row)[0], len(data))

    def decompress(self, data: bytes) -> bytes:
        blob = bytes(data)
        if blob[:4] != MAGIC or len(blob) < 8:
            raise ValueError("not a device-compressor blob")
        (orig_len,) = struct.unpack_from("<I", blob, 4)
        Lp = _padded_len(orig_len)
        ncells = Lp // CELL
        nbitmap = (ncells + 7) // 8
        mask = np.unpackbits(
            np.frombuffer(blob[8 : 8 + nbitmap], dtype=np.uint8),
            bitorder="little",
        )[:ncells].astype(bool)
        payload = np.frombuffer(blob[8 + nbitmap :], dtype=np.uint8)
        if payload.size != int(mask.sum()) * CELL:
            raise ValueError("device-compressor blob truncated")
        cells = np.zeros((ncells, CELL), dtype=np.uint8)
        if payload.size:
            cells[mask] = payload.reshape(-1, CELL)
        # inverse transpose: flat transposed stream -> original order
        out = (
            cells.reshape(Lp)
            .reshape(TR, Lp // TR)
            .transpose()
            .reshape(Lp)
        )
        return out.tobytes()[:orig_len]

    def compress_batch(self, blocks: list[bytes]) -> list[bytes]:
        """Compress many block images with their transforms batched into
        shared offload-runtime launches (same-length groups coalesce
        across concurrent callers through the aggregation window); small
        batches and the fault/DEGRADED matrix take the byte-identical
        host transform."""
        if not blocks:
            return []
        total = sum(len(b) for b in blocks)
        if total < COMPRESS_OFFLOAD_MIN_BYTES:
            return [self.compress(b) for b in blocks]
        agg = default_compress_aggregator()
        by_len: dict[int, list[int]] = {}
        for i, b in enumerate(blocks):
            by_len.setdefault(len(b), []).append(i)
        out: list[bytes] = [b""] * len(blocks)
        tickets = []
        for n, idxs in by_len.items():
            Lp = _padded_len(n)
            rows = np.zeros((len(idxs), Lp), dtype=np.uint8)
            for r, i in enumerate(idxs):
                rows[r, :n] = np.frombuffer(blocks[i], dtype=np.uint8)
            tickets.append((n, idxs, agg.submit_rows(rows)))
        for n, idxs, ticket in tickets:
            transformed = ticket.result()
            for r, i in enumerate(idxs):
                out[i] = assemble_blob(transformed[r], n)
        return out


# registry entry: resolved by get_compressor("device") exactly like the
# zlib/zstd plugins (BlueStore's bluestore_compression_algorithm knob)
from .registry import CompressorRegistry

CompressorRegistry._PLUGINS.setdefault("device", DeviceCompressor)


from ceph_tpu.ops.offload_runtime import (  # noqa: E402
    AggTicket,
    LaunchAggregator,
    _AggGroup,
    register_service,
)


class CompressAggregator(LaunchAggregator):
    """Cross-block / cross-object compressor-transform aggregation:
    same-padded-length block images submitted inside one window ride ONE
    device transpose+elide launch (background lane).  Tickets resolve to
    (stripes, Lp + Lp//CELL) transform rows; `assemble_blob` turns each
    row into the stored form."""

    PERF_NAME = "compress_aggregator"
    WHAT = "compress"
    SCHED_CLASS = "background"
    MEM_POOL = "offload_inflight"

    def submit_rows(self, rows: np.ndarray) -> AggTicket:
        """Queue one (S, Lp) uint8 padded block batch (Lp % 64 == 0)."""
        shaped = np.ascontiguousarray(rows, dtype=np.uint8)
        if shaped.ndim != 2 or shaped.shape[1] % TR:
            raise ValueError(f"expected (S, 64k) rows, got {shaped.shape}")
        return self._submit(
            ("#compress", shaped.shape[1]), None, None, shaped[:, None, :]
        )

    def _dispatch(self, g: _AggGroup, data: np.ndarray):
        S = data.shape[0]
        return transform_rows_device(data.reshape(S, -1))

    def _dispatch_host(self, g: _AggGroup, data: np.ndarray) -> np.ndarray:
        return transform_rows(data.reshape(data.shape[0], -1))


_DEFAULT_COMPRESS_AGGREGATOR: CompressAggregator | None = None


def default_compress_aggregator() -> CompressAggregator:
    """Process-wide compressor aggregator shared by every BlueStore in
    the process (one per OSD harness), so concurrent writers' block
    transforms coalesce exactly like their encodes do."""
    global _DEFAULT_COMPRESS_AGGREGATOR
    if _DEFAULT_COMPRESS_AGGREGATOR is None:
        from ceph_tpu.common.options import OPTIONS

        _DEFAULT_COMPRESS_AGGREGATOR = CompressAggregator(
            window=int(OPTIONS["bluestore_csum_offload_window"].default),
            max_bytes=int(
                OPTIONS["bluestore_csum_offload_max_bytes"].default
            ),
        )
    return _DEFAULT_COMPRESS_AGGREGATOR


register_service(
    "compress", default_compress_aggregator, lane="background",
    oracle="compressor/device.transform_rows",
    doc="batched byte-plane transpose + zero-run elision compressor",
)
