"""Pallas TPU kernel: fused SWAR bitsliced GF(2^8) coding.

The perf-critical path behind the 40 GB/s/chip north star (BASELINE.md).
The jnp reference (ceph_tpu.ops.xor_mm) materializes the 8x bit-plane
expansion and int32 parity accumulators in HBM, capping throughput at ~1/10
of HBM bandwidth.  This kernel keeps the whole pipeline in VMEM per tile
and — unlike earlier revisions that fed an (8m, 8k) bit-matrix matmul to
the MXU — does the GF(2) contraction as a compile-time XOR schedule on
SWAR-packed words, because on-chip measurement showed the MXU formulation
was bottlenecked on the VPU-side uint8 -> int32 bit-plane expansion
(the unpacking relayout + 16 vector ops/byte), not on the matmul:

    HBM -> VMEM:  (k, R, C) uint8 chunk tile          (the only data read)
    VMEM:         pltpu.bitcast -> (R/4, C) int32     free register
                  reinterpret: a uint8 tile already packs 4 sublanes per
                  32-bit register row, so "4 bytes per word" costs nothing
    VPU:          plane(j,b) = (word >> b) & 0x01010101   (2 ops / 4 bytes)
    VPU:          out bit-plane = XOR of scheduled planes; GF(2) linearity
                  keeps the 4 packed byte fields independent (no carries:
                  every field holds 0/1)
    VPU:          out word = OR of (plane_r << r)      (byte re-assembly)
    VMEM -> HBM:  (m, R, C) uint8 parity tile          (the only data write)

so HBM traffic is the information-theoretic minimum (k bytes in, m bytes
out per stripe byte) and the inner loop is pure full-width int32 vector
XORs — no MXU, no bf16 casts, no sub-byte relayouts.  The byte->word
grouping the bitcast induces (bytes strided by the lane count) is
immaterial: the transform is byte-elementwise, and the output is bitcast
back through the exact inverse mapping.

The schedule (which input planes XOR into each output bit-plane) is the
bit-expanded coding matrix (gf.bitslice.expand_matrix), baked into the
kernel at trace time.  One compiled kernel per (matrix, geometry) — the
device analog of ISA-L's `ec_init_tables` product
(/root/reference/src/erasure-code/isa/ErasureCodeIsa.cc:83-91); decode
matrices get the same treatment through the signature-keyed coder LRU in
codec/matrix_codec.py, mirroring the reference's decode-table cache
(isa/ErasureCodeIsaTableCache.h:48).

Measured on a v5e chip (serial-chain methodology, 256 MiB launches):
52.9 GB/s input-rate vs 56.2 GB/s for a pure HBM copy kernel — i.e. the
kernel runs at ~94% of the achievable memory-bandwidth ceiling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ceph_tpu.gf.bitslice import expand_matrix

# One bit per packed byte field: plane words hold bit b of 4 bytes at bit
# positions {0, 8, 16, 24}.
_FIELD_MASK = 0x01010101

# Per-chunk tile (rows x cols bytes) each program processes.  rows % 4 == 0
# so the sublane bitcast packs exactly; VMEM per program is k data tiles +
# up to 8k int32 plane tiles + m output tiles: ~2.5 MB at (128, 256), k=8.
_GEOMETRY_COLS = (256, 128, 64, 32)
_MAX_ROWS = 128


def pick_geometry(L: int) -> tuple[int, int] | None:
    """(rows, cols) byte tile for chunk length L, or None if unsupported.

    cols is the lane axis (prefer full 128/256-lane tiles), rows the sublane
    axis (must be a multiple of 4 for the uint8->int32 register bitcast).
    Any L that is a multiple of 128 has a geometry (worst case (4, 32)).
    """
    for cols in _GEOMETRY_COLS:
        if L % cols:
            continue
        rows_total = L // cols
        # scan only multiples of 4 (start rounded down, else e.g.
        # rows_total=66 never lands on one and skips this cols entirely)
        r = min(_MAX_ROWS, rows_total - rows_total % 4)
        while r >= 4:
            if rows_total % r == 0:
                return r, cols
            r -= 4
    return None


def schedule_from_matrix(gf_matrix: np.ndarray) -> tuple[tuple[tuple[int, int], ...], ...]:
    """(m, k) GF matrix -> per-output-bit-row tuple of (chunk, bit) terms.

    Row o = 8*i + r of the bit-expanded matrix lists which input planes
    (chunk j, bit b) XOR into bit r of output byte i.
    """
    plain = expand_matrix(np.asarray(gf_matrix, dtype=np.uint8))  # (8m, 8k)
    m8, k8 = plain.shape
    return tuple(
        tuple((c // 8, c % 8) for c in range(k8) if plain[o, c])
        for o in range(m8)
    )


def _swar_kernel(data_ref, out_ref, *, sched, m: int):
    """One (stripe, tile) program: data_ref (1, k, 1, R, C) uint8 ->
    out_ref (1, m, 1, R, C) uint8."""
    _, k, _, r_, c_ = data_ref.shape
    needed = {t for row in sched for t in row}
    planes: dict[tuple[int, int], jax.Array] = {}
    zeros = jnp.zeros((1, r_ // 4, c_), jnp.int32)
    for j in range(k):
        bits = [b for b in range(8) if (j, b) in needed]
        if not bits:
            continue
        d32 = pltpu.bitcast(data_ref[0, j], jnp.int32)  # (1, R/4, C)
        for b in bits:
            shifted = jax.lax.shift_right_logical(d32, b) if b else d32
            planes[(j, b)] = shifted & _FIELD_MASK
    for i in range(m):
        word = None
        for r in range(8):
            row = sched[i * 8 + r]
            if not row:
                continue
            acc = planes[row[0]]
            for t in row[1:]:
                acc = acc ^ planes[t]
            contrib = acc << r if r else acc
            word = contrib if word is None else word | contrib
        if word is None:  # all-zero matrix row
            word = zeros
        out_ref[0, i] = pltpu.bitcast(word, jnp.uint8)


def _swar_code(
    data: jax.Array,
    *,
    sched,
    m: int,
    rows: int,
    cols: int,
    interpret: bool = False,
) -> jax.Array:
    s, k, L = data.shape
    tile = rows * cols
    nt = L // tile
    d = data.reshape(s, k, nt, rows, cols)
    out = pl.pallas_call(
        functools.partial(_swar_kernel, sched=sched, m=m),
        grid=(s, nt),
        interpret=interpret,
        in_specs=[
            pl.BlockSpec(
                (1, k, 1, rows, cols),
                lambda i, j: (i, 0, j, 0, 0),
                memory_space=pltpu.VMEM,
            )
        ],
        out_specs=pl.BlockSpec(
            (1, m, 1, rows, cols),
            lambda i, j: (i, 0, j, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((s, m, nt, rows, cols), jnp.uint8),
    )(d)
    return out.reshape(s, m, L)


_STATIC = ("sched", "m", "rows", "cols", "interpret")


# Encode and decode are one kernel under two program names, so that a
# device trace tells them apart; both contain `gf_code`, which the
# benchmark's kernel reader matches.
@functools.partial(jax.jit, static_argnames=_STATIC)
def _gf_code_swar(data, *, sched, m, rows, cols, interpret=False):
    return _swar_code(data, sched=sched, m=m, rows=rows, cols=cols,
                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _gf_code_swar_decode(data, *, sched, m, rows, cols, interpret=False):
    return _swar_code(data, sched=sched, m=m, rows=rows, cols=cols,
                      interpret=interpret)


class CodingPlan:
    """Host-built plan: XOR schedule for the kernel + dispatch info.

    The device-side analog of ISA-L's `ec_init_tables` product
    (/root/reference/src/erasure-code/isa/ErasureCodeIsa.cc:83-91): built
    once per (matrix, geometry), then applied to any number of stripe
    batches.  Chunk lengths without a tile geometry (not a multiple of 128)
    fall back to the jnp bitsliced matmul.
    """

    def __init__(
        self, gf_matrix: np.ndarray, *, interpret: bool = False, decode: bool = False
    ):
        gf_matrix = np.asarray(gf_matrix, dtype=np.uint8)
        self.m, self.k = gf_matrix.shape
        self.interpret = interpret
        self.sched = schedule_from_matrix(gf_matrix)
        self.bm = jnp.asarray(expand_matrix(gf_matrix), dtype=jnp.uint8)
        self._gf = gf_matrix
        self._packed = None  # lazy packed-plane fallback for unaligned L
        self.decode = decode  # decode-kind plans also count DECODE_LAUNCHES

    def __call__(self, data: jax.Array) -> jax.Array:
        """(..., k, L) uint8 -> (..., m, L) uint8 coded output."""
        from .dispatch import record_launch

        *lead, k, L = data.shape
        assert k == self.k, (k, self.k)
        geom = pick_geometry(L)
        stripes = int(np.prod(lead)) if lead else 1
        if geom is None:
            from .packed_gf import PACKED_MIN_BYTES, PackedPlan
            from .xor_mm import xor_matmul

            if int(np.prod(data.shape)) >= PACKED_MIN_BYTES:
                if self._packed is None:
                    self._packed = PackedPlan(self._gf, decode=self.decode)
                return self._packed(data)
            record_launch(stripes, int(np.prod(data.shape)), decode=self.decode)
            return xor_matmul(self.bm, data)
        rows, cols = geom
        record_launch(stripes, int(np.prod(data.shape)), decode=self.decode)
        flat = data.reshape(stripes, k, L)
        code = _gf_code_swar_decode if self.decode else _gf_code_swar
        out = code(
            flat,
            sched=self.sched,
            m=self.m,
            rows=rows,
            cols=cols,
            interpret=self.interpret,
        )
        return out.reshape(*lead, self.m, L)


def gf_code(bit_matrix_or_plan, data: jax.Array) -> jax.Array:
    """Shape-flexible coding entry.

    Accepts a CodingPlan (preferred, TPU path; also runs anywhere with
    interpret=True) or a raw (8m, 8k) bit-matrix (jnp fallback — used
    off-TPU where Pallas TPU kernels can't run).
    """
    if isinstance(bit_matrix_or_plan, CodingPlan):
        plan = bit_matrix_or_plan
        if plan.interpret or jax.devices()[0].platform == "tpu":
            return plan(data)
        raise TypeError("CodingPlan requires a TPU backend; pass a bit-matrix")
    from .xor_mm import xor_matmul

    return xor_matmul(bit_matrix_or_plan, data)
