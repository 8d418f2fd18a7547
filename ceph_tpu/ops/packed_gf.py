"""Packed-bitplane GF(2^8) coding — the device hot path without the 8x blow-up.

The original jnp path (ceph_tpu.ops.xor_mm.xor_matmul) inflates every data
byte into 8 int8 bit-planes before an (8m, 8k) int32 matmul: an 8x operand
expansion plus a 4x-wide accumulator, exactly the operand blow-up where
bitmatrix codecs lose their bandwidth ("Accelerating XOR-based Erasure
Coding using Program Optimization Techniques", arXiv:2108.02692).  This
module keeps the planes PACKED 8-per-byte and reorganizes the contraction
around packed words ("Fast Xor-based Erasure Coding based on Polynomial
Ring Transforms", arXiv:1701.07731):

    byte j of a chunk already holds its own 8 bit-planes, packed.  The
    GF(2)-linear action of a coefficient c decomposes over the bits of c:

        c * x = XOR over set bits b of c of (x * 2^b)

    and multiplication by 2 (`xtime`) is itself a packed GF(2) map:

        x * 2 = (x << 1) ^ (0x1d if x & 0x80)      (poly 0x11d, ISA-L's)

    so the whole encode is: build the k x 8 tower of packed power planes
    (7 xtime steps per chunk, pure byte-wise shifts/XORs), then XOR the
    planes selected by each output coefficient's bits.  Operand stays
    (k, L) uint8 — 8x smaller than the bit-plane expansion — accumulators
    stay uint8, and the schedule's XOR count is sum(popcount(c_ij)), a
    fraction of the 8m x 8k bit-row schedule.

The gather-reshape -> plane tower -> XOR schedule -> output stack chain is
ONE jitted computation per (matrix, geometry).

Byte-identical to `xor_matmul` and to the host oracle
(gf.bitslice.xor_matmul_host) for every matrix — the schedule is an exact
refactoring of the same GF(2) linear map, verified across geometries by
tests/test_packed_gf.py.
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.common.lockdep import make_lock
from ceph_tpu.gf.tables import GF_MUL_TABLE

from .dispatch import record_launch

# xtime reduction byte: 2 * 0x80 in GF(2^8) == generator poly & 0xFF.
# Derived from the table so the kernel can never drift from the host GF.
_XTIME_RED = int(GF_MUL_TABLE[2, 0x80])

# Below this many input bytes the one-kernel-per-(shape) bitsliced matmul
# (matrix as a runtime operand) wins: the packed kernel bakes its XOR
# schedule in at trace time, so every distinct matrix costs a compile —
# fine for the handful of encode matrices and hot decode patterns, wasteful
# for tiny one-off decodes (SHEC's searched inverses on 4 KiB chunks).
PACKED_MIN_BYTES = 64 * 1024


def plane_schedule(gf_matrix: np.ndarray) -> tuple[tuple[tuple[int, int], ...], ...]:
    """(m, k) GF matrix -> per-output-row tuple of (chunk j, power b) terms.

    Output byte i is the XOR of packed planes data[j] * 2^b for every set
    bit b of coefficient gf_matrix[i, j]."""
    gfm = np.asarray(gf_matrix, dtype=np.uint8)
    m, k = gfm.shape
    return tuple(
        tuple(
            (j, b)
            for j in range(k)
            for b in range(8)
            if (int(gfm[i, j]) >> b) & 1
        )
        for i in range(m)
    )


# --- schedule reduction (ISSUE 11) -----------------------------------------
#
# A *plane program* is a straight-line schedule over uint8 plane registers:
# registers 0..k-1 are the input chunk planes data[..., j, :]; each op
# appends one new register, either ("x", a, b) = regs[a] ^ regs[b] or
# ("t", a) = xtime(regs[a]); `outputs` names one register per output row
# (-1 = all-zero row).  The whole tuple is hashable, so it rides the jit's
# static args exactly like the old (j, b) row schedule did, and the SAME
# program executes on device (jnp) and host (numpy) — the fallback oracle
# is derived from the schedule, not re-derived from the matrix.
#
# Three generators, cheapest picked per matrix at plan-build time:
#
# - `naive_program`: the original tower construction — xtime power towers
#   per chunk, then one XOR chain per output row over the selected tower
#   planes.  Cost = tower xtimes + sum(popcount(c_ij)) - rows.
# - `cse_program`: the naive leaves run through greedy pairwise
#   common-subexpression elimination across output rows ("Accelerating
#   XOR-based Erasure Coding using Program Optimization Techniques",
#   arXiv:2108.02692 §4): every tower-plane pair shared by f >= 2 rows is
#   factored into one intermediate, saving f-1 XORs.  By construction
#   cse_cost <= naive_cost for every matrix.
# - `ring_program`: the polynomial-ring evaluation ("Fast XOR-based
#   Erasure Coding based on Polynomial Ring Transforms", arXiv:1701.07731):
#   a coefficient is a polynomial in the ring F2[x]/(p(x)) acting through
#   multiplication-by-x, and xtime is GF(2)-linear — xtime(a ^ b) =
#   xtime(a) ^ xtime(b) — so each output row evaluates Horner-style over
#   its bit levels: row = x*(...x*(x*L_B ^ L_{B-1})...) ^ L_0 with L_b the
#   XOR of the chunks whose coefficient has bit b set.  No towers at all:
#   at most 7 xtimes per OUTPUT row instead of up to 7 per INPUT chunk,
#   which wins exactly when m < k (RS(8,3): 3 rows vs 8 chunk towers).
#
# Cost currency: one op = one vector instruction's worth of work (an XOR,
# or an xtime = shift + carry-fold XOR).  The tier-1 regression bound
# (tests/test_schedule_reduce.py) pins best <= naive per matrix family and
# strictly below for RS(8,3).

_PROG_TAG = "prog"


def naive_program(gf_matrix: np.ndarray) -> tuple:
    """The tower schedule as a plane program (the pre-reduction shape)."""
    gfm = np.asarray(gf_matrix, dtype=np.uint8)
    m, k = gfm.shape
    ops, leaf = _tower_ops(plane_schedule(gfm), k)
    outputs = []
    for row in plane_schedule(gfm):
        outputs.append(_xor_chain(ops, k, [leaf[t] for t in row]))
    return (_PROG_TAG, k, m, tuple(ops), tuple(outputs))


def cse_program(gf_matrix: np.ndarray) -> tuple:
    """Greedy pairwise CSE over the tower leaves (arXiv:2108.02692):
    repeatedly factor the plane pair shared by the most output rows into
    one intermediate register.  Deterministic (ties break on the lowest
    register pair) so the jit cache sees one program per matrix."""
    gfm = np.asarray(gf_matrix, dtype=np.uint8)
    m, k = gfm.shape
    rows_terms = plane_schedule(gfm)
    ops, leaf = _tower_ops(rows_terms, k)
    rows = [set(leaf[t] for t in row) for row in rows_terms]
    while True:
        counts: dict[tuple[int, int], int] = {}
        for row in rows:
            srow = sorted(row)
            for i, a in enumerate(srow):
                for b in srow[i + 1 :]:
                    counts[(a, b)] = counts.get((a, b), 0) + 1
        best = None
        for pair, f in counts.items():
            if f < 2:
                continue
            rank = (f, -pair[0], -pair[1])
            if best is None or rank > best[0]:
                best = (rank, pair)
        if best is None:
            break
        a, b = best[1]
        ops.append(("x", a, b))
        node = k + len(ops) - 1
        for row in rows:
            if a in row and b in row:
                row.discard(a)
                row.discard(b)
                row.add(node)
    outputs = [_xor_chain(ops, k, sorted(row)) for row in rows]
    return (_PROG_TAG, k, m, tuple(ops), tuple(outputs))


def ring_program(gf_matrix: np.ndarray) -> tuple:
    """Horner evaluation over the polynomial ring (arXiv:1701.07731):
    per output row, XOR the bit-level sums and chain multiply-by-x —
    tower-free, at most 7 xtimes per output row."""
    gfm = np.asarray(gf_matrix, dtype=np.uint8)
    m, k = gfm.shape
    ops: list[tuple] = []
    outputs = []
    for i in range(m):
        levels = [
            [j for j in range(k) if (int(gfm[i, j]) >> b) & 1]
            for b in range(8)
        ]
        nonzero = [b for b in range(8) if levels[b]]
        if not nonzero:
            outputs.append(-1)
            continue
        top = nonzero[-1]
        acc = _xor_chain(ops, k, levels[top])
        for b in range(top - 1, -1, -1):
            ops.append(("t", acc))
            acc = k + len(ops) - 1
            if levels[b]:
                lvl = _xor_chain(ops, k, levels[b])
                ops.append(("x", acc, lvl))
                acc = k + len(ops) - 1
        outputs.append(acc)
    return (_PROG_TAG, k, m, tuple(ops), tuple(outputs))


def _tower_ops(rows, k: int):
    """xtime power towers for every (chunk, power) leaf the rows use.
    Returns (ops list, {(j, b): register})."""
    ops: list[tuple] = []
    leaf: dict[tuple[int, int], int] = {}
    max_pow = [0] * k
    for row in rows:
        for j, b in row:
            max_pow[j] = max(max_pow[j], b)
    for j in range(k):
        leaf[(j, 0)] = j
        prev = j
        for b in range(1, max_pow[j] + 1):
            ops.append(("t", prev))
            prev = k + len(ops) - 1
            leaf[(j, b)] = prev
    return ops, leaf


def _xor_chain(ops: list, k: int, regs: list[int]) -> int:
    """Left-to-right XOR chain over registers; returns the result reg
    (-1 for an empty row — an all-zero output)."""
    if not regs:
        return -1
    acc = regs[0]
    for r in regs[1:]:
        ops.append(("x", acc, r))
        acc = k + len(ops) - 1
    return acc


def is_program(sched) -> bool:
    return bool(sched) and sched[0] == _PROG_TAG


def program_cost(prog) -> int:
    """Vector-op count of a plane program (XORs + xtimes)."""
    assert is_program(prog), prog
    return len(prog[3])


# best_program memo: decode matrices churn (one per erasure pattern), and
# the host-fallback oracle re-derives the program per launch without it.
_PROGRAM_MEMO_CAPACITY = 512
_PROGRAM_MEMO: "dict[tuple, tuple]" = {}
_PROGRAM_LOCK = make_lock("packed_program_cache")


def best_program(gf_matrix: np.ndarray) -> tuple:
    """The cheapest schedule for this matrix: min-cost of the naive
    tower, CSE-reduced, and ring-transform constructions (memoized).
    Every candidate is an exact refactoring of the same GF(2) linear map,
    so the choice is pure cost — bytes are identical by construction."""
    gfm = np.asarray(gf_matrix, dtype=np.uint8)
    key = (gfm.shape, gfm.tobytes())
    with _PROGRAM_LOCK:
        cached = _PROGRAM_MEMO.get(key)
    if cached is not None:
        return cached
    candidates = [cse_program(gfm), ring_program(gfm), naive_program(gfm)]
    prog = min(candidates, key=program_cost)
    with _PROGRAM_LOCK:
        if len(_PROGRAM_MEMO) >= _PROGRAM_MEMO_CAPACITY:
            _PROGRAM_MEMO.clear()  # tiny entries; wholesale reset is fine
        _PROGRAM_MEMO.setdefault(key, prog)
        return _PROGRAM_MEMO[key]


def _xtime_host(x: np.ndarray) -> np.ndarray:
    """Host xtime, bit-identical to the device `_xtime` (uint8 shift
    wraps mod 256 in numpy exactly like jnp)."""
    return ((x << 1) ^ ((x >> 7) * np.uint8(_XTIME_RED))).astype(np.uint8)


def run_program_host(prog: tuple, data: np.ndarray) -> np.ndarray:
    """Execute a plane program in pure numpy: (..., k, L) -> (..., m, L).
    This IS the host oracle of the packed kernel — same schedule, same
    xtime, so the DEGRADED-mode fallback can never drift from the device
    bytes.  Never touches the jax runtime."""
    tag, k, m, ops, outputs = prog
    assert tag == _PROG_TAG
    data = np.asarray(data, dtype=np.uint8)
    *lead, kk, L = data.shape
    assert kk == k, (kk, k)
    regs: list[np.ndarray] = [data[..., j, :] for j in range(k)]
    for op in ops:
        if op[0] == "x":
            regs.append(regs[op[1]] ^ regs[op[2]])
        else:
            regs.append(_xtime_host(regs[op[1]]))
    outs = [
        np.zeros((*lead, L), np.uint8) if o < 0 else regs[o]
        for o in outputs
    ]
    return np.stack(outs, axis=-2)


def packed_code_host(gf_matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
    """Host-oracle encode through the SAME reduced schedule the device
    kernel compiles (best_program): (..., k, L) uint8 -> (..., m, L).
    Memory-light next to the bit-matrix oracle — (k + ops) uint8 planes
    instead of the 8x int32 bit-plane expansion."""
    return run_program_host(best_program(gf_matrix), data)


def _xtime(x: jax.Array) -> jax.Array:
    """Packed multiply-by-2 in GF(2^8): byte-wise, carry folded via the
    reduction poly.  uint8 shift-left wraps mod 256, which is exactly the
    discard of the top bit the reduction replaces."""
    return (x << 1) ^ ((x >> 7) * jnp.uint8(_XTIME_RED))


def _packed_code_impl(data: jax.Array, sched, k: int, m: int) -> jax.Array:
    *lead, kk, L = data.shape
    assert kk == k, (kk, k)
    if is_program(sched):
        # reduced straight-line schedule (ISSUE 11): execute the plane
        # program — the same op list run_program_host executes in numpy
        _tag, pk, pm, ops, outputs = sched
        assert (pk, pm) == (k, m), (sched[1:3], k, m)
        regs: list[jax.Array] = [data[..., j, :] for j in range(k)]
        for op in ops:
            if op[0] == "x":
                regs.append(regs[op[1]] ^ regs[op[2]])
            else:
                regs.append(_xtime(regs[op[1]]))
        outs = [
            jnp.zeros((*lead, L), jnp.uint8) if o < 0 else regs[o]
            for o in outputs
        ]
        return jnp.stack(outs, axis=-2)
    # legacy (chunk, power)-row schedule: power towers + per-row chains
    max_pow = [0] * k
    for row in sched:
        for j, b in row:
            max_pow[j] = max(max_pow[j], b)
    towers: list[list[jax.Array]] = []
    for j in range(k):
        t = [data[..., j, :]]
        for _ in range(max_pow[j]):
            t.append(_xtime(t[-1]))
        towers.append(t)
    outs = []
    for i in range(m):
        row = sched[i]
        if not row:
            outs.append(jnp.zeros((*lead, L), jnp.uint8))
            continue
        acc = towers[row[0][0]][row[0][1]]
        for j, b in row[1:]:
            acc = acc ^ towers[j][b]
        outs.append(acc)
    return jnp.stack(outs, axis=-2)


@functools.partial(jax.jit, static_argnames=("sched", "k", "m"))
def _packed_code(data: jax.Array, *, sched, k: int, m: int) -> jax.Array:
    return _packed_code_impl(data, sched, k, m)


# The decode twins: the same program under its own name, so that a
# device trace tells decode launches from encode launches.  Every name
# contains `packed`, which the benchmark's kernel reader matches.
@functools.partial(jax.jit, static_argnames=("sched", "k", "m"))
def _packed_decode(data: jax.Array, *, sched, k: int, m: int) -> jax.Array:
    return _packed_code_impl(data, sched, k, m)


def _packed_verify_impl(codeword: jax.Array, sched, k: int, m: int) -> jax.Array:
    """(..., k+m, L) uint8 codeword -> (...,) uint8 per-stripe mismatch
    bitmap: bit j set iff recomputed parity row j differs from the
    stored row j anywhere in the chunk.  The recompute is the SAME
    packed-plane schedule the encode kernel runs — an exact refactoring
    of the GF(2) linear map — so a zero bitmap is a proof the stored
    parity matches the encode kernel (and the host oracle) bit for bit."""
    data = codeword[..., :k, :]
    stored = codeword[..., k:, :]
    recomputed = _packed_code_impl(data, sched, k, m)
    # per-(stripe, parity-row) mismatch -> packed per-stripe bitmap.
    # m <= 8 for every registered geometry (the uint8 bitmap bound is
    # asserted host-side in PackedVerifyPlan.__init__).
    row_bad = jnp.any(recomputed ^ stored, axis=-1)  # (..., m) bool
    weights = (jnp.uint8(1) << jnp.arange(m, dtype=jnp.uint8))
    return jnp.sum(row_bad.astype(jnp.uint8) * weights, axis=-1).astype(
        jnp.uint8
    )


@functools.partial(jax.jit, static_argnames=("sched", "k", "m"))
def _packed_verify(codeword: jax.Array, *, sched, k: int, m: int) -> jax.Array:
    return _packed_verify_impl(codeword, sched, k, m)


@functools.partial(jax.jit, static_argnames=("sched", "k", "m"))
def _packed_delta(
    old_data: jax.Array,
    new_data: jax.Array,
    old_parity: jax.Array,
    *,
    sched,
    k: int,
    m: int,
) -> jax.Array:
    """RMW parity delta (ISSUE 18), fully on device: the GF(2^8) code is
    linear over GF(2), so

        parity_new = parity_old ^ Encode(data_old ^ data_new)

    with Encode the SAME plane program a full encode would run — the
    delta path can never drift from the materialize path byte-wise.
    (..., k, L) old/new data + (..., m, L) old parity -> (..., m, L) new
    parity, one fused launch, no host round-trip."""
    return old_parity ^ _packed_code_impl(old_data ^ new_data, sched, k, m)


@functools.partial(
    jax.jit, static_argnames=("sched", "k", "m", "chunk")
)
def _packed_delta_flat(
    old_data: tuple,
    new_data: tuple,
    old_parity: tuple,
    *,
    sched,
    k: int,
    m: int,
    chunk: int,
) -> jax.Array:
    """`_packed_delta` over the cache's native layout: k + k + m FLAT
    per-shard device buffers (each a shard's contiguous (stripes*chunk,)
    bytes, exactly what DeviceChunkCache holds) fused into one launch —
    the reshape/stack/xor/encode/xor chain compiles as a single program,
    so a cache-hit RMW pays ONE dispatch and zero host transfers."""
    od = jnp.stack([b.reshape(-1, chunk) for b in old_data], axis=1)
    nd = jnp.stack([b.reshape(-1, chunk) for b in new_data], axis=1)
    op_ = jnp.stack([b.reshape(-1, chunk) for b in old_parity], axis=1)
    return op_ ^ _packed_code_impl(od ^ nd, sched, k, m)


def packed_delta_host(
    gf_matrix: np.ndarray,
    old_data: np.ndarray,
    new_data: np.ndarray,
    old_parity: np.ndarray,
) -> np.ndarray:
    """Host oracle of `_packed_delta`: same chosen program via
    run_program_host, same xor composition — the byte-identity anchor
    the delta-path tests pin the device bytes against."""
    delta = run_program_host(
        best_program(gf_matrix),
        np.asarray(old_data, np.uint8) ^ np.asarray(new_data, np.uint8),
    )
    return np.asarray(old_parity, np.uint8) ^ delta


class PackedVerifyPlan:
    """Compare-only packed-plane plan (ISSUE 9): one fused jit per
    parity matrix that recomputes parity for a (batch, k+m, L) codeword
    window and returns the per-stripe mismatch bitmap instead of chunks
    — the deep-scrub aggregation kernel.  Dispatches count on
    VERIFY_LAUNCHES (and LAUNCHES) so "a whole scrub chunk verified in
    one launch" is a testable dispatch-shape invariant."""

    __slots__ = ("k", "m", "sched")

    def __init__(self, gf_matrix: np.ndarray):
        gfm = np.asarray(gf_matrix, dtype=np.uint8)
        self.m, self.k = gfm.shape
        assert self.m <= 8, f"mismatch bitmap is uint8; m={self.m} > 8"
        # the recompute is the SAME reduced schedule the encode kernel
        # compiles, so verify stays an exact replay of the encode bytes
        self.sched = best_program(gfm)

    def __call__(self, codeword: jax.Array) -> jax.Array:
        """(..., k+m, L) uint8 -> (...,) uint8 mismatch bitmap."""
        lead = codeword.shape[:-2]
        record_launch(
            int(np.prod(lead)) if lead else 1,
            int(np.prod(codeword.shape)),
            verify=True,
        )
        return _packed_verify(codeword, sched=self.sched, k=self.k, m=self.m)


def packed_verify_host(
    gf_matrix: np.ndarray, codeword: np.ndarray
) -> np.ndarray:
    """Byte-identical HOST oracle of PackedVerifyPlan (pure numpy, never
    touches the jax runtime): the DEGRADED-mode fallback of the verify
    aggregator, and the reference the kernel tests pin the bitmap
    against.  Recomputes parity through the same reduced plane program
    the host encode oracle runs, so both paths agree on every byte."""
    gfm = np.asarray(gf_matrix, dtype=np.uint8)
    m, k = gfm.shape
    assert m <= 8, f"mismatch bitmap is uint8; m={m} > 8"
    cw = np.asarray(codeword, dtype=np.uint8)
    data, stored = cw[..., :k, :], cw[..., k:, :]
    # recompute through the SAME reduced schedule the device kernel
    # compiles (ISSUE 11): the host oracle is derived from the program,
    # not re-derived from the matrix, so the paths cannot drift
    recomputed = packed_code_host(gfm, data)
    row_bad = np.any(recomputed ^ stored, axis=-1)  # (..., m) bool
    weights = (np.uint8(1) << np.arange(m, dtype=np.uint8))
    return np.sum(
        row_bad.astype(np.uint8) * weights, axis=-1, dtype=np.uint8
    )


class PackedPlan:
    """Host-built packed-plane plan: one fused jit per (matrix, geometry).

    The packed analog of pallas_gf.CodingPlan — works on every backend
    (pure jnp), no chunk-length alignment constraint, and the dispatch
    unit the launch counter observes."""

    __slots__ = ("k", "m", "sched", "decode")

    def __init__(self, gf_matrix: np.ndarray, decode: bool = False):
        gfm = np.asarray(gf_matrix, dtype=np.uint8)
        self.m, self.k = gfm.shape
        # the cheapest of the naive/CSE/ring schedules for THIS matrix
        # (ISSUE 11 schedule reduction); cached in PLAN_CACHE with the
        # plan, and byte-identical to every other construction
        self.sched = best_program(gfm)
        # decode-kind plans additionally count on DECODE_LAUNCHES so
        # recovery batching invariants are assertable on their own
        self.decode = decode

    def _stripes(self, shape) -> int:
        lead = shape[:-2]
        return int(np.prod(lead)) if lead else 1

    def __call__(self, data: jax.Array) -> jax.Array:
        """(..., k, L) uint8 -> (..., m, L) uint8 parity/coded output."""
        record_launch(
            self._stripes(data.shape), int(np.prod(data.shape)), decode=self.decode
        )
        kernel = _packed_decode if self.decode else _packed_code
        return kernel(data, sched=self.sched, k=self.k, m=self.m)
