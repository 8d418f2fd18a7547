"""Sharded stripe-batch pipelines — pjit/shard_map over a (stripe, lane) mesh.

The bulk scrub/rebuild data path (SURVEY.md §7 step 6; BASELINE config
"RS(10,4) batched encode, 64K stripes in flight"): stripe batches are sharded
data-parallel over the mesh's `stripe` axis, chunk bytes over `lane` (GF
coding is bytewise independent, so both axes need no communication for
encode/decode).  Cross-device work appears only in verification/scrub
reductions (psum over both axes) — those are the collectives that ride ICI,
playing the role the reference's messenger fan-out plays for `ECSubWrite`
(/root/reference/src/osd/ECBackend.cc:2071-2120).

Multi-pod meshes (mesh.make_mesh(pods=N)) add a leading DCN axis: stripes
shard over ('pod', 'stripe') jointly, so chunk bytes stay inside their pod
and only the scalar scrub verdict reduces across DCN.

Two encode paths:
- `sharded_encode(bit_matrix, ...)` — the jnp XOR-matmul partitioned by
  XLA's sharding propagation; runs on any backend.
- `sharded_plan_encode(plan, ...)` — shard_map: every device runs the fused
  Pallas SWAR kernel (ops.pallas_gf.CodingPlan) on its local tile.  This is
  the production TPU path, the same kernel `encode_chunks` ships; XLA can't
  partition a pallas_call automatically, so the per-device view is explicit.
"""

from __future__ import annotations

import functools
from collections import OrderedDict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

import numpy as np

from ceph_tpu.common.mempool import track_buffer
from ceph_tpu.common.tracer import section
from ceph_tpu.ops.dispatch import record_launch
from ceph_tpu.ops.packed_gf import PackedPlan, _packed_code_impl
from ceph_tpu.ops.pallas_gf import CodingPlan
from ceph_tpu.ops.xor_mm import xor_matmul

from .mesh import LANE_AXIS, POD_AXIS, STRIPE_AXIS


def _stripe_axes(mesh: Mesh):
    """Mesh axes the stripe dim shards over: pods join the stripe axis so
    bulk bytes never cross the DCN boundary."""
    if POD_AXIS in mesh.axis_names:
        return (POD_AXIS, STRIPE_AXIS)
    return STRIPE_AXIS


def _stripe_spec(mesh: Mesh) -> P:
    # (S, k, L): shard stripes over `(pod,) stripe`, chunk bytes over `lane`.
    return P(_stripe_axes(mesh), None, LANE_AXIS)


def _stripe_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, _stripe_spec(mesh))


def _stripe_shards(mesh: Mesh) -> int:
    n = mesh.shape[STRIPE_AXIS]
    if POD_AXIS in mesh.axis_names:
        n *= mesh.shape[POD_AXIS]
    return n


def shard_batch(data: jax.Array, mesh: Mesh) -> jax.Array:
    """Place a (S, k, L) stripe batch with stripe+lane sharding.

    Batches that don't divide the mesh are zero-padded up to the next
    divisible (S, L) — exact for GF coding (zero stripes encode to zero
    parity, and scrub sees matching zeros), so callers slice results back to
    their logical shape with `result[:S, ..., :L]`.
    """
    S, _, L = data.shape
    pad_s = -S % _stripe_shards(mesh)
    pad_l = -L % mesh.shape[LANE_AXIS]
    if pad_s or pad_l:
        data = jnp.pad(data, ((0, pad_s), (0, 0), (0, pad_l)))
    # HBM ledger (ISSUE 13): the placement is resident until the launch
    # retires and the caller drops it — GC-tracked, not hand-released
    return track_buffer(
        jax.device_put(data, _stripe_sharding(mesh)), "sharded_placement"
    )


@functools.cache
def _encode_executable(mesh: Mesh):
    """One held jit wrapper per mesh.

    Building `jax.jit(...)` inside every call would discard its trace cache
    each time; holding the wrapper makes steady-state launches (the 64K
    stripes-in-flight bulk-rebuild config, BASELINE config 3) pure cache
    hits — the device analog of the reference's precomputed-table reuse
    (isa/ErasureCodeIsaTableCache.h:48).
    """
    return jax.jit(
        xor_matmul,
        in_shardings=(NamedSharding(mesh, P()), _stripe_sharding(mesh)),
        out_shardings=_stripe_sharding(mesh),
    )


def sharded_encode(bit_matrix: jax.Array, data: jax.Array, mesh: Mesh) -> jax.Array:
    """(S, k, L) uint8 -> (S, m, L) parity, fully sharded, no collectives.

    XLA partitions the XOR-matmul per shard; each device encodes its own
    stripe/lane tile — the embarrassingly-parallel layout that turns a pod
    into one wide encoder for bulk rebuild.
    """
    return _encode_executable(mesh)(bit_matrix, data)


def sharded_decode(
    decode_bit_matrix: jax.Array, survivors: jax.Array, mesh: Mesh
) -> jax.Array:
    """(S, k, L) survivors (decode_index order) -> (S, nerrs, L) rebuilt."""
    return sharded_encode(decode_bit_matrix, survivors, mesh)


# Content-keyed LRU of shard_map executables: keyed by the plan's schedule
# (not object identity, so equal matrices reuse one executable) and bounded
# like the codec's decode-coder LRU (matrix_codec.DECODE_LRU_CAPACITY) so
# long-running rebuild services cycling through erasure signatures don't pin
# compiled executables forever.
_PLAN_EXEC_CAPACITY = 256
_plan_execs: "OrderedDict[tuple, object]" = OrderedDict()


def _cached_exec(key: tuple, build):
    """One LRU for every shard_map executable: get-or-build with
    move-to-front and bounded eviction."""
    exe = _plan_execs.get(key)
    if exe is not None:
        _plan_execs.move_to_end(key)
        return exe
    exe = build()
    _plan_execs[key] = exe
    while len(_plan_execs) > _PLAN_EXEC_CAPACITY:
        _plan_execs.popitem(last=False)
    return exe


def _plan_encode_executable(mesh: Mesh, plan: CodingPlan):
    """shard_map wrapper: the fused Pallas kernel on each device's tile.

    The per-device chunk-length tile (L / lane shards) must keep a kernel
    geometry (128-aligned); CodingPlan itself falls back to the jnp matmul
    for tiles that don't, so this is total either way.
    """
    spec = _stripe_spec(mesh)

    def build():
        # check_vma=False: the body is a pallas_call, which can't declare
        # its varying-mesh-axes; operands/results are explicitly sharded.
        local = jax.shard_map(
            plan, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False
        )
        return jax.jit(local)

    return _cached_exec(
        (mesh, plan.sched, plan.m, plan.k, plan.interpret), build
    )


def sharded_plan_encode(plan: CodingPlan, data: jax.Array, mesh: Mesh) -> jax.Array:
    """(S, k, L) uint8 -> (S, m, L) parity via the production Pallas kernel.

    Identical sharding layout to `sharded_encode`, but each device executes
    the compiled SWAR XOR-schedule kernel on its local (S/ns, k, L/nl) tile
    — the multi-chip fan-out of the exact kernel the codec's
    `encode_chunks`/`encode_array` path ships (VERDICT r3 item: the sharded
    path must shard the fast kernel, not the reference matmul).
    """
    return _plan_encode_executable(mesh, plan)(data)


def sharded_plan_decode(
    plan: CodingPlan, survivors: jax.Array, mesh: Mesh
) -> jax.Array:
    """Survivors (decode_index order) -> rebuilt chunks via the Pallas plan
    built from a decode matrix (codec.matrix_codec decode_plan/LRU)."""
    return sharded_plan_encode(plan, survivors, mesh)


def _scrub_impl(bit_matrix, chunks, k):
    data = chunks[:, :k, :]
    stored_parity = chunks[:, k:, :]
    recomputed = xor_matmul(bit_matrix, data)
    # Per-stripe mismatch flag, reduced over the lane axis automatically by
    # XLA's partitioner (psum over lane shards under the hood).
    mismatch = jnp.any(recomputed != stored_parity, axis=(1, 2))
    return jnp.sum(mismatch.astype(jnp.int32)), mismatch


@functools.cache
def _scrub_executable(mesh: Mesh, k: int):
    return jax.jit(
        functools.partial(_scrub_impl, k=k),
        in_shardings=(NamedSharding(mesh, P()), _stripe_sharding(mesh)),
        out_shardings=(
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P(_stripe_axes(mesh))),
        ),
    )


def _plan_scrub_executable(mesh: Mesh, plan: CodingPlan, k: int):
    # k is a real key component (the closure slices with it); it must
    # also agree with the plan's geometry or the compiled executable
    # would be poisoned for later correct calls
    assert k == plan.k, (k, plan.k)
    spec = _stripe_spec(mesh)

    def local(chunks):
        data = chunks[:, :k, :]
        stored_parity = chunks[:, k:, :]
        recomputed = plan(data)  # the production Pallas kernel, per tile
        local_mismatch = jnp.any(recomputed != stored_parity, axis=(1, 2))
        # lane shards each hold a byte-range verdict: OR across the lane
        # axis; the total count sums across every stripe shard (the only
        # cross-pod traffic on a DCN mesh)
        mismatch = jax.lax.pmax(
            local_mismatch.astype(jnp.int32), LANE_AXIS
        ).astype(jnp.bool_)
        # after the lane pmax every lane shard holds identical verdicts,
        # so summing across stripe shards only (no lane sum) counts each
        # stripe exactly once
        count = jax.lax.psum(
            jnp.sum(mismatch.astype(jnp.int32)), _stripe_axes(mesh)
        )
        return count, mismatch

    def build():
        local_sm = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=spec,
            out_specs=(P(), P(_stripe_axes(mesh))),
            check_vma=False,
        )
        return jax.jit(local_sm)

    return _cached_exec(
        ("scrub", mesh, plan.sched, plan.m, plan.k, k, plan.interpret), build
    )


def plan_scrub_step(
    plan: CodingPlan, chunks: jax.Array, k: int, mesh: Mesh
) -> tuple[jax.Array, jax.Array]:
    """scrub_step with the recompute running the production Pallas kernel
    on each device's tile (shard_map) — the multi-chip scrub ships the
    same kernel as encode_chunks."""
    return _plan_scrub_executable(mesh, plan, k)(chunks)


def _packed_shard_executable(mesh: Mesh, packed: PackedPlan):
    """shard_map wrapper of the packed-plane kernel: each device runs the
    fused plane-tower/XOR-schedule program (ops/packed_gf.py) on its own
    (S/n, k, L) stripe tile — the multi-chip fan-out of the exact kernel
    the aggregated single-device launch ships."""
    spec = _stripe_spec(mesh)

    def build():
        local = jax.shard_map(
            lambda data: _packed_code_impl(data, packed.sched, packed.k, packed.m),
            mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False,
        )
        return jax.jit(local)

    return _cached_exec(("packed", mesh, packed.sched, packed.k, packed.m), build)


def sharded_coder_code(coder, data, mesh: Mesh) -> jax.Array:
    """One (S, k, L) uint8 coding launch, data-parallel over the mesh's
    stripe axis — the sharded dispatch mode of codec/matrix_codec.py's
    `_DeviceCoder` (ISSUE 6 tentpole).

    `coder` duck-types _DeviceCoder: `.plan` (Pallas CodingPlan or None),
    `.packed` (PackedPlan), `.decode` (kind flag).  The batch is padded
    to a stripe-shard multiple (zero stripes code to zero output — exact
    for GF maps), placed with a NamedSharding over `stripe` (ONE sharded
    H2D instead of a single-device put plus a reshard), run per-device
    via the cached shard_map executable, and sliced back to the logical
    stripe count.  Kernel choice per device mirrors the single-device
    dispatch: the fused Pallas kernel on TPU-aligned chunk lengths
    (lane_parallelism is 1, so the per-device tile keeps L), the packed
    plane kernel otherwise — bytes are identical either way."""
    S, _, L = data.shape
    n = _stripe_shards(mesh)
    pad = -S % n
    record_launch(
        S, int(np.prod(data.shape)), decode=coder.decode, devices=n
    )
    if pad:
        if isinstance(data, np.ndarray):
            data = np.concatenate(
                [data, np.zeros((pad, *data.shape[1:]), dtype=np.uint8)]
            )
        else:
            data = jnp.pad(data, ((0, pad), (0, 0), (0, 0)))
    # HBM ledger (ISSUE 13): the sharded H2D placement is device-resident
    # for the life of the launch — tracked so dump_mempools shows bulk
    # launches' staging alongside the cache and in-flight pools
    with section("device.h2d", data.nbytes):
        placed = track_buffer(
            jax.device_put(data, _stripe_sharding(mesh)), "sharded_placement"
        )
    if coder.plan is not None and L % 128 == 0:
        # trace-time caveat: the CodingPlan wrapper records its own
        # (single) launch while the shard_map body is first traced; the
        # per-dispatch accounting above is the authoritative count
        result = _plan_encode_executable(mesh, coder.plan)(placed)
    else:
        result = _packed_shard_executable(mesh, coder.packed)(placed)
    return result[:S] if pad else result


def scrub_step(
    bit_matrix: jax.Array, chunks: jax.Array, k: int, mesh: Mesh
) -> tuple[jax.Array, jax.Array]:
    """Deep-scrub analog: recompute parity for a (S, k+m, L) batch, compare.

    Returns (total mismatching stripe count, per-stripe mismatch mask) — the
    device-side equivalent of `ECBackend::be_deep_scrub` chunk verification
    (/root/reference/src/osd/ECBackend.cc:2518), with the mismatch count
    produced by cross-device reduction instead of primary-gathered maps.
    On a multi-pod mesh the only DCN traffic is this scalar verdict psum —
    tiles and parity stay inside their pods.
    """
    return _scrub_executable(mesh, k)(bit_matrix, chunks)
