"""Sharded-dispatch policy: when a coding launch spans the device mesh.

The PR 2/3 aggregators produce exactly the right input for multi-chip
data parallelism — large padded `(batch, k, L)` encode/decode launches —
and this module is the policy layer that decides, per launch, whether
that batch is placed on ONE device (the single-chip path) or sharded
over the `stripe` axis of a device mesh and run per-device via shard_map
(parallel/sharded.py executables).  The decision is the storage analog
of a training stack's data-parallel switch: XOR-based coding is
stripe-wise independent (arXiv:2108.02692), so splitting the batch axis
is communication-free and turns the pod into one wide encoder for bulk
rebuild/backfill.

Two runtime knobs ride `common/options.py` and the OSD's config
observers, mirroring the aggregation knobs:

- `ec_tpu_shard_min_batch`: batches with at least this many stripes
  shard; smaller launches stay single-device (a sharded dispatch pays a
  resharding device_put and a per-mesh compile — pure overhead for the
  few-stripe writes the aggregator window already coalesces).
- `ec_tpu_shard_devices`: mesh width; 0 = every visible device, 1
  disables sharding entirely.

Mesh construction is lazy and cached per width: querying jax.devices()
initializes the backend, so nothing here touches jax until the first
launch actually crosses the threshold.
"""

from __future__ import annotations

import threading

from ceph_tpu.common.lockdep import make_lock

# Defaults mirror common/options.py (the option table is the source of
# truth for daemons; library users get the same numbers without a Config).
DEFAULT_MIN_BATCH = 32
DEFAULT_DEVICES = 0  # 0 = all visible

_lock = make_lock("shard_dispatch_policy")
_min_batch = DEFAULT_MIN_BATCH
_devices = DEFAULT_DEVICES
_mesh_cache: dict[int, object] = {}  # resolved width -> Mesh
_visible: int | None = None  # len(jax.devices()), queried once


def configure(min_batch: int | None = None, devices: int | None = None) -> None:
    """Apply live config (the OSD wires its Config + runtime observers
    here, so the ec_tpu_shard_* settings reach the process-wide policy)."""
    global _min_batch, _devices
    with _lock:
        if min_batch is not None:
            _min_batch = int(min_batch)
        if devices is not None:
            _devices = int(devices)


def settings() -> tuple[int, int]:
    """(min_batch, devices) as currently configured."""
    with _lock:
        return _min_batch, _devices


def _visible_devices() -> int:
    """Device count of the default backend, cached (like
    matrix_codec._on_tpu: the answer cannot change within one process).
    A failed query raises instead of coding on one device."""
    global _visible
    if _visible is None:
        import jax

        _visible = len(jax.devices())
    return _visible


def _mesh_for_width(width: int):
    """Stripe-only mesh over the first `width` devices, cached per width.

    lane_parallelism is pinned to 1: the dispatch path shards the BATCH
    axis only (PartitionSpec over `stripe`), keeping per-device chunk
    length — and therefore kernel geometry — identical to the
    single-device launch, so bytes cannot drift with mesh shape."""
    mesh = _mesh_cache.get(width)
    if mesh is None:
        from .mesh import make_mesh

        mesh = make_mesh(width, lane_parallelism=1)
        _mesh_cache[width] = mesh
    return mesh


def shard_mesh(stripes: int):
    """The mesh a `stripes`-wide launch should shard over, or None for
    the single-device path (the byte floor is the caller's
    PACKED_MIN_BYTES gate; this policy is stripe-count-only).

    None when: sharding is disabled (`ec_tpu_shard_devices` = 1), the
    batch is under `ec_tpu_shard_min_batch`, the batch has fewer stripes
    than the mesh has shards (a device with zero real stripes is pure
    padding waste), or the mesh is degenerate (one visible device — the
    single-device fallback the tests pin)."""
    with _lock:
        min_batch, devices = _min_batch, _devices
    if devices == 1 or stripes < min_batch:
        return None
    width = _visible_devices()
    if devices > 0:
        width = min(width, devices)
    if width < 2 or stripes < width:
        return None
    with _lock:
        return _mesh_for_width(width)


def reset_for_tests() -> None:
    """Drop cached meshes and restore default knobs (test isolation)."""
    global _min_batch, _devices, _visible
    with _lock:
        _min_batch = DEFAULT_MIN_BATCH
        _devices = DEFAULT_DEVICES
        _visible = None
        _mesh_cache.clear()
