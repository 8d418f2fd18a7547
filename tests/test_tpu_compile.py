"""Compile the smoke path's kernels for a described v5e chip.

Nothing runs: the TPU compiler that ships with jaxlib compiles for a
`v5e:2x2` topology that is described, not attached, and refuses what the
chip would refuse (unaligned slices, too much VMEM, unpartitionable
kernels).  The topology is described inside a fixture, never while a
module is imported, so every xdist worker collects the same tests and
only the one running this file loads the TPU library.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from ceph_tpu.gf import isa_decode_matrix, isa_rs_vandermonde_matrix
from ceph_tpu.ops.pallas_gf import CodingPlan, _gf_code_swar, pick_geometry

K, M = 8, 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip can be written to the persistent
    # cache but never read back here: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _encode_rows():
    return isa_rs_vandermonde_matrix(K, M)[K:]


@pytest.mark.parametrize(
    "shape",
    [
        (256, K, 128 * 1024),  # the codec phase: 256 stripes of 1 MiB
        (128, K, 4096),  # one 4 MiB object at the 4 KiB stripe unit
        (64, K, 64 * 1024),
        (4, K, 512),
    ],
)
def test_swar_kernel_compiles(one_chip, shape):
    plan = CodingPlan(_encode_rows())
    rows, cols = pick_geometry(shape[-1])
    x = jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=one_chip)
    compiled = _gf_code_swar.lower(
        x, sched=plan.sched, m=M, rows=rows, cols=cols, interpret=False
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rs83_decode_plan_compiles(one_chip):
    dist = isa_rs_vandermonde_matrix(K, M)
    c, index = isa_decode_matrix(dist, [0, 5, 9], K)
    assert len(index) == K
    plan = CodingPlan(c, decode=True)
    x = jax.ShapeDtypeStruct((128, K, 4096), jnp.uint8, sharding=one_chip)
    compiled = jax.jit(plan).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.memory_analysis().output_size_in_bytes
    assert out == 128 * 3 * 4096


def test_sharded_plan_encode_compiles_on_four_chips(topo):
    from ceph_tpu.parallel import sharded
    from ceph_tpu.parallel.mesh import LANE_AXIS, STRIPE_AXIS

    mesh = Mesh(
        np.array(topo.devices[:4]).reshape(4, 1), (STRIPE_AXIS, LANE_AXIS)
    )
    plan = CodingPlan(_encode_rows())
    x = jax.ShapeDtypeStruct(
        (256, K, 128 * 1024), jnp.uint8,
        sharding=NamedSharding(mesh, sharded._stripe_spec(mesh)),
    )
    try:
        exe = sharded._plan_encode_executable(mesh, plan)
        compiled = exe.lower(x).compile()
    finally:
        sharded._plan_execs.clear()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # stripe-parallel coding needs no cross-chip traffic
    for op in ("all-reduce", "all-gather", "all-to-all", "collective-permute"):
        assert op not in text, op
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    assert per_chip == 256 * K * 128 * 1024 // 4


@pytest.mark.parametrize("erasures", [None, [3]])
def test_clay84_object_program_compiles(one_chip, erasures):
    """CLAY(8,4,d=11): the encode, and one program for every decode
    pattern, of one 4 MiB object (128 stripes of 64 planes of 64 B)."""
    from ceph_tpu.codec.registry import instance
    from ceph_tpu.ops import clay_gf

    ec = instance().factory("clay", {"k": "8", "m": "4", "d": "11", "scalar_mds": "isa"})
    if erasures is None:
        fn, pat = clay_gf.clay_encode, ec._encode_pattern()
    else:
        fn, pat = clay_gf.clay_decode, ec._decode_pattern(erasures)
    x = jax.ShapeDtypeStruct((128, 8, 4096), jnp.uint8, sharding=one_chip)
    arrays = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                   for a in pat.arrays)
    compiled = fn.lower(x, arrays, geo=ec._geometry, n_out=pat.n_out,
                        repair=pat.repair).compile()
    out = compiled.memory_analysis().output_size_in_bytes
    assert out == 128 * pat.n_out * 4096
