"""Packed-bitplane kernel equivalence suite (ISSUE 3 tentpole contract).

The packed-plane kernel (ceph_tpu.ops.packed_gf) must be byte-identical to
the bitsliced XOR-matmul (ceph_tpu.ops.xor_mm.xor_matmul) AND to the host
oracle (gf.bitslice.xor_matmul_host) for every geometry — it is an exact
refactoring of the same GF(2) linear map, so any diverging byte is a bug,
not a tolerance."""

import itertools

import numpy as np
import pytest

from ceph_tpu.codec import ErasureCodeTpuRs
from ceph_tpu.gf import isa_decode_matrix, isa_rs_vandermonde_matrix
from ceph_tpu.gf.bitslice import expand_matrix, xor_matmul_host
from ceph_tpu.ops.dispatch import LAUNCHES
from ceph_tpu.ops.packed_gf import PackedPlan, plane_schedule
from ceph_tpu.ops.xor_mm import xor_matmul


def rs_matrix(k, m):
    return isa_rs_vandermonde_matrix(k, m)[k:]


def rand_data(shape, seed=0):
    return np.random.default_rng(seed).integers(0, 256, shape, dtype=np.uint8)


class TestParityEquivalence:
    @pytest.mark.parametrize("k", [2, 4, 8, 12])
    @pytest.mark.parametrize("m", [1, 2, 3, 4])
    def test_geometry_grid_vs_matmul_and_host_oracle(self, k, m):
        gfm = rs_matrix(k, m)
        plan = PackedPlan(gfm)
        bm = expand_matrix(gfm)
        # lane-aligned (128-multiple) and ragged chunk lengths
        for L in (128, 100):
            data = rand_data((k, L), seed=k * 16 + m)
            got = np.asarray(plan(data))
            want_host = xor_matmul_host(bm, data)
            want_mm = np.asarray(xor_matmul(bm, data))
            assert np.array_equal(got, want_host), (k, m, L)
            assert np.array_equal(got, want_mm), (k, m, L)

    def test_batched_matches_per_stripe(self):
        gfm = rs_matrix(4, 2)
        plan = PackedPlan(gfm)
        bm = expand_matrix(gfm)
        data = rand_data((7, 4, 256), seed=9)
        got = np.asarray(plan(data))
        for s in range(7):
            assert np.array_equal(got[s], xor_matmul_host(bm, data[s])), s

    def test_random_matrix_with_zero_coefficients(self):
        rng = np.random.default_rng(11)
        gfm = rng.integers(0, 256, (3, 5), dtype=np.uint8)
        gfm[1] = 0  # all-zero output row must produce zero bytes
        gfm[0, 2] = 0
        plan = PackedPlan(gfm)
        data = rand_data((5, 160), seed=12)
        got = np.asarray(plan(data))
        assert np.array_equal(got, xor_matmul_host(expand_matrix(gfm), data))
        assert not got[1].any()

    def test_plane_schedule_is_coefficient_bits(self):
        gfm = np.array([[1, 2], [0, 255]], dtype=np.uint8)
        sched = plane_schedule(gfm)
        assert sched[0] == ((0, 0), (1, 1))  # 1 -> bit 0; 2 -> bit 1
        assert sched[1] == tuple((1, b) for b in range(8))  # 255 -> all bits

    def test_launch_counter_one_dispatch_per_batch(self):
        gfm = rs_matrix(4, 2)
        plan = PackedPlan(gfm)
        data = rand_data((16, 4, 128), seed=7)
        before = LAUNCHES.snapshot()
        plan(data)
        after = LAUNCHES.snapshot()
        assert after["launches"] - before["launches"] == 1
        assert after["stripes"] - before["stripes"] == 16


class TestDecodeRoundTrips:
    """Every erasure pattern of RS(4,2): production chunk round-trip plus
    packed-kernel equivalence on the inverted decode matrices."""

    def _codec(self):
        ec = ErasureCodeTpuRs()
        ec.init({"k": "4", "m": "2"})
        return ec

    def all_patterns(self):
        for r in (1, 2):
            yield from itertools.combinations(range(6), r)

    def test_chunk_roundtrip_every_pattern(self):
        ec = self._codec()
        payload = rand_data(4 * 512, seed=21).tobytes()
        chunks = ec.encode(set(range(6)), payload)
        for pattern in self.all_patterns():
            have = {i: chunks[i] for i in range(6) if i not in pattern}
            decoded = ec.decode(set(pattern), have)
            for e in pattern:
                assert np.array_equal(decoded[e], chunks[e]), pattern

    def test_packed_plan_on_decode_matrices(self):
        ec = self._codec()
        dist = ec.distribution_matrix()
        for pattern in self.all_patterns():
            plan = isa_decode_matrix(dist, list(pattern), 4)
            assert plan is not None, pattern
            c, decode_index = plan
            survivors = rand_data((4, 128), seed=sum(pattern))
            got = np.asarray(PackedPlan(c)(survivors))
            want = xor_matmul_host(expand_matrix(c), survivors)
            assert np.array_equal(got, want), pattern
            assert len(decode_index) == 4
