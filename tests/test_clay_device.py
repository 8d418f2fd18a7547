"""CLAY as one device program per stripe batch (ops/clay_gf.py).

The programs (JAX on the CPU here) must give, byte for byte, what the
plain reference (perfbench/clay_reference.py) and the codec's host path
(codec/clay.py, the chunk-level oracle) give: encode, layered decode of
every erasure pattern of 1..m chunks, and single-chunk repair.  Every
pattern runs both jitted and in numpy (`run_host`, the offload
runtime's fallback).  The erasure pattern is data of the program, so
one compile serves every pattern of a shape and round count.  Then the
served path: an ECBackend write and a degraded read of a CLAY(8,4,d=11)
pool at stripe unit 4096, one device program per object and no stripe
coupled on the host.
"""

import itertools

import numpy as np
import pytest

from ceph_tpu.codec.registry import instance
from ceph_tpu.ops import clay_gf, dispatch
from perfbench import clay_reference
from test_ec_backend import Cluster, ec_pool, payload, shard_coll

# (k, m, d) -> (q, t, nu): two codes without shortening, one with
CODES = {(4, 2, 5): (2, 3, 0), (8, 4, 11): (4, 3, 0), (5, 3, 7): (3, 3, 1)}


def clay(k, m, d):
    return instance().factory(
        "clay", {"k": str(k), "m": str(m), "d": str(d), "scalar_mds": "isa"})


def codewords(k, m, d, stripes, sc=64, seed=0):
    """(stripes, k+m, chunk): random data and the reference's parity."""
    g = clay_reference.Geometry(k, m, d)
    rng = np.random.default_rng([k, m, d, stripes, seed])
    data = rng.integers(0, 256, (stripes, k, g.planes * sc), dtype=np.uint8)
    return np.concatenate([data, clay_reference.encode_parity(data, g)], axis=1)


@pytest.mark.parametrize("code", list(CODES))
def test_geometry(code):
    ec, g = clay(*code), clay_reference.Geometry(*code)
    assert (ec.q, ec.t, ec.nu) == (g.q, g.t, g.nu) == CODES[code]


@pytest.mark.parametrize("code,stripes", [
    ((4, 2, 5), 3), ((8, 4, 11), 1), ((8, 4, 11), 3), ((8, 4, 11), 128), ((5, 3, 7), 3)])
def test_encode_program_equals_reference_and_host_codec(code, stripes):
    k, m, _ = code
    ec = clay(*code)
    full = codewords(*code, stripes)
    data = full[:, :k]
    assert np.array_equal(np.asarray(ec.encode_array(data)), full[:, k:])
    assert np.array_equal(ec.encode_array_host(data), full[:, k:])
    for s in range(min(stripes, 2)):
        chunks = ec.encode(set(range(k + m)), data[s].reshape(-1))
        assert all(np.array_equal(chunks[k + j], full[s, k + j]) for j in range(m))


@pytest.mark.parametrize("code,erasures",
                         [(c, e) for c in CODES for e in range(1, c[1] + 1)])
def test_every_decode_pattern(code, erasures):
    """Every pattern of `erasures` lost chunks: the jitted program against
    the reference's codeword; its numpy run and the host codec's decode
    against it on a sample."""
    k, m, _ = code
    ec = clay(*code)
    full = codewords(*code, 3)
    for n_pat, er in enumerate(itertools.combinations(range(k + m), erasures)):
        er = list(er)
        idx = ec.decode_index(er)
        assert len(idx) == k and not set(idx) & set(er)
        got = np.asarray(ec.decode_array(er, full[:, idx]))
        assert np.array_equal(got, full[:, er]), er
        if n_pat % 5 == 0:
            assert np.array_equal(ec.decode_array_host(er, full[:, idx]), got), er
        if n_pat % 17 == 0:
            have = {i: full[1, i] for i in range(k + m) if i not in er}
            decoded = ec.decode(set(er), have)
            assert all(np.array_equal(decoded[e], full[1, e]) for e in er), er


@pytest.mark.parametrize("stripes", [1, 128])
def test_decode_at_batch_sizes(stripes):
    """(8,4,d=11) decodes of one to four erasures at one stripe and at a
    4 MiB object's 128."""
    ec = clay(8, 4, 11)
    full = codewords(8, 4, 11, stripes)
    for er in ([3], [0, 9], [1, 5, 10], [0, 4, 8, 11]):
        got = np.asarray(ec.decode_array(er, full[:, ec.decode_index(er)]))
        assert np.array_equal(got, full[:, er]), er


def _repairs(code):
    k, m, d = code
    ec = clay(*code)
    full = codewords(*code, 3, sc=32)
    sc = full.shape[-1] // ec.sub_chunk_no
    for lost in range(k + m):
        avail = set(range(k + m)) - {lost}
        assert ec.is_repair({lost}, avail)
        plan = ec.minimum_to_decode({lost}, avail)
        assert len(plan) == d
        frags = {n: np.concatenate([full[:, n, o * sc:(o + c) * sc] for o, c in runs], axis=1)
                 for n, runs in plan.items()}
        # each helper sends 1/q of its chunk
        assert all(f.shape[1] * ec.q == full.shape[-1] for f in frags.values())
        got = ec.decode_fragments_batch({lost}, frags, full.shape[-1])[lost]
        assert np.array_equal(got, full[:, lost]), lost
        helpers = tuple(ec._ext(i) for i in sorted(frags))
        pat = clay_gf.pattern("repair", ec._geometry, ec._ext(lost), helpers)
        data = np.stack([frags[i] for i in sorted(frags)], axis=1)
        host = clay_gf.run_host(ec._geometry, pat, data)[:, 0]
        assert np.array_equal(host, full[:, lost]), lost
        assert np.array_equal(ec._repair({lost}, frags, full.shape[-1])[lost],
                              full[:, lost]), lost


@pytest.mark.parametrize("code", list(CODES))
def test_single_chunk_repair(code):
    _repairs(code)


def test_launch_counters():
    ec = clay(8, 4, 11)
    full = codewords(8, 4, 11, 5)
    before, launches = dispatch.CLAY.snapshot(), dispatch.LAUNCHES.snapshot()
    np.asarray(ec.encode_array(full[:, :8]))
    np.asarray(ec.decode_array([2, 9], full[:, ec.decode_index([2, 9])]))
    after = dispatch.CLAY.snapshot()
    assert after["encode_launches"] - before["encode_launches"] == 1
    assert after["decode_launches"] - before["decode_launches"] == 1
    assert after["stripes"] - before["stripes"] == 10
    # encode: one round (the parities fill one row); {2, 9}: one per score
    assert after["rounds"] - before["rounds"] == 1 + ec._decode_pattern([2, 9]).rounds
    assert after["host_stripes"] == before["host_stripes"]
    assert dispatch.LAUNCHES.snapshot()["launches"] - launches["launches"] == 2
    assert "clay.encode_launches" in dispatch.perf_dump()


@pytest.mark.parametrize("k,m,d", [(8, 4, 11), (4, 2, 5)])
def test_stripe_unit_4096_passes(k, m, d):
    """The mon requires chunk == stripe_unit; a chunk of 4096 bytes is
    q^t planes of 32-byte multiples."""
    ec = clay(k, m, d)
    assert ec.get_chunk_size(k * 4096) == 4096
    assert ec.get_chunk_size(1) == ec.sub_chunk_no * 32


@pytest.mark.parametrize("k,m", [(8, 3), (4, 2)])
def test_rs_chunk_sizes_unchanged(k, m):
    rs = instance().factory("tpu", {"k": str(k), "m": str(m)})
    assert rs.get_chunk_size(k * 4096) == 4096
    assert rs.get_chunk_size(1) == 128
    assert rs.get_chunk_size(k * 4096 + 1) == 4096 + 128


def test_served_write_and_degraded_read():
    """A 4 MiB write through ECBackend: one clay_encode program for its
    128 stripes; every shard equals the reference; a read with a shard
    lost runs one clay_decode program; nothing coupled on the host."""
    pool, profiles = ec_pool(8, 4, plugin="clay", d="11", scalar_mds="isa")
    c = Cluster(pool, profiles)
    obj = payload(4 << 20, seed=3)
    cfg = {"k": 8, "m": 4, "stripe_unit": 4096, "profile": {"d": 11}}
    before, fallbacks = dispatch.CLAY.snapshot(), dispatch.FALLBACK_LAUNCHES.snapshot()
    c.write("obj", 0, obj)
    mid = dispatch.CLAY.snapshot()
    assert mid["encode_launches"] - before["encode_launches"] == 1
    assert mid["stripes"] - before["stripes"] == 128
    want = clay_reference.object_shards(obj, cfg)
    for s in range(12):
        assert c.stores[s].read(shard_coll(c.pgid, s), "obj", 0, 0) == want[s], s
    c.stores[3]._remove(shard_coll(c.pgid, 3), "obj")
    assert c.read("obj", 0, len(obj)) == obj
    after = dispatch.CLAY.snapshot()
    assert after["decode_launches"] - mid["decode_launches"] == 1
    assert after["host_stripes"] == before["host_stripes"]
    assert dispatch.FALLBACK_LAUNCHES.snapshot() == fallbacks
