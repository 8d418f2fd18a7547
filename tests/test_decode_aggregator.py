"""DecodeAggregator semantics + batched recovery/degraded-read parity
(ISSUE 5 contracts).

Covers: every RS(4,2) and RS(8,3) erasure pattern decoded through the
aggregated path byte-identical to the host GF oracle; ticket ordering and
flush triggers mirroring tests/test_aggregator.py; sticky per-group error
containment; the "N same-pattern objects recovered in one window = O(1)
decode dispatches" launch-counter invariant through a full ECBackend
recovery flow; multi-object degraded reads sharing one launch; and the
prometheus export of the decode occupancy/launch-size histograms."""

import itertools

import numpy as np
import pytest

from ceph_tpu.codec import ErasureCodeTpuRs
from ceph_tpu.codec.matrix_codec import DecodeAggregator
from ceph_tpu.common.perf_counters import PerfCountersCollection
from ceph_tpu.gf.bitslice import expand_matrix, xor_matmul_host
from ceph_tpu.ops.dispatch import DECODE_LAUNCHES, LAUNCHES
from ceph_tpu.osd.osdmap import PG_NONE
from ceph_tpu.stripe import StripeInfo
from ceph_tpu.stripe import stripe as stripe_mod


def make_rs(k=4, m=2):
    ec = ErasureCodeTpuRs()
    ec.init({"k": str(k), "m": str(m)})
    return ec


def payload(sinfo, stripes, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, stripes * sinfo.stripe_width, dtype=np.uint8)


def oracle_shards(ec, data, sinfo):
    """Host-oracle per-shard streams (data + parity) for a whole object."""
    k, m = ec.k, ec.m
    shaped = data.reshape(-1, k, sinfo.chunk_size)
    bm = expand_matrix(ec.distribution_matrix()[k:])
    parity = np.stack([xor_matmul_host(bm, s) for s in shaped])
    out = {i: np.ascontiguousarray(shaped[:, i, :]).reshape(-1) for i in range(k)}
    for j in range(m):
        out[k + j] = np.ascontiguousarray(parity[:, j, :]).reshape(-1)
    return out


def erasure_patterns(n, m):
    """Every erasure pattern of 1..m shards out of n."""
    for r in range(1, m + 1):
        yield from itertools.combinations(range(n), r)


class TestAllErasurePatterns:
    """Batched decode through the aggregated path must be byte-identical
    to the host oracle for EVERY decodable erasure pattern (acceptance
    criterion)."""

    @pytest.mark.parametrize("k,m", [(4, 2), (8, 3)])
    def test_recovery_decode_all_patterns(self, k, m):
        ec = make_rs(k, m)
        sinfo = StripeInfo(k * 1024, 1024)
        data = payload(sinfo, 4, seed=k * 100 + m)
        truth = oracle_shards(ec, data, sinfo)
        agg = DecodeAggregator(window=10_000)
        pends = []
        for pat in erasure_patterns(k + m, m):
            have = {i: truth[i] for i in range(k + m) if i not in pat}
            pends.append(
                (
                    pat,
                    stripe_mod.decode_shards_launch(
                        sinfo, ec, have, set(pat), aggregator=agg
                    ),
                )
            )
        agg.flush()
        for pat, pend in pends:
            out = pend.result()
            for e in pat:
                assert np.array_equal(out[e], truth[e]), (pat, e)

    @pytest.mark.parametrize("k,m", [(4, 2), (8, 3)])
    def test_degraded_read_decode_all_data_patterns(self, k, m):
        """decode_concat (the client-read path) through the aggregator:
        the logical bytes come back exactly for every erasure pattern."""
        ec = make_rs(k, m)
        sinfo = StripeInfo(k * 1024, 1024)
        data = payload(sinfo, 2, seed=k * 10 + m)
        truth = oracle_shards(ec, data, sinfo)
        agg = DecodeAggregator(window=10_000)
        pends = []
        for pat in erasure_patterns(k + m, m):
            have = {i: truth[i] for i in range(k + m) if i not in pat}
            pends.append(
                stripe_mod.decode_concat_launch(sinfo, ec, have, aggregator=agg)
            )
        agg.flush()
        for pend in pends:
            assert np.array_equal(pend.result(), data)


class TestDecodeAggregatorCore:
    def setup_method(self):
        self.ec = make_rs(4, 2)
        self.sinfo = StripeInfo(4 * 4096, 4096)

    def _launch(self, agg, stripes, seed, lost=(1,)):
        data = payload(self.sinfo, stripes, seed)
        truth = oracle_shards(self.ec, data, self.sinfo)
        have = {i: truth[i] for i in range(6) if i not in lost}
        pend = stripe_mod.decode_shards_launch(
            self.sinfo, self.ec, have, set(lost), aggregator=agg
        )
        return truth, pend

    def test_same_pattern_submitters_coalesce_into_one_dispatch(self):
        agg = DecodeAggregator(window=8)
        subs = [self._launch(agg, 8, seed=i) for i in range(8)]
        before = DECODE_LAUNCHES.snapshot()["launches"]
        agg.flush()
        launches = DECODE_LAUNCHES.snapshot()["launches"] - before
        assert launches <= 2, launches
        # every submitter gets ITS reconstruction back, byte-exact
        for truth, pend in subs:
            assert np.array_equal(pend.result()[1], truth[1])

    def test_window_trigger_and_pending(self):
        agg = DecodeAggregator(window=4)
        pends = [self._launch(agg, 1, seed=i)[1] for i in range(3)]
        assert agg.pending() == 3
        assert not any(p.launched() for p in pends)
        assert not any(p.ready() for p in pends)
        p4 = self._launch(agg, 1, seed=9)[1]
        assert agg.pending() == 0
        assert all(p.launched() for p in pends) and p4.launched()
        assert agg.perf.get("flush_window") == 1

    def test_byte_budget_trigger(self):
        agg = DecodeAggregator(window=1000, max_bytes=3 * self.sinfo.stripe_width)
        self._launch(agg, 1, seed=0)
        assert agg.pending() == 1
        self._launch(agg, 2, seed=1)
        assert agg.pending() == 0
        assert agg.perf.get("flush_bytes") == 1

    def test_reap_forces_launch(self):
        """Materializing a windowed ticket must flush its group rather
        than deadlock (recovery barriers depend on this)."""
        agg = DecodeAggregator(window=100)
        truth, pend = self._launch(agg, 2, seed=3)
        assert not pend.launched()
        out = pend.result()
        assert np.array_equal(out[1], truth[1])
        assert agg.perf.get("flush_reap") == 1

    def test_distinct_patterns_group_separately(self):
        """Interleaved submissions of two erasure patterns: each ticket
        resolves to its own pattern's reconstruction, in order."""
        agg = DecodeAggregator(window=100)
        subs = [
            self._launch(agg, 2, seed=100 + i, lost=((1,) if i % 2 else (2, 4)))
            for i in range(6)
        ]
        assert len(agg._groups) == 2
        agg.flush()
        for i, (truth, pend) in enumerate(subs):
            out = pend.result()
            for e in (1,) if i % 2 else (2, 4):
                assert np.array_equal(out[e], truth[e])

    def test_padding_to_pow2_sliced_back(self):
        agg = DecodeAggregator(window=100)
        truth, pend = self._launch(agg, 3, seed=5)
        agg.flush()
        out = pend.result()
        assert agg.perf.get("pad_stripes") == 1  # 3 -> 4
        assert np.array_equal(out[1], truth[1])
        assert out[1].size == 3 * 4096

    def test_immediate_mode_still_counts_metrics(self):
        agg = DecodeAggregator(window=0)
        truth, pend = self._launch(agg, 2, seed=8)
        assert pend.launched()
        assert np.array_equal(pend.result()[1], truth[1])
        assert agg.perf.get("submits") == 1
        assert agg.perf.get("launches") == 1
        assert agg.perf.get("flush_immediate") == 1
        assert agg.perf.get("pad_stripes") == 0

    def test_failed_launch_is_sticky_and_reported_to_coriders(self):
        from ceph_tpu.codec.interface import EcError

        agg = DecodeAggregator(window=2)
        _, pend1 = self._launch(agg, 1, seed=0)
        real = self.ec.decode_array
        real_host = self.ec.decode_array_host

        def boom(erasures, survivors):
            # device AND host-oracle failure: only then is the error
            # sticky (a device-only failure now completes on the host)
            raise RuntimeError("injected device OOM")

        self.ec.decode_array = boom
        self.ec.decode_array_host = boom
        try:
            # second submission trips the window; its launch fails, but
            # submit must NOT raise into an arbitrary co-rider — the
            # error is sticky on the group and reported at reap
            _, pend2 = self._launch(agg, 1, seed=1)
        finally:
            self.ec.decode_array = real
            self.ec.decode_array_host = real_host
        for pend in (pend1, pend2):
            assert pend.ready()
            with pytest.raises(EcError):
                pend.result()

    def test_prometheus_export_has_histogram_families(self):
        agg = DecodeAggregator(window=2)
        for i in range(2):
            self._launch(agg, 1, seed=i)
        coll = PerfCountersCollection()
        coll.add(agg.perf)
        text = coll.prometheus_text()
        for family in ("stripes_per_launch", "tickets_per_launch", "launch_bytes"):
            assert f"ceph_tpu_ec_decode_aggregator_{family}_bucket" in text
            assert f"ceph_tpu_ec_decode_aggregator_{family}_count" in text


class TestBackendAggregatedRecovery:
    """Recovery and degraded reads through a full ECBackend cluster with
    the decode window open: correctness survives, and same-pattern
    objects share device launches."""

    def _cluster(self, k=4, m=2, window=64):
        from test_ec_backend import Cluster, ec_pool

        pool, profiles = ec_pool(k, m)
        c = Cluster(pool, profiles)
        agg = DecodeAggregator(window=window)
        for b in c.backends:
            b.decode_aggregator = agg
        return c, agg

    def _deliver_no_flush(self, c):
        """Drain the message queue WITHOUT the pump barrier, so recovery
        decodes stay windowed until an explicit flush."""
        steps = 0
        while c.queue:
            osd, msg = c.queue.pop(0)
            if osd == PG_NONE or not (0 <= osd < len(c.backends)):
                continue
            c.backends[osd].handle_message(msg)
            steps += 1
            assert steps < 100000, "message storm"

    def test_n_objects_one_pattern_one_decode_launch(self):
        from ceph_tpu.osd.pg_backend import shard_coll

        c, agg = self._cluster(window=64)
        n_obj = 6
        datas = {}
        originals = {}
        for i in range(n_obj):
            oid = f"obj{i}"
            datas[oid] = payload(
                StripeInfo(c.pool.stripe_width, c.pool.stripe_width // 4),
                2,
                seed=i,
            ).tobytes()
            c.write(oid, 0, datas[oid])
        lost = 1
        coll = shard_coll(c.pgid, lost)
        for oid in datas:
            originals[oid] = c.stores[lost].read(coll, oid, 0, 0)
            c.stores[lost]._remove(coll, oid)
            c.missing[oid] = {lost}
        res = []
        before = DECODE_LAUNCHES.snapshot()["launches"]
        for oid in datas:
            c.primary.recover_object(oid, {lost}, lambda e: res.append(e))
        # deliver all reads + replies with no barrier: every object's
        # decode lands in the shared window
        self._deliver_no_flush(c)
        assert c.primary._decode_pipe and agg.pending() == n_obj
        assert DECODE_LAUNCHES.snapshot()["launches"] == before
        c.primary.flush_decodes()  # ONE aggregated launch for all objects
        launches = DECODE_LAUNCHES.snapshot()["launches"] - before
        assert launches == 1, launches
        c.pump()  # pushes land
        for oid in datas:
            c.missing.pop(oid)
        assert res == [0] * n_obj
        for oid in datas:
            assert c.stores[lost].read(coll, oid, 0, 0) == originals[oid]

    def test_multi_object_degraded_read_one_decode_launch(self):
        c, agg = self._cluster(window=64)
        n_obj = 4
        datas = {}
        for i in range(n_obj):
            oid = f"d{i}"
            datas[oid] = payload(
                StripeInfo(c.pool.stripe_width, c.pool.stripe_width // 4),
                2,
                seed=10 + i,
            ).tobytes()
            c.write(oid, 0, datas[oid])
        c.acting[1] = PG_NONE  # one shard dark -> every read reconstructs
        out = {}
        before = DECODE_LAUNCHES.snapshot()["launches"]
        c.primary.objects_read_and_reconstruct(
            {oid: [(0, len(d))] for oid, d in datas.items()},
            lambda res: out.update(res),
        )
        c.pump()
        launches = DECODE_LAUNCHES.snapshot()["launches"] - before
        assert launches == 1, launches
        for oid, data in datas.items():
            err, bufs = out[oid]
            assert err == 0
            assert b"".join(bufs) == data

    def test_recovery_all_patterns_through_backend(self):
        """Full-cluster recovery for every RS(4,2) erasure pattern whose
        shards can all be marked missing (parity + data mixes)."""
        from ceph_tpu.osd.pg_backend import shard_coll

        c, agg = self._cluster(window=64)
        sinfo = StripeInfo(c.pool.stripe_width, c.pool.stripe_width // 4)
        for pi, pat in enumerate(erasure_patterns(6, 2)):
            oid = f"p{pi}"
            c.write(oid, 0, payload(sinfo, 2, seed=50 + pi).tobytes())
            snapshots = {}
            for s in pat:
                coll = shard_coll(c.pgid, s)
                snapshots[s] = c.stores[s].read(coll, oid, 0, 0)
                c.stores[s]._remove(coll, oid)
            c.missing[oid] = set(pat)
            res = []
            c.primary.recover_object(oid, set(pat), lambda e: res.append(e))
            c.pump()
            c.missing.pop(oid)
            assert res == [0], (pat, res)
            for s in pat:
                coll = shard_coll(c.pgid, s)
                assert c.stores[s].read(coll, oid, 0, 0) == snapshots[s], pat

    def test_decode_launch_failure_fails_recovery_cleanly(self):
        """A failed aggregated decode launch must fail the affected
        RecoveryOps (negative errno, no recovery_ops leak) and leave the
        backend able to recover the same object afterwards."""
        from ceph_tpu.osd.pg_backend import shard_coll

        c, agg = self._cluster(window=64)
        sinfo = StripeInfo(c.pool.stripe_width, c.pool.stripe_width // 4)
        data = payload(sinfo, 2, seed=77).tobytes()
        c.write("fx", 0, data)
        lost = 2
        coll = shard_coll(c.pgid, lost)
        original = c.stores[lost].read(coll, "fx", 0, 0)
        c.stores[lost]._remove(coll, "fx")
        c.missing["fx"] = {lost}
        primary = c.primary
        real = primary.ec.decode_array
        real_host = primary.ec.decode_array_host

        def boom(erasures, survivors):
            # fails on the device AND the host oracle: truly unrecoverable
            raise RuntimeError("injected decode launch failure")

        res = []
        primary.ec.decode_array = boom
        primary.ec.decode_array_host = boom
        try:
            primary.recover_object("fx", {lost}, lambda e: res.append(e))
            c.pump()  # barrier reaps the failed launch
        finally:
            primary.ec.decode_array = real
            primary.ec.decode_array_host = real_host
        assert len(res) == 1 and res[0] < 0
        assert not primary.recovery_ops
        assert not primary._decode_pipe
        # the backend recovers: the same object repairs fine afterwards
        primary.recover_object("fx", {lost}, lambda e: res.append(e))
        c.pump()
        c.missing.pop("fx")
        assert res[1] == 0
        assert c.stores[lost].read(coll, "fx", 0, 0) == original
