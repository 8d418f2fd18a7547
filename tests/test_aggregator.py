"""EncodeAggregator semantics (ISSUE 3 satellite contract).

Covers: ticket ordering, window/byte-budget/explicit flush triggers, the
"64 stripes across 8 submitters <= 2 device dispatches" launch-counter
invariant, padding correctness, flush-on-commit through
a full ECBackend write pipeline, and the prometheus export of the
occupancy/launch-size histograms."""

import numpy as np
import pytest

from ceph_tpu.codec import ErasureCodeTpuRs
from ceph_tpu.codec.matrix_codec import EncodeAggregator
from ceph_tpu.common.perf_counters import PerfCountersCollection
from ceph_tpu.gf.bitslice import expand_matrix, xor_matmul_host
from ceph_tpu.ops.dispatch import LAUNCHES
from ceph_tpu.stripe import StripeInfo
from ceph_tpu.stripe import stripe as stripe_mod


def make_rs(k=4, m=2):
    ec = ErasureCodeTpuRs()
    ec.init({"k": str(k), "m": str(m)})
    return ec


def payload(sinfo, stripes, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, stripes * sinfo.stripe_width, dtype=np.uint8)


def parity_oracle(ec, data, sinfo):
    bm = expand_matrix(ec.distribution_matrix()[ec.k :])
    shaped = data.reshape(-1, ec.k, sinfo.chunk_size)
    return np.stack([xor_matmul_host(bm, s) for s in shaped])


class TestAggregatorCore:
    def setup_method(self):
        self.ec = make_rs(4, 2)
        self.sinfo = StripeInfo(4 * 4096, 4096)

    def test_64_stripes_8_submitters_at_most_2_dispatches(self):
        agg = EncodeAggregator(window=8)
        pends = []
        before = LAUNCHES.snapshot()["launches"]
        for w in range(8):
            data = payload(self.sinfo, 8, seed=w)
            pends.append(
                (data, stripe_mod.encode_launch(self.sinfo, self.ec, data, aggregator=agg))
            )
        agg.flush()
        launches = LAUNCHES.snapshot()["launches"] - before
        assert launches <= 2, launches
        # every submitter gets ITS parity back, byte-exact
        for data, pend in pends:
            shards = pend.result()
            want = parity_oracle(self.ec, data, self.sinfo)
            for i in range(2):
                assert np.array_equal(
                    shards[4 + i].reshape(-1, 4096), want[:, i, :]
                )

    def test_window_trigger_and_pending(self):
        agg = EncodeAggregator(window=4)
        pends = [
            stripe_mod.encode_launch(
                self.sinfo, self.ec, payload(self.sinfo, 1, seed=i), aggregator=agg
            )
            for i in range(3)
        ]
        assert agg.pending() == 3
        assert not any(p.launched() for p in pends)
        assert not any(p.ready() for p in pends)
        # the 4th submission fills the window and launches everything
        p4 = stripe_mod.encode_launch(
            self.sinfo, self.ec, payload(self.sinfo, 1, seed=9), aggregator=agg
        )
        assert agg.pending() == 0
        assert all(p.launched() for p in pends) and p4.launched()
        assert agg.perf.get("flush_window") == 1

    def test_byte_budget_trigger(self):
        agg = EncodeAggregator(window=1000, max_bytes=3 * self.sinfo.stripe_width)
        stripe_mod.encode_launch(
            self.sinfo, self.ec, payload(self.sinfo, 1, seed=0), aggregator=agg
        )
        assert agg.pending() == 1
        stripe_mod.encode_launch(
            self.sinfo, self.ec, payload(self.sinfo, 2, seed=1), aggregator=agg
        )
        assert agg.pending() == 0
        assert agg.perf.get("flush_bytes") == 1

    def test_reap_forces_launch(self):
        """Materializing a windowed ticket must flush its group rather
        than deadlock (the commit path depends on this)."""
        agg = EncodeAggregator(window=100)
        data = payload(self.sinfo, 2, seed=3)
        pend = stripe_mod.encode_launch(self.sinfo, self.ec, data, aggregator=agg)
        assert not pend.launched()
        shards = pend.result()
        want = parity_oracle(self.ec, data, self.sinfo)
        assert np.array_equal(shards[4].reshape(-1, 4096), want[:, 0, :])
        assert agg.perf.get("flush_reap") == 1

    def test_ticket_ordering_across_interleaved_geometries(self):
        """Interleaved submissions of two geometries: each ticket resolves
        to its own submission's parity, in order."""
        ec2 = make_rs(2, 1)
        sinfo2 = StripeInfo(2 * 4096, 4096)
        agg = EncodeAggregator(window=100)
        subs = []
        for i in range(6):
            if i % 2:
                d = payload(sinfo2, 1, seed=100 + i)
                subs.append((ec2, sinfo2, d, stripe_mod.encode_launch(sinfo2, ec2, d, aggregator=agg)))
            else:
                d = payload(self.sinfo, 2, seed=100 + i)
                subs.append((self.ec, self.sinfo, d, stripe_mod.encode_launch(self.sinfo, self.ec, d, aggregator=agg)))
        agg.flush()
        for ec, sinfo, d, pend in subs:
            shards = pend.result()
            want = parity_oracle(ec, d, sinfo)
            assert np.array_equal(
                shards[ec.k].reshape(-1, sinfo.chunk_size), want[:, 0, :]
            )

    def test_padding_to_pow2_sliced_back(self):
        agg = EncodeAggregator(window=100)
        data = payload(self.sinfo, 3, seed=5)
        pend = stripe_mod.encode_launch(self.sinfo, self.ec, data, aggregator=agg)
        agg.flush()
        shards = pend.result()
        assert agg.perf.get("pad_stripes") == 1  # 3 -> 4
        want = parity_oracle(self.ec, data, self.sinfo)
        assert np.array_equal(shards[4].reshape(-1, 4096), want[:, 0, :])
        assert shards[4].size == 3 * 4096

    def test_single_unpadded_ticket_gets_launch_host_bytes(self):
        """The default path: a lone submission in immediate mode is its
        own unpadded launch, and its ticket resolves to that launch's
        host bytes — no concatenate, no pad, no slice copy."""
        agg = EncodeAggregator(window=0)
        data = payload(self.sinfo, 4, seed=7)
        ticket = agg.submit(self.ec, data.reshape(-1, 4, 4096))
        parity = ticket.result()
        group = ticket._group
        assert group.pad == 0 and agg.perf.get("pad_stripes") == 0
        assert parity.shape == group.host.shape
        assert np.shares_memory(parity, group.host)
        assert np.array_equal(parity, parity_oracle(self.ec, data, self.sinfo))

    def test_immediate_mode_still_counts_metrics(self):
        agg = EncodeAggregator(window=0)
        data = payload(self.sinfo, 2, seed=8)
        pend = stripe_mod.encode_launch(self.sinfo, self.ec, data, aggregator=agg)
        assert pend.launched()
        pend.result()
        assert agg.perf.get("submits") == 1
        assert agg.perf.get("launches") == 1
        assert agg.perf.get("flush_immediate") == 1
        # immediate mode must not pad: the direct path never did
        assert agg.perf.get("pad_stripes") == 0

    def test_prometheus_export_has_histogram_families(self):
        agg = EncodeAggregator(window=2)
        for i in range(2):
            stripe_mod.encode_launch(
                self.sinfo, self.ec, payload(self.sinfo, 1, seed=i), aggregator=agg
            )
        coll = PerfCountersCollection()
        coll.add(agg.perf)
        text = coll.prometheus_text()
        for family in ("stripes_per_launch", "tickets_per_launch", "launch_bytes"):
            assert f"ceph_tpu_ec_aggregator_{family}_bucket" in text
            assert f"ceph_tpu_ec_aggregator_{family}_count" in text


class TestFlushOnCommit:
    """The ECBackend commit barrier must drain the aggregation window:
    writes submitted into a wide-open window still commit, and their
    shard bytes land byte-exact."""

    def _cluster(self, window):
        from test_ec_backend import Cluster, ec_pool

        pool, profiles = ec_pool(4, 2)
        c = Cluster(pool, profiles)
        agg = EncodeAggregator(window=window)
        for b in c.backends:
            b.encode_aggregator = agg
        return c, agg

    def test_windowed_writes_commit_and_verify(self):
        from ceph_tpu.msg.messages import ReqId
        from ceph_tpu.osd.ec_transaction import PGTransaction

        c, agg = self._cluster(window=64)
        rng = np.random.default_rng(0)
        done = []
        datas = {}
        for i in range(5):
            oid = f"obj{i}"
            datas[oid] = rng.integers(
                0, 256, 2 * c.pool.stripe_width, dtype=np.uint8
            ).tobytes()
            pgt = PGTransaction(oid).write(0, datas[oid])
            c.primary.submit_transaction(
                pgt, ReqId("client", i), lambda i=i: done.append(i)
            )
        # encodes submitted but windowed: nothing committed yet
        assert agg.pending() > 0
        c.pump()  # flush_encodes drains the window (the commit barrier)
        assert sorted(done) == list(range(5))
        assert agg.pending() == 0
        for oid, data in datas.items():
            assert c.read(oid, 0, len(data)) == data

    def test_flush_encodes_drains_everything(self):
        from ceph_tpu.msg.messages import ReqId
        from ceph_tpu.osd.ec_transaction import PGTransaction

        c, agg = self._cluster(window=64)
        for i in range(3):
            pgt = PGTransaction(f"o{i}").write(0, bytes(c.pool.stripe_width))
            c.primary.submit_transaction(pgt, ReqId("cl", i), lambda: None)
        assert agg.pending() == 3
        c.primary.flush_encodes()
        assert agg.pending() == 0


class TestAggregatorRobustness:
    def setup_method(self):
        self.ec = make_rs(4, 2)
        self.sinfo = StripeInfo(4 * 4096, 4096)

    def test_pad_target_bucketing_is_capped(self):
        agg = EncodeAggregator(window=2)
        assert agg._pad_target(1) == 1
        assert agg._pad_target(3) == 4
        assert agg._pad_target(64) == 64
        assert agg._pad_target(65) == 128
        # beyond 64, multiples of 64 — never the up-to-2x of pure pow2
        assert agg._pad_target(260) == 320
        assert agg._pad_target(1000) == 1024

    def test_failed_launch_is_sticky_and_reported_to_coriders(self):
        from ceph_tpu.codec.interface import EcError

        agg = EncodeAggregator(window=2)
        data1 = payload(self.sinfo, 1, seed=0)
        pend1 = stripe_mod.encode_launch(self.sinfo, self.ec, data1, aggregator=agg)

        real = self.ec.encode_array
        real_host = self.ec.encode_array_host

        def boom(data):
            # both the device dispatch AND the host-oracle fallback fail:
            # only then is the error sticky (a device-only failure now
            # completes on the host, ISSUE 7)
            raise RuntimeError("injected device OOM")

        self.ec.encode_array = boom
        self.ec.encode_array_host = boom
        try:
            # second submission trips the window; its launch fails, but
            # submit must NOT raise into an arbitrary co-rider's write —
            # the error is sticky on the group and reported at reap
            pend2 = stripe_mod.encode_launch(
                self.sinfo, self.ec, payload(self.sinfo, 1, seed=1), aggregator=agg
            )
        finally:
            self.ec.encode_array = real
            self.ec.encode_array_host = real_host
        # every co-rider's reap reports the failure instead of crashing
        # on a half-torn group, and polling sees it as "ready" (reapable)
        for pend in (pend1, pend2):
            assert pend.ready()
            with pytest.raises(EcError):
                pend.result()

    def test_ecbackend_fails_ops_cleanly_on_launch_failure(self):
        """A failed aggregated launch must fail the affected write ops
        (on_failure fires, pins released, no in_flight leak) — not leak
        an exception out of the commit barrier."""
        from test_ec_backend import Cluster, ec_pool

        from ceph_tpu.msg.messages import ReqId
        from ceph_tpu.osd.ec_transaction import PGTransaction

        pool, profiles = ec_pool(4, 2)
        c = Cluster(pool, profiles)
        agg = EncodeAggregator(window=64)
        for b in c.backends:
            b.encode_aggregator = agg
        primary = c.primary
        real = primary.ec.encode_array
        real_host = primary.ec.encode_array_host

        def boom(data):
            # device AND host-oracle failure: the truly-unrecoverable case
            raise RuntimeError("injected launch failure")

        primary.ec.encode_array = boom
        primary.ec.encode_array_host = boom
        outcomes = []
        try:
            for i in range(2):
                pgt = PGTransaction(f"f{i}").write(0, bytes(pool.stripe_width))
                primary.submit_transaction(
                    pgt,
                    ReqId("cl", i),
                    lambda i=i: outcomes.append(("commit", i)),
                    on_failure=lambda err, i=i: outcomes.append(("fail", i, err)),
                )
            primary.flush_encodes()  # barrier must not throw
        finally:
            primary.ec.encode_array = real
            primary.ec.encode_array_host = real_host
        assert [(o[0], o[1]) for o in outcomes] == [("fail", 0), ("fail", 1)]
        assert all(o[2] < 0 for o in outcomes)  # negative errno convention
        assert not primary.in_flight
        assert not primary._projected
        # the backend recovers: the same objects write fine afterwards
        data = np.random.default_rng(1).integers(
            0, 256, pool.stripe_width, dtype=np.uint8
        ).tobytes()
        c.write("f0", 0, data)
        assert c.read("f0", 0, len(data)) == data

    def test_launch_failure_dooms_later_encoded_writes_same_object(self):
        """A later write on the same object may already be encoded against
        projected state embedding the failed write's bytes — committing it
        would persist a write the client was told failed, so the chain
        abort must doom it too."""
        from test_ec_backend import Cluster, ec_pool

        from ceph_tpu.msg.messages import ReqId
        from ceph_tpu.osd.ec_transaction import PGTransaction

        pool, profiles = ec_pool(4, 2)
        c = Cluster(pool, profiles)
        agg = EncodeAggregator(window=64)
        for b in c.backends:
            b.encode_aggregator = agg
        primary = c.primary
        real = primary.ec.encode_array
        real_host = primary.ec.encode_array_host

        def boom_two_stripes(data):
            if data.shape[0] == 2:  # only W1's 2-stripe group fails
                raise RuntimeError("injected launch failure")
            return real(data)

        def boom_two_stripes_host(data):
            if data.shape[0] == 2:  # the host fallback fails identically
                raise RuntimeError("injected launch failure")
            return real_host(data)

        sw = pool.stripe_width
        outcomes = []
        primary.ec.encode_array = boom_two_stripes
        primary.ec.encode_array_host = boom_two_stripes_host
        try:
            w1 = PGTransaction("fx").write(0, bytes(2 * sw))
            primary.submit_transaction(
                w1, ReqId("cl", 1),
                lambda: outcomes.append(("commit", 1)),
                on_failure=lambda err: outcomes.append(("fail", 1, err)),
            )
            # W1's group launches now and fails (sticky); W2 then encodes
            # into a NEW group that succeeds — only the chain abort at
            # W1's reap can stop W2's commit
            agg.flush()
            w2 = PGTransaction("fx").write(2 * sw, bytes(sw))
            primary.submit_transaction(
                w2, ReqId("cl", 2),
                lambda: outcomes.append(("commit", 2)),
                on_failure=lambda err: outcomes.append(("fail", 2, err)),
            )
            c.pump()
        finally:
            primary.ec.encode_array = real
            primary.ec.encode_array_host = real_host
        assert [(o[0], o[1]) for o in outcomes] == [("fail", 1), ("fail", 2)]
        assert not primary.in_flight and not primary._projected
        # neither write landed: the object does not exist on any shard
        assert primary.object_size("fx") == 0

    def test_stale_rmw_read_cannot_resurrect_doomed_op(self):
        """An op doomed by an earlier same-object encode failure while its
        RMW reads were in flight must stay dead when the read completes —
        not re-encode and persist bytes its client saw fail."""
        from test_ec_backend import Cluster, ec_pool, payload as mk_payload

        from ceph_tpu.msg.messages import ReqId
        from ceph_tpu.osd.ec_transaction import PGTransaction
        from ceph_tpu.osd.osdmap import FLAG_EC_OVERWRITES

        pool, profiles = ec_pool(4, 2, flags=FLAG_EC_OVERWRITES)
        c = Cluster(pool, profiles)
        agg = EncodeAggregator(window=64)
        for b in c.backends:
            b.encode_aggregator = agg
        primary = c.primary
        sw = pool.stripe_width
        base = mk_payload(2 * sw, seed=5)
        c.write("rx", 0, base)  # pre-existing 2-stripe object

        real = primary.ec.encode_array
        real_host = primary.ec.encode_array_host
        armed = [True]

        def boom_once(data):
            if armed[0]:
                raise RuntimeError("injected launch failure")
            return real(data)

        def boom_once_host(data):
            # the host fallback fails the same launch, then disarms: the
            # pair models ONE launch no path can compute
            if armed[0]:
                armed[0] = False
                raise RuntimeError("injected launch failure")
            return real_host(data)

        outcomes = []
        # W1: full-stripe overwrite (no RMW read); stays windowed
        primary.submit_transaction(
            PGTransaction("rx").write(0, bytes(sw)),
            ReqId("cl", 1),
            lambda: outcomes.append(("commit", 1)),
            on_failure=lambda err: outcomes.append(("fail", 1, err)),
        )
        # W2: partial overwrite of stripe 1 -> issues RMW reads (async)
        primary.submit_transaction(
            PGTransaction("rx").write(sw, b"\xAA" * 100),
            ReqId("cl", 2),
            lambda: outcomes.append(("commit", 2)),
            on_failure=lambda err: outcomes.append(("fail", 2, err)),
        )
        primary.ec.encode_array = boom_once
        primary.ec.encode_array_host = boom_once_host
        try:
            agg.flush()  # W1's group launches and fails, sticky
            primary.flush_encodes()  # W1 reap fails -> dooms W2 too
        finally:
            primary.ec.encode_array = real
            primary.ec.encode_array_host = real_host
        assert [(o[0], o[1]) for o in outcomes] == [("fail", 1), ("fail", 2)]
        c.pump()  # delivers W2's stale RMW read replies
        assert [(o[0], o[1]) for o in outcomes] == [("fail", 1), ("fail", 2)]
        assert not primary.in_flight
        # neither overwrite landed: the object still holds the base bytes
        assert c.read("rx", 0, 2 * sw) == base

    def test_failure_preserves_projection_for_dispatched_survivor(self):
        """When a later write's encode fails while an earlier write on the
        same object is dispatched-but-uncommitted, the next write must
        plan against the survivor's size, not the stale on-disk size."""
        from test_ec_backend import Cluster, ec_pool, payload as mk_payload

        from ceph_tpu.msg.messages import ReqId
        from ceph_tpu.osd.ec_transaction import PGTransaction

        pool, profiles = ec_pool(4, 2)
        c = Cluster(pool, profiles)
        agg = EncodeAggregator(window=64)
        for b in c.backends:
            b.encode_aggregator = agg
        primary = c.primary
        sw = pool.stripe_width
        real = primary.ec.encode_array
        real_host = primary.ec.encode_array_host
        armed = [False]

        def boom_when_armed(data):
            if armed[0]:
                raise RuntimeError("injected launch failure")
            return real(data)

        def boom_when_armed_host(data):
            if armed[0]:
                armed[0] = False  # one launch, failed on both paths
                raise RuntimeError("injected launch failure")
            return real_host(data)

        outcomes = []
        d1 = mk_payload(sw, seed=11)
        # W1 commits-in-progress: encode + dispatch sub-writes, but do NOT
        # deliver the commit replies yet (pending_commits stays non-empty)
        primary.submit_transaction(
            PGTransaction("px").write(0, d1),
            ReqId("cl", 1),
            lambda: outcomes.append("commit1"),
        )
        primary.flush_encodes()  # W1 dispatched; replies queued, undelivered
        assert primary.in_flight and not outcomes
        # W2 appends at sw (planned against projection size sw); its
        # launch fails at reap
        primary.ec.encode_array = boom_when_armed
        primary.ec.encode_array_host = boom_when_armed_host
        try:
            primary.submit_transaction(
                PGTransaction("px").write(sw, bytes(sw)),
                ReqId("cl", 2),
                lambda: outcomes.append("commit2"),
                on_failure=lambda err: outcomes.append(("fail2", err)),
            )
            armed[0] = True
            agg.flush()
            primary.flush_encodes()
        finally:
            primary.ec.encode_array = real
            primary.ec.encode_array_host = real_host
        assert ("fail2" in [o[0] if isinstance(o, tuple) else o for o in outcomes])
        # W1 survives: projection still reflects ITS planned size, so W3
        # (an append at sw) plans correctly even before W1's commits land
        assert primary._projected["px"]["size"] == sw
        d3 = mk_payload(sw, seed=12)
        primary.submit_transaction(
            PGTransaction("px").write(sw, d3),
            ReqId("cl", 3),
            lambda: outcomes.append("commit3"),
        )
        c.pump()  # delivers everything: W1 + W3 commit
        assert "commit1" in outcomes and "commit3" in outcomes
        assert c.read("px", 0, 2 * sw) == d1 + d3
