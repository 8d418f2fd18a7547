"""Headline benchmark: RS(8,3) encode throughput, GB/s per chip.

The TPU analog of the reference harness invocation
`ceph_erasure_code_benchmark -p isa -P k=8 -P m=3 -S 1048576 -i 1000`
(/root/reference/src/erasure-code/isa/README:36-47; harness at
src/test/erasure-code/ceph_erasure_code_benchmark.cc): each "object" is a
1 MiB stripe split into eight 128 KiB data chunks; throughput counts input
object bytes per second of encode, exactly like the harness's
`iterations * size / elapsed`.  Stripes are batched and resident in HBM —
the codec's deep-batching design (SURVEY.md §7 step 3) that replaces the
reference's per-stripe CPU loop (src/osd/ECUtil.cc:139).

The measured function is the SHIPPING path: the registered `tpu` plugin's
`encode_array` (the same cached-coder dispatch `encode_chunks` uses), which
on a TPU backend runs the fused Pallas kernel (ceph_tpu/ops/pallas_gf.py).
Before timing, every stage checks its device bytes against the host
oracle — bytes first, then speed.

Runs in one process, on the TPU only.  Without a chip it exits 3 with no
metric line; a byte mismatch exits 4; any other failure raises.  On
success it prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "GB/s", "vs_baseline": N, ...}
vs_baseline is the ratio against the 40 GB/s/chip north-star target
(BASELINE.json).
"""

import json
import os
import sys
import time

NORTH_STAR_GBPS = 40.0


def measure(clog) -> dict:
    """Every stage, in this process; returns the merged result dict."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    got = dev.platform
    clog(f"backend up: {len(jax.devices())} x {got} ({dev.device_kind})")
    if got != "tpu":
        clog(f"no TPU (backend is {got}): nothing to measure")
        sys.exit(3)

    from ceph_tpu.codec.registry import instance
    from ceph_tpu.gf import gf_matmul, isa_rs_vandermonde_matrix

    k, m = 8, 3
    chunk = 128 * 1024  # 1 MiB object / 8 data chunks
    # 256 stripes of 1 MiB per launch (256 MiB of input, resident in
    # HBM): deep enough to amortize the per-launch fixed cost
    batch = 256
    iters = 40

    # The SHIPPING path: the registered `tpu` plugin's device encode — the
    # same dispatch encode_chunks uses (on TPU backends the cached
    # _DeviceCoder runs the fused Pallas kernel).
    clog("building codec via plugin registry")
    ec = instance().factory("tpu", {"k": str(k), "m": str(m)})
    encode_fn = ec.encode_array
    rng = np.random.default_rng(0)
    gfm = isa_rs_vandermonde_matrix(k, m)[k:]
    parity_checked = False

    # Small compile probe before the measured batch: bytes of the same
    # kernel family on a seconds-scale shape, checked against the oracle
    t_probe = time.perf_counter()
    # 64 KiB: the smallest shape that takes the bulk kernel path (packed
    # plane / Pallas), so the probe compiles the same kernel family the
    # tuned batch will
    probe_in = rng.integers(0, 256, (1, k, 8192), dtype=np.uint8)
    probe_par = np.asarray(encode_fn(probe_in))
    if not np.array_equal(probe_par[0], gf_matmul(gfm, probe_in[0])):
        clog("PROBE PARITY MISMATCH vs host oracle")
        sys.exit(4)
    probe_s = time.perf_counter() - t_probe
    clog(f"warm-up probe OK ({probe_s:.2f}s)")

    # Serial-chain methodology: each launch's input depends on the previous
    # launch's parity (a 128-byte patch, updated in place via donation), so
    # the measured loop is back-to-back encodes, never overlapped ones.
    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(d, p):
        patch = (p[:1, :1, :128] ^ jnp.uint8(1)).reshape(1, 1, 128)
        d2 = jax.lax.dynamic_update_slice(d, patch, (0, 0, 0))
        return d2, encode_fn(d2)

    def run_chain(batch: int, n: int) -> float:
        """GB/s (input bytes) over n chained launches at this depth."""
        nonlocal parity_checked
        host = rng.integers(0, 256, (batch, k, chunk), dtype=np.uint8)
        data = jnp.asarray(host, dtype=jnp.uint8)
        # zeros seed: step only reads 128 bytes of p for the patch, and
        # the warm call below regenerates real parity
        p = jnp.zeros((batch, m, chunk), jnp.uint8)
        data, p = step(data, p)  # compile + warm
        jax.block_until_ready((data, p))
        if not parity_checked:
            # On-chip byte check ON THE MEASUREMENT SHAPE (bytes first,
            # then speed).  The warm step patched stripe 0's first 128
            # bytes with p^1 = 0x01.
            stripe0 = host[0].copy()
            stripe0[0, :128] = 1
            want = gf_matmul(gfm, stripe0)
            got = np.asarray(p[0])
            if not np.array_equal(got, want):
                clog("PARITY MISMATCH vs host oracle")
                sys.exit(4)
            clog("on-chip parity vs host oracle OK")
            parity_checked = True
        t0 = time.perf_counter()
        for _ in range(n):
            data, p = step(data, p)
        jax.block_until_ready((data, p))
        elapsed = time.perf_counter() - t0
        del data, p
        return batch * k * chunk * n / elapsed / 1e9

    def _run_multichip(mc_base_batch: int) -> None:
        """Multichip stage (ISSUE 6): shard the aggregated launch over
        the device mesh, verify bytes through the SHIPPING sharded
        dispatch, and measure AGGREGATE GB/s alongside the per-chip
        number.  Returns the `multichip` slice of the result."""
        n_dev = len(jax.devices())
        mc: dict = {"devices": n_dev}
        from ceph_tpu.ops.dispatch import SHARDED_LAUNCHES
        from ceph_tpu.parallel import dispatch as shard_dispatch
        from ceph_tpu.parallel.sharded import _stripe_sharding

        # Bytes first, through the SHIPPING sharded dispatch: an
        # eager encode_array above the shard threshold must register
        # one sharded launch and match the host oracle.
        shard_dispatch.configure(min_batch=n_dev, devices=0)
        mc_probe = rng.integers(0, 256, (2 * n_dev, k, 8192), dtype=np.uint8)
        s0 = SHARDED_LAUNCHES.snapshot()["launches"]
        mc_par = np.asarray(encode_fn(mc_probe))
        if SHARDED_LAUNCHES.snapshot()["launches"] != s0 + 1:
            raise RuntimeError("multichip: dispatch did not shard")
        if not np.array_equal(mc_par[0], gf_matmul(gfm, mc_probe[0])):
            clog("MULTICHIP PARITY MISMATCH vs host oracle")
            sys.exit(4)
        clog(f"multichip probe OK: 1 sharded launch over {n_dev} devices")

        # Aggregate throughput: the same serial-chain methodology as
        # the per-chip number, but the arrays live stripe-sharded
        # over the mesh — each device runs the per-chip workload
        # concurrently, so input bytes/elapsed is honest aggregate.
        mc_batch = mc_base_batch * n_dev
        mesh = shard_dispatch.shard_mesh(mc_batch)  # the locked public path
        if mesh is None:
            raise RuntimeError("multichip: shard policy returned no mesh")
        sharding = _stripe_sharding(mesh)
        mc_host = rng.integers(0, 256, (mc_batch, k, chunk), dtype=np.uint8)
        mc_data = jax.device_put(mc_host, sharding)
        mc_p = jax.device_put(
            np.zeros((mc_batch, m, chunk), np.uint8), sharding
        )
        mc_data, mc_p = step(mc_data, mc_p)  # compile + warm, sharded
        jax.block_until_ready((mc_data, mc_p))
        mc_iters = max(4, iters // 2)
        clog(f"multichip measuring: batch={mc_batch} iters={mc_iters} "
             f"over {n_dev} devices")
        t0 = time.perf_counter()
        for _ in range(mc_iters):
            mc_data, mc_p = step(mc_data, mc_p)
        jax.block_until_ready((mc_data, mc_p))
        elapsed = time.perf_counter() - t0
        del mc_data, mc_p
        mc["encode_gbps"] = mc_batch * k * chunk * mc_iters / elapsed / 1e9
        mc["batch"] = mc_batch
        clog(f"multichip encode: {mc['encode_gbps']:.3f} GB/s aggregate")

        # Decode twin: chained sharded decode at the same geometry.
        erasures = [0, 5, 9]
        idx = ec.decode_index(erasures)
        d_host = rng.integers(
            0, 256, (mc_batch, k, chunk), dtype=np.uint8
        )
        d_data = jax.device_put(d_host, sharding)
        surv = jnp.concatenate(
            [d_data, encode_fn(d_data)], axis=1)[:, idx, :]
        del d_data
        r = jax.device_put(
            np.zeros((mc_batch, len(erasures), chunk), np.uint8),
            sharding,
        )

        @functools.partial(jax.jit, donate_argnums=(0,))
        def mc_dstep(s, r):
            patch = (r[:1, :1, :128] ^ jnp.uint8(1)).reshape(1, 1, 128)
            s2 = jax.lax.dynamic_update_slice(s, patch, (0, 0, 0))
            return s2, ec.decode_array(erasures, s2)

        surv, r = mc_dstep(surv, r)  # compile + warm
        jax.block_until_ready((surv, r))
        t0 = time.perf_counter()
        for _ in range(mc_iters):
            surv, r = mc_dstep(surv, r)
        jax.block_until_ready((surv, r))
        elapsed = time.perf_counter() - t0
        del surv, r
        mc["decode_gbps"] = (
            mc_batch * k * chunk * mc_iters / elapsed / 1e9
        )
        clog(f"multichip decode: {mc['decode_gbps']:.3f} GB/s aggregate")
        return mc

    clog(f"measuring: batch={batch} iters={iters}")
    gbps = run_chain(batch, iters)
    clog(f"done: {gbps:.3f} GB/s at batch={batch}")

    # Per-stage breakdown (one un-chained encode, stages serialized with
    # block_until_ready): attributes the headline to H2D staging, kernel,
    # or D2H readback instead of a single number, at the probe shape
    # (already compiled during warm-up).
    stage_shape = (1, k, 8192)
    clog(f"sampling per-stage breakdown (h2d/kernel/d2h) at {stage_shape}")
    stage_in = rng.integers(0, 256, stage_shape, dtype=np.uint8)
    # warm the standalone-encode compile at this shape (the measured
    # chain compiled it fused inside `step`) so it is steady-state
    jax.block_until_ready(encode_fn(jax.device_put(stage_in)))
    t0 = time.perf_counter()
    stage_dev = jax.block_until_ready(jax.device_put(stage_in))
    t1 = time.perf_counter()
    stage_par = jax.block_until_ready(encode_fn(stage_dev))
    t2 = time.perf_counter()
    _ = np.asarray(stage_par)
    t3 = time.perf_counter()
    stages = {
        "h2d_s": round(t1 - t0, 6),
        "kernel_s": round(t2 - t1, 6),
        "d2h_s": round(t3 - t2, 6),
        "shape": list(stage_shape),
    }
    clog(f"stages: {stages}")
    # Decode stage: same RS(8,3) geometry, three erasures (two data + one
    # parity) — the recovery/degraded-read-shaped workload (ISSUE 5).
    # Bytes first: the probe reconstruction is checked against the host GF
    # oracle before anything is timed.  Throughput counts survivor input
    # bytes per second, symmetrical with the encode metric.
    erasures = [0, 5, 9]
    idx = ec.decode_index(erasures)
    clog(f"decode probe: erasures {erasures}, survivors {idx}")
    # probe shape reuses the encode probe's compiled parity kernel;
    # only the decode coder itself compiles here (seconds-scale)
    probe_full = np.concatenate([probe_in[0], gf_matmul(gfm, probe_in[0])])
    probe_surv = jnp.concatenate(
        [jnp.asarray(probe_in), encode_fn(jnp.asarray(probe_in))], axis=1
    )[:, idx, :]
    probe_rec = np.asarray(ec.decode_array(erasures, probe_surv))
    if not np.array_equal(probe_rec[0], probe_full[erasures]):
        clog("DECODE PROBE MISMATCH vs host oracle")
        sys.exit(4)
    clog("decode probe vs host oracle OK")

    # Serial-chain methodology, mirroring the encode loop: each
    # launch's survivors depend on the previous reconstruction.
    @functools.partial(jax.jit, donate_argnums=(0,))
    def dstep(s, r):
        patch = (r[:1, :1, :128] ^ jnp.uint8(1)).reshape(1, 1, 128)
        s2 = jax.lax.dynamic_update_slice(s, patch, (0, 0, 0))
        return s2, ec.decode_array(erasures, s2)

    clog(f"decode warm-up at batch={batch}")
    d_host = rng.integers(0, 256, (batch, k, chunk), dtype=np.uint8)
    d_data = jnp.asarray(d_host)
    surv = jnp.concatenate([d_data, encode_fn(d_data)], axis=1)[:, idx, :]
    del d_data
    r = jnp.zeros((batch, len(erasures), chunk), jnp.uint8)
    surv, r = dstep(surv, r)  # compile + warm
    jax.block_until_ready((surv, r))
    d_iters = iters
    clog(f"decode measuring: batch={batch} iters={d_iters}")
    t0 = time.perf_counter()
    for _ in range(d_iters):
        surv, r = dstep(surv, r)
    jax.block_until_ready((surv, r))
    d_elapsed = time.perf_counter() - t0
    d_gbps = batch * k * chunk * d_iters / d_elapsed / 1e9
    del surv, r
    clog(f"decode done: {d_gbps:.3f} GB/s at batch={batch}")
    decode_result = {"gbps": d_gbps, "batch": batch, "parity_ok": True}
    # per-stage h2d/kernel/d2h breakdown for the decode launch,
    # guarded like the encode one: losing the breakdown must never
    # lose the decode (or encode) headline
    # host-side copy staged BEFORE the timing window, so h2d_s
    # times only the put (symmetry with the encode breakdown)
    host_surv = np.asarray(probe_surv)
    jax.block_until_ready(ec.decode_array(erasures, jax.device_put(probe_surv)))
    t0 = time.perf_counter()
    d_dev = jax.block_until_ready(jax.device_put(host_surv))
    t1 = time.perf_counter()
    d_rec = jax.block_until_ready(ec.decode_array(erasures, d_dev))
    t2 = time.perf_counter()
    _ = np.asarray(d_rec)
    t3 = time.perf_counter()
    decode_result["stages"] = {
        "h2d_s": round(t1 - t0, 6),
        "kernel_s": round(t2 - t1, 6),
        "d2h_s": round(t3 - t2, 6),
        "shape": list(probe_surv.shape),
    }
    clog(f"decode stages: {decode_result['stages']}")

    # Verify stage (ISSUE 9): the deep-scrub compare-only kernel at the
    # same RS(8,3) geometry — full (batch, k+m, chunk) codewords in, a
    # per-stripe mismatch bitmap out.  Bytes first: the probe bitmap is
    # checked against the pure-numpy host oracle (clean codewords AND a
    # corrupted shard) before anything is timed.  Throughput counts full
    # codeword input bytes per second — what a continuous background
    # integrity sweep actually pushes through the chip.
    clog("verify probe: bitmap vs host oracle")
    probe_cw = np.concatenate(
        [probe_in, np.asarray(encode_fn(jnp.asarray(probe_in)))], axis=1
    )
    probe_bm = np.asarray(ec.verify_array(probe_cw))
    if not np.array_equal(probe_bm, ec.verify_array_host(probe_cw)):
        clog("VERIFY PROBE MISMATCH vs host oracle")
        sys.exit(4)
    if probe_bm.any():
        clog("VERIFY PROBE: clean codeword flagged inconsistent")
        sys.exit(4)
    bad_cw = probe_cw.copy()
    bad_cw[0, 3, 11] ^= 0x5A  # silent single-shard corruption
    bad_bm = np.asarray(ec.verify_array(bad_cw))
    if not np.array_equal(bad_bm, ec.verify_array_host(bad_cw)) or not bad_bm[0]:
        clog("VERIFY PROBE: corrupted shard not flagged")
        sys.exit(4)
    clog("verify probe vs host oracle OK")

    # Serial-chain methodology, mirroring the encode/decode loops:
    # each launch's codeword depends on the previous bitmap, so
    # runtime caching cannot elide repeated launches.
    @functools.partial(jax.jit, donate_argnums=(0,))
    def vstep(cw, bm):
        patch = (cw[:1, :1, :128] ^ bm[0] ^ jnp.uint8(1)).reshape(1, 1, 128)
        cw2 = jax.lax.dynamic_update_slice(cw, patch, (0, 0, 0))
        return cw2, ec.verify_array(cw2)

    clog(f"verify warm-up at batch={batch}")
    v_host = rng.integers(0, 256, (batch, k, chunk), dtype=np.uint8)
    v_data = jnp.asarray(v_host)
    cw = jnp.concatenate([v_data, encode_fn(v_data)], axis=1)
    del v_data
    bm = jnp.zeros((batch,), jnp.uint8)
    cw, bm = vstep(cw, bm)  # compile + warm
    jax.block_until_ready((cw, bm))
    clog(f"verify measuring: batch={batch} iters={iters}")
    t0 = time.perf_counter()
    for _ in range(iters):
        cw, bm = vstep(cw, bm)
    jax.block_until_ready((cw, bm))
    v_elapsed = time.perf_counter() - t0
    v_gbps = batch * (k + m) * chunk * iters / v_elapsed / 1e9
    del cw, bm
    clog(f"verify done: {v_gbps:.3f} GB/s at batch={batch}")
    verify_result = {"gbps": v_gbps, "batch": batch, "bitmap_ok": True}

    # Pipeline stage (ISSUE 11): steady-state OVERLAPPED throughput at
    # in-flight depth 1/2/4.  Unlike the serial chain (which keeps its
    # round-over-round comparability above and never re-uploads inside
    # the loop), every iteration here pays the full end-to-end launch
    # path — fresh host bytes, H2D, kernel, bounded-ring reap — exactly
    # the aggregator's production shape; depth d lets launch N+1's H2D
    # run under launch N's kernel.
    p_iters = max(8, iters)
    hosts = [
        rng.integers(0, 256, (batch, k, chunk), dtype=np.uint8)
        for _ in range(4)
    ]

    # HBM ledger (ISSUE 13): the staged inputs and in-flight parity
    # are tracked in the `scratch` pool, and the per-depth PEAK is
    # folded into the JSON — bench rounds correlate throughput
    # against the memory headroom each depth costs, the number that
    # decides how far ec_tpu_pipeline_depth can be pushed
    from ceph_tpu.common.mempool import ledger as hbm_ledger
    from ceph_tpu.common.mempool import track_buffer

    hbm = hbm_ledger()

    def run_pipeline(depth: int, n: int) -> float:
        inflight = []
        # warm: one launch per slot buffer (compile already paid)
        for s in range(depth):
            jax.block_until_ready(encode_fn(jax.device_put(hosts[s])))
        t0 = time.perf_counter()
        for i in range(n):
            h = hosts[i % depth]
            par = encode_fn(track_buffer(jax.device_put(h), "scratch"))
            inflight.append(track_buffer(par, "scratch"))
            if len(inflight) >= depth:
                inflight.pop(0).block_until_ready()
        while inflight:
            inflight.pop(0).block_until_ready()
        elapsed = time.perf_counter() - t0
        return batch * k * chunk * n / elapsed / 1e9

    run_pipeline(1, 2)  # warm the eager-dispatch path end to end
    depths = {}
    hbm_peaks = {}
    for depth in (1, 2, 4):
        hbm.reset_peaks()
        depths[depth] = run_pipeline(depth, p_iters)
        hbm_peaks[str(depth)] = hbm.peak_total_bytes()
        clog(
            f"pipeline depth={depth}: {depths[depth]:.3f} GB/s "
            f"(hbm peak {hbm_peaks[str(depth)]} B)"
        )
    best_depth = max(depths, key=depths.get)
    overlap = max(0.0, 1.0 - depths[1] / depths[best_depth])
    pipeline_result = {
        "depths": {str(d): round(g, 3) for d, g in depths.items()},
        "best_depth": best_depth,
        "gbps": depths[best_depth],
        "overlap_fraction": round(overlap, 4),
        "batch": batch,
        "hbm_peak_bytes": hbm_peaks,
    }
    clog(
        f"pipeline best: depth={best_depth} "
        f"{depths[best_depth]:.3f} GB/s (overlap {overlap:.2%})"
    )
    # Device-cache witness (ISSUE 11 acceptance): a chunk served
    # from the device-resident cache must skip the H2D leg — the
    # flight record of the hit carries d2h only, h2d_s == 0.
    from ceph_tpu.ops.device_cache import DeviceChunkCache
    from ceph_tpu.ops.flight_recorder import flight_recorder

    cc = DeviceChunkCache(max_bytes=8 << 20)
    chunk_bytes = rng.integers(0, 256, 64 * 1024, dtype=np.uint8)
    assert cc.put("bench/obj", 0, 1, chunk_bytes)
    served = cc.fetch_many("bench/obj", [0], 1, length=chunk_bytes.nbytes)
    assert served is not None and np.array_equal(
        served[0], chunk_bytes
    ), "device-cache hit returned wrong bytes"
    hit_recs = [
        r for r in flight_recorder().records()
        if r["flags"].get("cache_hit")
    ]
    assert hit_recs and hit_recs[-1]["h2d_s"] == 0.0, (
        "cache-hit flight record must carry no H2D span"
    )
    pipeline_result["device_cache"] = {
        "hit_skipped_h2d": True,
        "d2h_s": round(hit_recs[-1]["d2h_s"], 6),
        **cc.perf_dump(),
    }

    # Checksum stage (ISSUE 20): BlueStore per-block crc32c as packed
    # bit-matrix matmuls through the offload runtime's device kernel.
    # Bytes first: the probe digests are checked against utils/crc32c
    # itself (the host oracle the fallback path IS) before anything is
    # timed.  Every round pays a fresh H2D + launch.
    CS_BLOCK = 4096
    cs_batch = 4096  # blocks per launch
    from ceph_tpu.ops.checksum_offload import (
        crc32c_device,
        crc32c_host_rows,
    )

    clog("csum probe: device digests vs utils/crc32c host oracle")
    cs_probe = rng.integers(0, 256, (64, CS_BLOCK), dtype=np.uint8)
    if not np.array_equal(
        np.asarray(crc32c_device(cs_probe)), crc32c_host_rows(cs_probe)
    ):
        clog("CSUM PROBE MISMATCH vs utils/crc32c host oracle")
        sys.exit(4)
    # ragged tail length too: compressed stored forms are not
    # BLOCK-sized, and the matrix cache must be right for every L
    cs_tail = rng.integers(0, 256, (16, 1000), dtype=np.uint8)
    if not np.array_equal(
        np.asarray(crc32c_device(cs_tail)), crc32c_host_rows(cs_tail)
    ):
        clog("CSUM PROBE MISMATCH at ragged tail length")
        sys.exit(4)
    clog("csum probe vs host oracle OK")

    cs_blocks = rng.integers(
        0, 256, (cs_batch, CS_BLOCK), dtype=np.uint8
    )
    crcs = crc32c_device(cs_blocks)
    jax.block_until_ready(crcs)
    clog(f"csum measuring: blocks={cs_batch} iters={iters}")
    t0 = time.perf_counter()
    for i in range(iters):
        cs_blocks[0, :4] ^= np.uint8(i + 1)  # fresh bytes each round
        crcs = crc32c_device(cs_blocks)
    jax.block_until_ready(crcs)
    cs_elapsed = time.perf_counter() - t0
    cs_gbps = cs_batch * CS_BLOCK * iters / cs_elapsed / 1e9
    clog(f"csum done: {cs_gbps:.3f} GB/s at blocks={cs_batch}")
    csum_result = {
        "gbps": cs_gbps,
        "blocks": cs_batch,
        "block_bytes": CS_BLOCK,
        "digest_ok": True,
    }

    # Write-path offload stage (ISSUE 20): the full offloaded BlueStore
    # large-write device work — the compressor's byte-plane transpose +
    # zero-run-elision transform AND the per-block crc32c — over the
    # same block batch per round.  Probe checks the device transform
    # byte-identical to the host transform (the fallback IS the host
    # transform) before timing; throughput counts raw input bytes once.
    from ceph_tpu.compressor.device import (
        transform_rows,
        transform_rows_device,
    )

    clog("compress probe: device transform vs host oracle")
    off_probe = rng.integers(0, 256, (32, CS_BLOCK), dtype=np.uint8)
    off_probe[:, ::2] = 0  # zero-heavy planes: elision has work to do
    if not np.array_equal(
        np.asarray(transform_rows_device(off_probe)),
        transform_rows(off_probe),
    ):
        clog("COMPRESS PROBE MISMATCH vs host transform oracle")
        sys.exit(4)
    clog("compress probe vs host oracle OK")

    off_blocks = rng.integers(
        0, 256, (cs_batch, CS_BLOCK), dtype=np.uint8
    )
    off_blocks[:, 1::2] = 0
    t = transform_rows_device(off_blocks)
    c = crc32c_device(off_blocks)
    jax.block_until_ready((t, c))
    clog(f"offload measuring: blocks={cs_batch} iters={iters}")
    t0 = time.perf_counter()
    for i in range(iters):
        t = transform_rows_device(off_blocks)
        c = crc32c_device(off_blocks)
    jax.block_until_ready((t, c))
    off_elapsed = time.perf_counter() - t0
    off_gbps = cs_batch * CS_BLOCK * iters / off_elapsed / 1e9
    del t, c
    clog(f"offload done: {off_gbps:.3f} GB/s at blocks={cs_batch}")
    offload_result = {
        "gbps": off_gbps,
        "blocks": cs_batch,
        "block_bytes": CS_BLOCK,
        "transform_ok": True,
    }

    result = {
        "platform": got,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "gbps": gbps,
        "batch": batch,
        "parity_ok": True,
        "probe_s": round(probe_s, 3),
    }
    # a number measured while launches fell back to the host oracle
    # (ISSUE 7 device guard) is not the device's: refuse it
    from ceph_tpu.ops import dispatch as ec_dispatch
    from ceph_tpu.ops.guard import device_guard

    fallbacks = ec_dispatch.FALLBACK_LAUNCHES.snapshot()["launches"]
    if device_guard().degraded or fallbacks:
        raise RuntimeError(
            f"{fallbacks} launches fell back to the host "
            f"(guard degraded: {device_guard().degraded})"
        )
    result.update(
        decode=decode_result, verify=verify_result,
        pipeline=pipeline_result, csum=csum_result, offload=offload_result,
        stages=stages,
    )
    # Flight-recorder summary (ISSUE 8): launch count, mean queue-wait,
    # occupancy over the run.  Bench encodes run OUTSIDE the
    # aggregators, so these are span-less dispatch-shape witnesses
    # (occupancy 0 here is expected); the aggregated data-path numbers
    # come from the OSD asok dump_flight / chaos report instead.
    from ceph_tpu.ops.flight_recorder import flight_recorder

    result["flight"] = flight_recorder().summary()
    if len(jax.devices()) > 1:
        result["multichip"] = _run_multichip(batch)
    return result


def main() -> None:
    from ceph_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    def clog(msg: str) -> None:
        print(f"[bench] {time.strftime('%H:%M:%S')} {msg}",
              file=sys.stderr, flush=True)

    result = measure(clog)

    gbps = result["gbps"]
    out = {
        "metric": "rs_8_3_encode_GBps_per_chip",
        "value": round(gbps, 3),
        "unit": "GB/s",
        "vs_baseline": round(gbps / NORTH_STAR_GBPS, 4),
        "platform": result["platform"],
        "device": {
            "platform": result["platform"],
            "kind": result["device_kind"],
            "count": result["device_count"],
        },
    }
    # decode twin metric rides the same line (the driver parses one JSON
    # object): survivor-input GB/s of the recovery-shaped RS(8,3) decode
    if "decode" in result:
        d = result["decode"]
        out["decode"] = {
            "metric": "rs_8_3_decode_GBps_per_chip",
            "value": round(d["gbps"], 3),
            "unit": "GB/s",
            "vs_encode": round(d["gbps"] / gbps, 4) if gbps else 0,
        }
        if "stages" in d:
            out["decode"]["stages"] = d["stages"]
    # verify triplet metric (ISSUE 9): full-codeword GB/s of the
    # deep-scrub compare-only RS(8,3) kernel — the device-speed ceiling
    # of continuous background integrity checking
    if "verify" in result:
        v = result["verify"]
        out["verify"] = {
            "metric": "rs_8_3_verify_GBps_per_chip",
            "value": round(v["gbps"], 3),
            "unit": "GB/s",
            "vs_encode": round(v["gbps"] / gbps, 4) if gbps else 0,
        }
    # pipelined metric (ISSUE 11): steady-state overlapped end-to-end
    # throughput at the best in-flight depth, alongside (never
    # replacing) the serial-chain headline, plus the overlap fraction
    # and the device-cache skipped-H2D witness
    if "pipeline" in result:
        p = result["pipeline"]
        out["pipelined"] = {
            "metric": "rs_8_3_encode_GBps_per_chip_pipelined",
            "value": round(p["gbps"], 3),
            "unit": "GB/s",
            "best_depth": p["best_depth"],
            "depths": p["depths"],
            "overlap_fraction": p["overlap_fraction"],
            "vs_serial": round(p["gbps"] / gbps, 4) if gbps else 0,
        }
        if "hbm_peak_bytes" in p:
            # per-depth HBM high-water mark (ISSUE 13): throughput vs
            # memory headroom in one place, per bench round
            out["pipelined"]["hbm_peak_bytes"] = p["hbm_peak_bytes"]
        if "device_cache" in p:
            out["pipelined"]["device_cache"] = p["device_cache"]
    # write-path offload metrics (ISSUE 20, same {metric, value} sub-
    # object shape): device crc32c GB/s and the fused compress+csum
    # write-path GB/s, both probe-checked byte-identical to their host
    # oracles before timing
    if "csum" in result:
        c = result["csum"]
        out["csum"] = {
            "metric": "bluestore_csum_GBps_per_chip",
            "value": round(c["gbps"], 3),
            "unit": "GB/s",
            "blocks": c["blocks"],
            "block_bytes": c["block_bytes"],
        }
    if "offload" in result:
        off = result["offload"]
        out["offload"] = {
            "metric": "write_path_offload_GBps",
            "value": round(off["gbps"], 3),
            "unit": "GB/s",
            "blocks": off["blocks"],
            "block_bytes": off["block_bytes"],
        }
    # multichip stage (ISSUE 6): aggregate GB/s of the mesh-sharded
    # launch path, alongside (never replacing) the per-chip metrics
    if "multichip" in result:
        m = result["multichip"]
        mc_out = {"devices": m.get("devices", 0)}
        if "encode_gbps" in m:
            mc_out["metric"] = "rs_8_3_encode_GBps_aggregate"
            mc_out["value"] = round(m["encode_gbps"], 3)
            mc_out["unit"] = "GB/s"
            mc_out["vs_per_chip"] = (
                round(m["encode_gbps"] / gbps, 4) if gbps else 0
            )
        if "decode_gbps" in m:
            mc_out["decode"] = {
                "metric": "rs_8_3_decode_GBps_aggregate",
                "value": round(m["decode_gbps"], 3),
                "unit": "GB/s",
            }
        mc_out["batch"] = m["batch"]
        out["multichip"] = mc_out
    if "stages" in result:
        out["stages"] = result["stages"]
    if "probe_s" in result:
        out["probe_s"] = result["probe_s"]
    if "flight" in result:
        # flight-recorder summary of the run (ISSUE 8):
        # launch count, mean queue-wait, occupancy — the bench
        # trajectory tracks device utilization alongside GB/s
        out["flight"] = result["flight"]
    if "trace" in result:
        out["trace"] = result["trace"]
    # chaos-harness metrics (tools/chaos.py --out): fold chaos_p99_ms +
    # recovery_occupancy into the bench line so the PROGRESS trajectory
    # tracks them alongside GB/s (ROADMAP item 4)
    chaos_path = os.environ.get("BENCH_CHAOS_JSON", "")
    if chaos_path and os.path.exists(chaos_path):
        try:
            with open(chaos_path) as f:
                chaos = json.load(f)
            out["chaos"] = {
                k: chaos[k]
                for k in (
                    "chaos_p99_ms", "recovery_occupancy", "converged",
                    # ISSUE 10 workload-attribution keys: the SLO burn
                    # rate under mixed load, per-pool windowed p99, and
                    # the trace-sampling verdicts (budget adherence)
                    "slo_worst_burn_rate", "pool_p99_ms", "trace_sampling",
                )
                if k in chaos
            }
        except (OSError, json.JSONDecodeError) as e:
            clog(f"ignoring unreadable BENCH_CHAOS_JSON: {e!r}")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
