"""bench.sh equivalent — sweep plugins x techniques x k/m grid.

Mirror of /root/reference/qa/workunits/erasure-code/bench.sh:40-57: runs the
benchmark harness over a parameter grid and emits one JSON line per run
(instead of flot JS) so results are machine-readable.

  python -m ceph_tpu.tools.bench_sweep --size 4096 --total-size 1048576
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from . import ec_benchmark

# The five BASELINE.md configs (LRC shape adjusted per the reference's own
# parse_kml constraints, see ec_corpus.py).
BASELINE_CONFIGS = [
    {
        "name": "jerasure_reed_sol_van_k4m2_4KiB",
        "plugin": "jerasure",
        "profile": {"k": "4", "m": "2", "technique": "reed_sol_van"},
        "size": 4 * 4096,
        "workloads": ("encode", "decode"),
    },
    {
        "name": "rs_8_3_cauchy_1MiB",
        "plugin": "tpu",
        "profile": {"k": "8", "m": "3", "technique": "cauchy"},
        "size": 1 << 20,
        # headline config: encode + decode at EVERY erasure count
        # (reference invocation: isa/README:36-47, decode e=1,2,3)
        "workloads": ("encode", "decode"),
        "erasure_counts": (1, 2, 3),
    },
    {
        # "64K stripes in flight" (BASELINE.md config 3): batching depth,
        # so the chunk is small (4 KiB) and the batch is what's measured
        "name": "rs_10_4_bulk_stripes",
        "plugin": "tpu",
        "profile": {"k": "10", "m": "4"},
        "size": 10 * 4096,  # 4 KiB chunks (chunk = size / k)
        "workloads": ("bulk",),
    },
    {
        "name": "clay_8_4_d11_subchunk_repair",
        "plugin": "clay",
        "profile": {"k": "8", "m": "4", "d": "11"},
        "size": 1 << 18,
        "workloads": ("repair",),
    },
    {
        "name": "lrc_12_3_l5_multi_failure",
        "plugin": "lrc",
        "profile": {"k": "12", "m": "3", "l": "5"},
        "size": 1 << 18,
        "workloads": ("encode", "decode"),
    },
]


def run_bulk(ec, size: int, batch: int, iters: int) -> tuple[float, int]:
    """BASELINE config 3: many stripes in flight through the held device
    executable (codec encode_array on a (S, k, L) batch) — the batched
    bulk-rebuild path, not per-object calls.

    Serial-chain methodology (same as bench.py): each launch's input is
    patched with bytes of the previous launch's parity under buffer
    donation, so the loop times back-to-back launches.

    `batch` stripes stay in flight as a QUEUE of chained sub-launches of
    at most 4096 stripes (~160 MiB at 4 KiB chunks): the OSD's pipeline
    submits bounded launches back-to-back, it does not build one 2.5 GB
    batch.
    """
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    k = ec.get_data_chunk_count()
    chunk = ec.get_chunk_size(size)
    sub = min(batch, 4096)
    rounds = -(-batch // sub)  # ceil: never measure fewer stripes than asked
    data = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (sub, k, chunk), dtype=np.uint8)
    )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(d, p):
        n = min(128, chunk)
        patch = (p[:1, :1, :n] ^ jnp.uint8(1)).reshape(1, 1, n)
        d2 = jax.lax.dynamic_update_slice(d, patch, (0, 0, 0))
        return d2, ec.encode_array(d2)

    p = ec.encode_array(data)
    data, p = step(data, p)  # compile + warm
    jax.block_until_ready((data, p))
    t0 = time.perf_counter()
    for _ in range(iters):
        for _ in range(rounds):  # `rounds` launches queue without a sync
            data, p = step(data, p)
    jax.block_until_ready((data, p))
    return time.perf_counter() - t0, sub * rounds * k * chunk * iters


def run_baseline(iterations: int, out=None) -> int:
    import jax

    platform = jax.devices()[0].platform
    # "64K stripes in flight" on real hardware; scaled down off-chip so the
    # CPU sweep stays tractable
    bulk_batch = 65536 if platform == "tpu" else 64

    def emit(rec: dict) -> None:
        line = json.dumps(rec)
        print(line, flush=True)
        if out is not None:
            out.write(line + "\n")
            out.flush()

    for cfg in BASELINE_CONFIGS:
        for workload in cfg["workloads"]:
            erasure_counts = (
                cfg.get("erasure_counts", (cfg.get("erasures", 2),))
                if workload == "decode"
                else (None,)
            )
            argv = ["-p", cfg["plugin"], "-S", str(cfg["size"]),
                    "-i", str(iterations)]
            for kv in cfg["profile"].items():
                argv += ["-P", f"{kv[0]}={kv[1]}"]
            opts = ec_benchmark.build_parser().parse_args(argv)
            try:
                ec = ec_benchmark.make_codec(opts)
            except (Exception, SystemExit) as e:
                emit({"config": cfg["name"], "workload": workload,
                      "platform": platform, "error": str(e)})
                continue
            for nerr in erasure_counts:
                rec = {
                    "config": cfg["name"],
                    "plugin": cfg["plugin"],
                    "profile": cfg["profile"],
                    "workload": workload,
                    "platform": platform,
                }
                try:
                    if workload == "encode":
                        elapsed = ec_benchmark.run_encode(ec, opts)
                        total = iterations * cfg["size"]
                    elif workload == "decode":
                        opts.erasures = min(
                            nerr, ec.get_coding_chunk_count()
                        )
                        rec["erasures"] = opts.erasures
                        elapsed = ec_benchmark.run_decode(ec, opts)
                        total = iterations * cfg["size"]
                    elif workload == "repair":
                        elapsed, bytes_read, bytes_repaired = (
                            ec_benchmark.run_repair(ec, opts)
                        )
                        total = iterations * cfg["size"]
                        rec["bytes_read"] = bytes_read
                        rec["bytes_repaired"] = bytes_repaired
                        rec["read_amplification"] = round(
                            bytes_read / max(1, bytes_repaired), 3
                        )
                    else:  # bulk
                        elapsed, total = run_bulk(
                            ec, cfg["size"], bulk_batch, max(2, iterations // 4)
                        )
                        rec["stripes_in_flight"] = bulk_batch
                    rec["seconds"] = round(elapsed, 6)
                    rec["MBps"] = round(total / max(elapsed, 1e-9) / 1e6, 1)
                except (Exception, SystemExit) as e:
                    # record failures, keep sweeping (run_decode/run_repair
                    # signal content mismatch via SystemExit)
                    rec["error"] = str(e)
                emit(rec)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="bench_sweep", description=__doc__)
    p.add_argument("--size", type=int, default=4096, help="object size per op")
    p.add_argument(
        "--total-size", type=int, default=1 << 20, help="total bytes per config"
    )
    p.add_argument(
        "--plugins", default="tpu,jerasure", help="comma-separated plugin list"
    )
    p.add_argument("--ks", default="2,3,4,6,8,10")
    p.add_argument("--ms", default="1,2,3")
    p.add_argument("--workloads", default="encode,decode")
    p.add_argument(
        "--baseline",
        action="store_true",
        help="run the five BASELINE.md configs instead of the grid",
    )
    p.add_argument("--iterations", type=int, default=8)
    p.add_argument(
        "--out", default="",
        help="also append JSONL to this file (baseline mode only)",
    )
    args = p.parse_args(argv)

    if args.baseline:
        out = open(args.out, "a") if args.out else None
        try:
            return run_baseline(args.iterations, out=out)
        finally:
            if out is not None:
                out.close()

    techniques = {
        "tpu": ["reed_sol_van", "cauchy"],
        "jerasure": ["reed_sol_van", "cauchy_good"],
    }
    iterations = max(1, args.total_size // args.size)
    for plugin in args.plugins.split(","):
        for technique in techniques.get(plugin, [None]):
            for k in (int(x) for x in args.ks.split(",")):
                for m in (int(x) for x in args.ms.split(",")):
                    if m > k:
                        continue
                    for workload in args.workloads.split(","):
                        bench_args = [
                            "-p", plugin,
                            "-P", f"k={k}",
                            "-P", f"m={m}",
                            "-S", str(args.size),
                            "-i", str(iterations),
                            "-w", workload,
                            "-e", str(min(m, 2)),
                        ]
                        if technique:
                            bench_args += ["-P", f"technique={technique}"]
                        parser = ec_benchmark.build_parser()
                        opts = parser.parse_args(bench_args)
                        try:
                            ec = ec_benchmark.make_codec(opts)
                            if workload == "encode":
                                elapsed = ec_benchmark.run_encode(ec, opts)
                            else:
                                elapsed = ec_benchmark.run_decode(ec, opts)
                        except (Exception, SystemExit) as e:  # record, keep sweeping
                            print(
                                json.dumps(
                                    {
                                        "plugin": plugin,
                                        "technique": technique,
                                        "k": k,
                                        "m": m,
                                        "workload": workload,
                                        "error": str(e),
                                    }
                                )
                            )
                            continue
                        total = iterations * args.size
                        print(
                            json.dumps(
                                {
                                    "plugin": plugin,
                                    "technique": technique,
                                    "k": k,
                                    "m": m,
                                    "workload": workload,
                                    "seconds": round(elapsed, 6),
                                    "KiB": total / 1024,
                                    "MBps": round(total / max(elapsed, 1e-9) / 1e6, 1),
                                }
                            )
                        )
    return 0


if __name__ == "__main__":
    from ceph_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
