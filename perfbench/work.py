"""Logical work of the window: the bytes each kernel has to move.

Counted by the benchmark from the ops it issued and from the program's
launch counters, never from the kernels' own accounting.  The bytes a
stripe's encode or decode moves come from the configuration's shard
reference (`Cell.ref`, see perfbench/reference.py), so that each erasure
code counts its own chunks.  A kernel's roofline share is the least time
these bytes take at the chip's peak HBM bandwidth, over the kernel's
summed device time.
"""

from __future__ import annotations


def stripes_per_object(ref, cfg: dict, nbytes: int) -> int:
    return -(-nbytes // ref.stripe_width(cfg))


def window_work(cell, ops, counters: dict) -> dict:
    """Bytes of the window's encodes and decodes, for the rooflines.

    Encodes: every acknowledged full-object write of an object-store
    mix is one encode of all its stripes (nothing can serve a write
    without one).  Decodes: the decode launches' stripes, one erasure
    each (one OSD down), because a degraded read whose rebuilt chunk is
    still in the device chunk cache runs no kernel."""
    ref, cfg, tr = cell.ref, cell.cfg, cell.traffic
    out = {"encode_bytes": None, "decode_bytes": None}
    full = cfg["api"] == "rados" and tr["op"] == "write"
    if full:
        done = sum(1 for o in ops if o.ok)
        out["encode_bytes"] = ref.encode_bytes(
            cfg, done * stripes_per_object(ref, cfg, int(tr["size"])))
    if tr.get("down_osds", 0) == 1 and tr["op"] == "read":
        out["decode_bytes"] = ref.decode_bytes(cfg, counters["decode_stripes"], 1)
    return out
