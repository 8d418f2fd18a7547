"""Loop-thread time in crc32c (`<layer>.crc32c` sections, whatever layer
called it: frame crcs, BlueStore block csums, hinfo), in ms per op of the
window (perfbench/layers.py)."""

from perfbench.layers import layer_window


def read(run):
    w = layer_window(run)
    if w is None or not w.ops:
        return None
    ns = sum(v["total_ns"] for k, v in w.loop.items() if k.endswith(".crc32c"))
    return ns / 1e6 / w.ops
