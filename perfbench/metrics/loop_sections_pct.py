"""Share of the loop thread's wall time in the window that its layer
sections cover (the sum of their self times): how much of the loop the
per-layer split accounts for.  The rest is asyncio, code outside any
section, and the harness's own work (perfbench/layers.py)."""

from perfbench.layers import layer_window


def read(run):
    w = layer_window(run)
    if w is None:
        return None
    return 100.0 * sum(v["self_ns"] for v in w.loop.values()) / (w.seconds * 1e9)
