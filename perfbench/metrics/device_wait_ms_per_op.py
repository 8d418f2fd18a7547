"""Loop-thread time blocked on the device, in ms per op of the window:
the `offload.dispatch_wait`, `offload.settle_wait` and
`offload.cache_wait` sections (ops/guard.py, stripe/stripe.py), whole,
with the launch work they wait for (perfbench/layers.py)."""

from perfbench.layers import layer_window

WAITS = ("offload.dispatch_wait", "offload.settle_wait", "offload.cache_wait")


def read(run):
    w = layer_window(run)
    if w is None or not w.ops:
        return None
    return sum(w.loop.get(k, {}).get("total_ns", 0) for k in WAITS) / 1e6 / w.ops
