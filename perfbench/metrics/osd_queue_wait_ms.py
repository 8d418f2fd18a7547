"""Mean time a client op waited in an OSD's op scheduler, from
`sched.enqueue` to the start of its run: the `osd.queue_wait` counter of
the window (osd/osd.py; perfbench/layers.py), in ms."""

from perfbench.layers import layer_window


def read(run):
    w = layer_window(run)
    if w is None:
        return None
    q = w.every.get("osd.queue_wait")
    if not q or not q["calls"]:
        return None
    return q["total_ns"] / 1e6 / q["calls"]
