"""Loop-thread self time of the client's layer sections (`client.*`:
op build and reply in client/objecter.py, striping in rbd/rbd.py), less
crc32c, in ms per op of the window (perfbench/layers.py)."""

from perfbench.layers import loop_ms_per_op


def read(run):
    return loop_ms_per_op(run, ("client",))
