"""Loop-thread self time of the object store's layer sections
(`store.txn`: transaction apply and commit; `store.read`), less crc32c,
in ms per op of the window (perfbench/layers.py)."""

from perfbench.layers import loop_ms_per_op


def read(run):
    return loop_ms_per_op(run, ("store",))
