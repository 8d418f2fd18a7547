"""Loop-thread self time of the OSD's and ECBackend's layer sections
(`osd.dispatch`, `osd.op`, `osd.pg`, `osd.reply`; `ec.write`, `ec.read`,
`ec.sub_op`, `ec.reap`, `ec.recovery`), less crc32c, in ms per op of the
window (perfbench/layers.py)."""

from perfbench.layers import loop_ms_per_op


def read(run):
    return loop_ms_per_op(run, ("osd", "ec"))
