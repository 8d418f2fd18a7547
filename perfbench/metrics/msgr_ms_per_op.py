"""Loop-thread self time of the messenger's layer sections (`msgr.encode`:
message encode, frame pack and socket write; `msgr.decode`: frame parse
and crc check, message decode), less crc32c, in ms per op of the window
(perfbench/layers.py)."""

from perfbench.layers import loop_ms_per_op


def read(run):
    return loop_ms_per_op(run, ("msgr",))
