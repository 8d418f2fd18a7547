"""The program's layer sections over a run's window, for the per-layer
metric readers.

The program keeps a table of synchronous layer sections per thread and
per second of `time.perf_counter()` (`ceph_tpu/common/tracer.py`,
`layer_window`).  A reader takes the whole seconds inside the run's
window: the loop thread's table (the harness runs the one asyncio loop
that serves every daemon on the main thread), every thread's table, and
the ops that completed in those seconds.  A program without layer
sections gives None, and so does every reader built on it.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass


@dataclass
class LayerWindow:
    loop: dict  # {section: {calls, total_ns, self_ns, bytes}}, loop thread
    every: dict  # the same over every thread
    seconds: float  # the whole seconds of the window the tables cover
    ops: int  # client ops completed in those seconds


def layer_window(run) -> LayerWindow | None:
    try:
        from ceph_tpu.common.tracer import layer_window as program_window
    except ImportError:
        return None
    lo, hi = run.window
    loop, c_lo, c_hi = program_window(lo, hi, threading.main_thread().ident)
    if c_hi <= c_lo:
        return None
    every, _, _ = program_window(lo, hi)
    ops = sum(1 for o in run.ops if c_lo <= o.done < c_hi)
    return LayerWindow(loop, every, c_hi - c_lo, ops)


def loop_ms_per_op(run, layers: tuple[str, ...], field: str = "self_ns"):
    """Loop-thread time of the sections of `layers` (`<layer>.*`), less
    their crc32c, in ms per op of the window."""
    w = layer_window(run)
    if w is None or not w.ops:
        return None
    ns = sum(v[field] for k, v in w.loop.items()
             if k.split(".", 1)[0] in layers and not k.endswith(".crc32c"))
    return ns / 1e6 / w.ops
