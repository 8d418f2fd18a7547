"""The CLAY encode program's share of its HBM roofline in the traced window.

Least time: the window's full-object encodes, (k+m) chunks of each
stripe (the configuration's reference, perfbench/work.py), at the chip's
peak HBM bytes/s (perfbench/peaks.json).  Device time: the summed time
of the `clay_encode` programs in the trace, each the whole program:
its transposes, pairwise transforms and inner code.  A program without
them gives None."""


def read(run):
    nbytes = run.work.get("encode_bytes")
    if not nbytes or run.trace is None:
        return None
    ns = run.trace.kernel_ns("clay_encode")
    if not ns:
        return None
    return 100.0 * (nbytes / run.peaks["hbm_bytes_per_s"]) / (ns / 1e9)
