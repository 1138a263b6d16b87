"""Refine kernel: the least time the tick's refine work needs at the
chip's HBM bandwidth, over the device time of the ``climber_refine_topk``
kernel's operations in the trace, in percent.

The least bytes are every distinct candidate record the window's routed
plans select, once per tick, at 4n + 8 bytes (``benchlib.work``); refine
reads memory far more than it computes (about half a flop per byte), so
bandwidth bounds it.  Read in the traced run only; None where the trace
shows no refine kernel."""
from benchlib.work import peaks

KERNEL = "climber_refine_topk"


def read(run):
    if run.trace is None or run.refine_bytes is None:
        return None
    secs = run.trace.family_s.get(KERNEL, 0.0)
    if secs <= 0.0:
        return None
    least = run.refine_bytes() / peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / secs
