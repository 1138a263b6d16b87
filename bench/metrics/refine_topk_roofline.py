"""Refine kernel: the least time the tick's refine work needs at the
chip's HBM bandwidth, over the device time of the ``climber_refine_topk``
kernel's operations in the trace, in percent.

The least bytes are every distinct candidate record the window's routed
plans select, once per tick, at 4n + 8 bytes (``benchlib.work``); refine
reads memory far more than it computes (about half a flop per byte), so
bandwidth bounds it.  The bytes are those of every shard, and the trace's
kernel time is the mean over the chips traced, so the least time is the
bytes over the bandwidth of all ``trace.chips`` chips: one chip's share
of the work at one chip's bandwidth (on one chip, bytes over one chip's
bandwidth).  Read in the traced run only; None where the trace shows no
refine kernel."""
from benchlib.work import peaks

KERNEL = "climber_refine_topk"


def read(run):
    if run.trace is None or run.refine_bytes is None:
        return None
    secs = run.trace.family_s.get(KERNEL, 0.0)
    if secs <= 0.0:
        return None
    bandwidth = run.trace.chips * peaks(run.device_kind)["hbm_bytes_per_s"]
    least = run.refine_bytes() / bandwidth
    return 100.0 * least / secs
