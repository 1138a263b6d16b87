"""The one general traffic generator: a traffic file's parameters and a
seed in, the work of one run out.

Two modes, as the file's ``mode`` says:

  * ``closed`` — ``outstanding`` requests are kept in flight over
    ``connections`` sockets; each sends its next request when the previous
    one is answered.  A request is due when it is sent.
  * ``open``   — requests are due on a fixed schedule at ``rate_qps``,
    whatever the server does, spread round-robin over ``connections``.
    Latency counts from the due time, so a stall delays every later
    request's clock too.

The open schedule has the same work for every seed: ``rate_qps x seconds``
requests whose gaps are one fixed set of exponential (Poisson) gaps,
scaled to fill the window exactly and put in an order that the seed
draws.  Seeds then differ in which queries arrive when, never in how many.
"""
from __future__ import annotations

import numpy as np

GAP_SEED = 0x0A11           # the fixed draw of the gaps, shared by all seeds


def check_traffic(t: dict) -> None:
    mode = t.get("mode")
    if mode == "closed":
        for key in ("outstanding", "connections", "pool"):
            if int(t[key]) < 1:
                raise ValueError(f"closed traffic needs {key} >= 1")
    elif mode == "open":
        if float(t["rate_qps"]) <= 0 or int(t["connections"]) < 1:
            raise ValueError("open traffic needs rate_qps > 0 and "
                             "connections >= 1")
    else:
        raise ValueError(f"unknown traffic mode {mode!r}")
    if not 0.0 <= float(t["query_noise"]) < 1.0:
        raise ValueError("query_noise must be in [0, 1)")


def open_schedule(rate_qps: float, seconds: float, seed: int) -> np.ndarray:
    """Due offsets (seconds from the window's start), ascending, all in
    ``[0, seconds)``: ``round(rate_qps * seconds)`` arrivals."""
    n = max(1, int(round(rate_qps * seconds)))
    gaps = np.random.default_rng(GAP_SEED).exponential(1.0, n + 1)
    gaps *= seconds / gaps.sum()                # n+1 gaps span the window
    order = np.random.default_rng(seed).permutation(n + 1)
    due = np.cumsum(gaps[order])[:n]
    return due


def queries_needed(t: dict, seconds: float) -> int:
    """How many distinct window queries a run may send."""
    if t["mode"] == "open":
        return max(1, int(round(float(t["rate_qps"]) * seconds)))
    return int(t["pool"])


def lateness_summary(sent: np.ndarray, due: np.ndarray) -> dict:
    """How late the generator sent, in ms: median, 99th percentile, max."""
    late = np.maximum(np.asarray(sent) - np.asarray(due), 0.0) * 1e3
    if late.size == 0:
        return {"p50_ms": 0.0, "p99_ms": 0.0, "max_ms": 0.0}
    return {"p50_ms": float(np.percentile(late, 50)),
            "p99_ms": float(np.percentile(late, 99)),
            "max_ms": float(late.max())}
