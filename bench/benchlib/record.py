"""What one run leaves for the metric readers (``bench/metrics/*.py``).

A reader is ``read(run: RunRecord) -> float | None``: it returns None
where the run has nothing for it to read, and the harness then leaves the
metric out of the line.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np


@dataclass
class RunRecord:
    seconds: float                 # the measured window's length
    setup_s: float                 # process start -> first timed request
    due: np.ndarray                # [N] when each request was due (monotonic)
    done: np.ndarray               # [N] when it was answered (NaN: never)
    ok: np.ndarray                 # [N] answered with a result
    window_end: float              # monotonic time the window closed
    batch_size: int
    # ticks of the window, from the benchmark's wrapper of IndexFleet.query
    tick_secs: np.ndarray          # [T] host seconds of each call
    tick_live: np.ndarray          # [T] live rows of each call
    recall: Optional[np.ndarray] = None    # [answered] per-query recall@k
    trace: Optional[object] = None         # benchlib.trace.TraceSummary
    device_kind: str = ""
    # lazily counted least bytes of refine over the traced ticks
    refine_bytes: Optional[Callable[[], int]] = None
