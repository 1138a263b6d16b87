"""Admission: live rows per tick over the engine's batch size, the mean
over the window's ticks (from the benchmark's wrapper of the engine's
IndexFleet.query call)."""
import numpy as np


def read(run):
    if len(run.tick_live) == 0:
        return None
    return float(np.mean(run.tick_live)) / run.batch_size
