"""Fleet tick: mean host time of the engine's IndexFleet.query call over
the window's ticks.  The call returns numpy arrays, so it ends when the
device has finished."""
import numpy as np


def read(run):
    if len(run.tick_secs) == 0:
        return None
    return float(np.mean(run.tick_secs)) * 1e3
