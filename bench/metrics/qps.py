"""Answered queries per second over the whole window (closed-loop cells).

Every request answered before the window closed counts, whatever batch it
rode in; the window's whole length is the denominator, idle time and
admission waits included."""
import numpy as np


def read(run):
    done = np.asarray(run.done)
    answered = np.asarray(run.ok) & (done < run.window_end)
    return float(answered.sum()) / run.seconds
