"""Device: one minus the union of the intervals in which an operation ran
on the chip, over the traced window (``benchlib.trace``)."""


def read(run):
    if run.trace is None:
        return None
    return run.trace.idle_share
