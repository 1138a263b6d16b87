"""Mean recall@k of every answered query against the exact top-k of a
brute-force scan of the same corpus (``benchlib.reference``), boundary
ties counted as hits."""
import numpy as np


def read(run):
    if run.recall is None or len(run.recall) == 0:
        return None
    return float(np.mean(run.recall))
