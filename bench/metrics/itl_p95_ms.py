"""95th percentile of the gaps between consecutive generated tokens of a
session, over every gap that ended in the window."""
import numpy as np


def read(run):
    g = [gap for t, gap in run.gaps if run.t_open <= t < run.t_close]
    if not g:
        return None
    return float(np.percentile(g, 95) * 1e3)
