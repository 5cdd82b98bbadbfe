"""95th percentile, over every session due in the window, of the time from
its due arrival to its first generated token. A session still waiting at
the window's close counts with the time it has waited."""
import numpy as np


def read(run):
    if not run.ttft:
        return None
    return float(np.percentile(run.ttft, 95) * 1e3)
