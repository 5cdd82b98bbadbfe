"""Mean rows per flush of the serve loop in the window
(`StreamingServer.batch_sizes`)."""
import numpy as np


def read(run):
    sizes = run.at_close["batch_sizes"][run.at_open["flushes"]:]
    return float(np.mean(sizes)) if sizes else None
