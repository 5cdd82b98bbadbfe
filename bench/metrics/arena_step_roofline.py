"""Share of the arena step's device time that the served rows need at the
chip's peaks: weights read once per flush, each served row's KV read at
its actual length and one position written, FLOPs of the served rows
only (bench/costs.py), over the device time of the step programs in the
traced slice. The rows are the replies that landed in the slice."""
import numpy as np

from bench import costs, trace as trace_mod

STEP_MODULES = ("fused_step", "arena_step")


def read(run):
    tr = run.trace
    if tr is None or run.peaks is None:
        return None
    sec, flushes = trace_mod.seconds_where(tr["modules"], *STEP_MODULES)
    m = run.replies_between(tr["t0"], tr["t1"])
    pos = run.r_step[m]
    if not flushes or not len(pos) or sec <= 0:
        return None
    flushes = min(flushes, len(pos))
    rows = np.diff(np.linspace(0, len(pos), flushes + 1).round()).astype(int)
    least, _bound = costs.step_least_seconds(run.conf, rows, pos, run.peaks)
    return 100.0 * least / sec
