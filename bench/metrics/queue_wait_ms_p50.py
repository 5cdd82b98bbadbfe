"""Median of the server registry's `queue_wait_ms` histogram (frame
enqueued to flush), over the pre-roll and the window (P-square estimate)."""
import math


def read(run):
    v = run.at_close["queue_wait_ms_p50"]
    return None if v is None or math.isnan(v) else float(v)
