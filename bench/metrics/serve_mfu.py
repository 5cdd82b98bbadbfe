"""The whole label-owner step's share of the chip's bf16 peak: model
FLOPs of the rows served in the window (top layers, attention at each
row's actual length, the head; bench/costs.py), per second of window,
over the peak."""
from bench import costs


def read(run):
    if run.peaks is None:
        return None
    m = run.replies_between(run.t_open, run.t_close)
    flops = float(costs.row_flops(run.conf, run.r_step[m]).sum())
    return 100.0 * flops / run.seconds / run.peaks["bf16_flops_per_s"]
