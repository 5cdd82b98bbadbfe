"""Share of the slot-decode kernel's device time that its bytes need at
the chip's HBM peak: the payloads of the rows served in the traced slice
in, and those rows out as dense activations, over the kernel's summed
device time in the slice."""
from bench import costs, trace as trace_mod

DECODE_OPS = ("decode_to_slots_kernel",)


def read(run):
    tr = run.trace
    if tr is None or run.peaks is None:
        return None
    sec, n = trace_mod.seconds_where(tr["ops"], *DECODE_OPS)
    m = run.replies_between(tr["t0"], tr["t1"])
    rows = int(m.sum())
    if not n or not rows or sec <= 0:
        return None
    need = costs.decode_bytes(run.conf, float(run.r_pay[m].sum()), rows)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / sec
