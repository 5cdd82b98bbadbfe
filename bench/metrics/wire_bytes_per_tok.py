"""Uplink frame bytes, framing included, per token served: for each
compressor of the fleet, the bytes of the frames whose replies landed in
the window over those replies, averaged with the mix's shares. It is
exact for a mix, whichever sessions happen to be live in the window."""


def read(run):
    m = run.replies_between(run.t_open, run.t_close)
    fleet = run.traffic["compressors"]
    total, weight = 0.0, 0.0
    for ci, c in enumerate(fleet):
        mc = m & (run.r_comp == ci)
        n = int(mc.sum())
        if not n:
            return None
        total += c["share"] * float(run.r_up[mc].sum()) / n
        weight += c["share"]
    return total / weight
