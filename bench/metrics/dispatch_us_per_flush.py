"""Mean `server.dispatch` of the serve loop's flushes in the window, in
microseconds: the step program's call (`_fused_step` or `top_step`, with
its argument transfer and launch) until it returns to the host
(`StreamingServer.stage_s["dispatch"]`, stamped where the span's ends are,
over the flushes that stepped). None where the program keeps no such
stage."""


def read(run):
    a, b = run.at_open, run.at_close
    n = b["flushes"] - a["flushes"]
    if n <= 0 or "dispatch" not in b["stage_s"]:
        return None
    return 1e6 * (b["stage_s"]["dispatch"] - a["stage_s"]["dispatch"]) / n
