"""Serve-loop host microseconds per token outside the blocking step:
`StreamingServer.stage_s` decode (staging, and on mixed flushes the
decode dispatches) plus reply (framing and sending), over the tokens
those flushes served, in the window."""


def read(run):
    a, b = run.at_open, run.at_close
    n = b["stage_tokens"] - a["stage_tokens"]
    if n <= 0:
        return None
    s = sum(b["stage_s"][k] - a["stage_s"][k] for k in ("decode", "reply"))
    return 1e6 * s / n
