"""Device milliseconds per execution of the label owner's arena step (the
fused decode+step program, or the plain arena step of a mixed flush),
found by its jit module name in the trace."""
from bench import trace as trace_mod

STEP_MODULES = ("fused_step", "arena_step")


def read(run):
    if run.trace is None:
        return None
    s, n = trace_mod.seconds_where(run.trace["modules"], *STEP_MODULES)
    return 1e3 * s / n if n else None
