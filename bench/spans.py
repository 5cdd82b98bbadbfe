"""The device's idle time in a traced slice, by what the serve loop did.

The label owner's serve loop marks its stages with spans named `server.*`
(`repro.obs.trace.SERVE_LOOP_SPANS`), which a tracer built with the
profiler sink writes into the profiler's trace, on the device's clock. They
tile one loop iteration: `server.wait` (blocked on the batching queue),
`server.prepare`, `server.decode`, `server.step` (parent of
`server.dispatch` and `server.sync`), `server.reply`.

Like bench/trace.py, everything here works on the plain planes of
`trace.planes` (events `[name, start_ns, duration_ns]`), inside the slice
that the `trace.WINDOW` span marks, so a recorded trace (`testdata/`)
tests it.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

from bench import trace

PREFIX = "server."
WAIT = "server.wait"
DISPATCH = "server.dispatch"
SYNC = "server.sync"
#: stages in which the device idles on the serve loop's host work
HOST = ("server.prepare", "server.decode", "server.dispatch",
        "server.reply")
#: what no serve-loop span covers
NONE = "none"
#: the step programs (jit modules) a flush runs
STEP_MODULES = ("fused_step", "arena_step")


def _host_events(pl: List[dict]):
    for p in pl:
        if p["name"].startswith(trace.DEVICE_PREFIX):
            continue
        for ln in p["lines"]:
            yield from ln["events"]


def serve_spans(pl: List[dict], lo: float, hi: float) -> List[tuple]:
    """(start, end, name) of the `server.*` spans that overlap [lo, hi)."""
    return sorted((start, start + dur, name)
                  for name, start, dur in _host_events(pl)
                  if name.startswith(PREFIX)
                  and start < hi and start + dur > lo)


def _labels(spans: List[tuple], lo: float, hi: float) -> List[tuple]:
    """[lo, hi) cut into (a, b, name) pieces, each named by the innermost
    span covering it (the latest to start; of two that start together the
    shorter), `NONE` where none does. `spans` is sorted."""
    edges = sorted({lo, hi} | {x for s in spans for x in s[:2]
                               if lo < x < hi})
    out, active, j = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while j < len(spans) and spans[j][0] <= a:
            active.append(spans[j])
            j += 1
        active = [s for s in active if s[1] > a]
        name = (max(active, key=lambda s: (s[0], -s[1]))[2] if active
                else NONE)
        out.append((a, b, name))
    return out


def _idle(events, lo: float, hi: float) -> List[tuple]:
    """The complement, in [lo, hi), of the union of device op intervals."""
    busy = trace._union([(max(s, lo), min(s + d, hi)) for _, s, d in events
                         if min(s + d, hi) > max(s, lo)])
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_by_span(pl: List[dict]) -> dict:
    """Seconds of the slice in which the device idled, by the innermost
    serve-loop span covering each idle instant (`NONE` where none does),
    averaged over the devices as `trace.reduce`'s busy time is; and the
    `server.dispatch` spans that start in the slice: their count and
    summed (unclipped) seconds."""
    lo, hi = trace.window(pl)
    labels = _labels(serve_spans(pl, lo, hi), lo, hi)
    devices = [p for p in pl if p["name"].startswith(trace.DEVICE_PREFIX)]
    idle: Dict[str, float] = {}
    for p in devices:
        ops = [ev for ln in p["lines"] if ln["name"] == trace.OPS_LINE
               for ev in ln["events"]]
        j = 0
        for a, b in _idle(ops, lo, hi):
            while labels[j][1] <= a:
                j += 1
            k = j
            while k < len(labels) and labels[k][0] < b:
                la, lb, name = labels[k]
                idle[name] = idle.get(name, 0.0) + min(b, lb) - max(a, la)
                k += 1
    n_dev = max(1, len(devices))
    disp = [dur for name, start, dur in _host_events(pl)
            if name == DISPATCH and lo <= start < hi]
    return {"window_s": (hi - lo) * 1e-9,
            "idle_s": {k: v * 1e-9 / n_dev for k, v in sorted(idle.items())},
            "dispatch_s": sum(disp) * 1e-9, "dispatch_n": len(disp)}


def flushes(pl: List[dict]) -> List[Tuple[float, float]]:
    """Each flush's [start of `server.dispatch`, end of the `server.sync`
    after it], from the serve loop's spans (one thread, in order). A
    dispatch with no sync after it was still syncing when the profiler
    stopped (an open span is not recorded): its flush ends at infinity."""
    ends = sorted((s, s + d) for name, s, d in _host_events(pl)
                  if name == SYNC)
    out, j = [], 0
    for name, s, d in sorted(_host_events(pl), key=lambda e: e[1]):
        if name != DISPATCH:
            continue
        while j < len(ends) and ends[j][0] < s + d:
            j += 1
        out.append((s, ends[j][1] if j < len(ends) else math.inf))
    return out


def module_offsets(pl: List[dict]) -> List[Tuple[float, float]]:
    """For each step program that starts in the slice, (lead, lag) in ns
    against the flush it overlaps most: how far it starts before that
    flush's `server.dispatch` starts and ends after its `server.sync`
    ends. On one clock neither can be much above 0: the program starts
    after its dispatch, and the sync waits for its result."""
    lo, hi = trace.window(pl)
    fl = flushes(pl)
    out = []
    for p in pl:
        if not p["name"].startswith(trace.DEVICE_PREFIX):
            continue
        for ln in p["lines"]:
            if ln["name"] != trace.MODULES_LINE:
                continue
            for name, s, d in ln["events"]:
                if not (lo <= s < hi and fl
                        and any(m in name for m in STEP_MODULES)):
                    continue
                a, b = max(fl, key=lambda f: min(s + d, f[1]) - max(s, f[0]))
                out.append((a - s, s + d - b))
    return out


def modules_in_flushes(pl: List[dict], tol_ns: float = 50e3):
    """(inside, total): the step programs that start in the slice, and how
    many of them ran within `tol_ns` of one flush's dispatch..sync — the
    host's spans and the device's ops read one clock if nearly all do."""
    lo, hi = trace.window(pl)
    total = sum(1 for p in pl if p["name"].startswith(trace.DEVICE_PREFIX)
                for ln in p["lines"] if ln["name"] == trace.MODULES_LINE
                for name, s, _ in ln["events"]
                if lo <= s < hi and any(m in name for m in STEP_MODULES))
    inside = sum(1 for lead, lag in module_offsets(pl)
                 if lead <= tol_ns and lag <= tol_ns)
    return inside, total


def shares(red: dict, busy_s: float) -> dict:
    """Percent of the slice: the device idle in `server.wait`, in the
    host stages (`HOST`), and the rest (`server.sync`, `server.step`
    between its children, `NONE`); with `idle_share` they add up."""
    w, idle = red["window_s"], red["idle_s"]
    wait = idle.get(WAIT, 0.0)
    host = sum(idle.get(k, 0.0) for k in HOST)
    return {"idle_share": 100.0 * (1.0 - busy_s / w),
            "idle_wait_share": 100.0 * wait / w,
            "idle_host_share": 100.0 * host / w,
            "idle_sync_share": 100.0 * idle.get(SYNC, 0.0) / w,
            "idle_none_share": 100.0 * idle.get(NONE, 0.0) / w,
            "idle_other_share": 100.0 * (sum(idle.values()) - wait - host
                                         - idle.get(SYNC, 0.0)
                                         - idle.get(NONE, 0.0)) / w}
