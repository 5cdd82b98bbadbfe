"""Reduce a profiler trace of the window to what the metric readers need.

`planes(path)` turns JAX's `.xplane.pb` into plain data (a list of planes,
each a name and its lines of `[name, start_ns, duration_ns]` events), and
`reduce` works on that plain data only, so a small recorded trace checked
in beside this file (`testdata/`) tests the arithmetic.

The traced slice is the host span named `WINDOW` (a `TraceAnnotation` the
harness opens around it). Device busy time is the union of the intervals
in which an operation ran on a device, clipped to the slice, averaged over
the devices used.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List

WINDOW = "bench_window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def planes(logdir: str) -> List[dict]:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} traces under {logdir}")
    out = []
    for p in ProfileData.from_file(paths[0]).planes:
        out.append({"name": p.name, "lines": [
            {"name": ln.name,
             "events": [[e.name, e.start_ns, e.duration_ns]
                        for e in ln.events]}
            for ln in p.lines]})
    return out


def op_name(event_name: str) -> str:
    """An XLA op event is named by its HLO text (`%fusion.3 = f32[...]
    fusion(...), ...`); the op is the name before ` = `. Nested ops (a
    `while` and the ops of its body) are events of their own."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _union(iv: List[tuple]) -> List[tuple]:
    out: List[list] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def window(pl: List[dict]):
    for p in pl:
        if p["name"].startswith(DEVICE_PREFIX):
            continue
        for ln in p["lines"]:
            for name, start, dur in ln["events"]:
                if name == WINDOW:
                    return float(start), float(start + dur)
    raise ValueError(f"no {WINDOW!r} span in the trace")


def reduce(pl: List[dict]) -> dict:
    """busy_s, window_s, per-op and per-module device seconds and counts,
    the idle gaps with what the host ran during each, all inside the slice.
    """
    lo, hi = window(pl)
    devices = [p for p in pl if p["name"].startswith(DEVICE_PREFIX)]
    ops: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    busy_ns, gaps = 0.0, []
    for p in devices:
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        iv = []
        for name, start, dur in lines.get(OPS_LINE, []):
            a, b = max(start, lo), min(start + dur, hi)
            if b > a:
                iv.append((a, b))
                rec = ops.setdefault(op_name(name), [0.0, 0])
                rec[0] += (b - a) * 1e-9
                rec[1] += 1
        for name, start, dur in lines.get(MODULES_LINE, []):
            a, b = max(start, lo), min(start + dur, hi)
            if b > a:
                rec = modules.setdefault(name, [0.0, 0])
                rec[0] += (b - a) * 1e-9
                rec[1] += 1
        merged = _union(iv)
        busy_ns += sum(b - a for a, b in merged)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    n_dev = max(1, len(devices))
    host = [(name, start, start + dur) for p in pl
            if not p["name"].startswith(DEVICE_PREFIX)
            for ln in p["lines"] for name, start, dur in ln["events"]
            if name != WINDOW and dur > 0]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for a, b in gaps[:10]:
        over = {}
        for name, s, e in host:
            ov = min(b, e) - max(a, s)
            if ov > 0:
                over[name] = over.get(name, 0.0) + ov
        label = max(over, key=over.get) if over else "no host event"
        named.append([label, (b - a) * 1e-9])
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy_ns * 1e-9 / n_dev,
            "devices": len(devices), "ops": ops, "modules": modules,
            "idle_gaps": named}


def seconds_where(table: Dict[str, list], *needles: str):
    """Total seconds and count of the entries whose name holds a needle."""
    s, n = 0.0, 0
    for name, (sec, cnt) in table.items():
        if any(x in name for x in needles):
            s += sec
            n += cnt
    return s, n


def top(table: Dict[str, list], n: int = 10) -> List[list]:
    return [[k, v[0]] for k, v in sorted(table.items(),
                                          key=lambda kv: -kv[1][0])[:n]]
