"""The reduction from a profiler trace to busy time, step and kernel time."""
import glob
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import spec, trace  # noqa: E402


def _planes():
    """One host span [100, 1100) ns and one device with ops that overlap,
    spill past the slice, and leave gaps of 200 and 100 ns."""
    host = {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [[trace.WINDOW, 100, 1000]]},
        {"name": "main", "events": [["PjitFunction(fused_step)", 390, 220],
                                    ["dispatch", 850, 50]]}]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": trace.OPS_LINE, "events": [
            ["%fusion.1 = f32[8] fusion(f32[8] %p)", 0, 200],   # [100, 200)
            ["fusion.2", 150, 250],         # overlaps: union [100, 400)
            ["%decode_to_slots_kernel.1 = bf16[9,1,64] custom-call(s32[4] %slots)", 600, 100],
            ["fusion.1", 700, 100],
            ["fusion.3", 900, 400]]},       # clipped to [900, 1100)
        {"name": trace.MODULES_LINE, "events": [
            ["jit_fused_step(3)", 100, 300], ["jit_fused_step(3)", 600, 200],
            ["jit_other", 900, 100]]}]}
    return [host, dev]


def test_busy_and_window():
    r = trace.reduce(_planes())
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: [100,400) + [600,800) + [900,1100) = 700 ns
    assert r["busy_s"] == pytest.approx(700e-9)
    assert r["devices"] == 1


def test_ops_modules_and_gaps():
    r = trace.reduce(_planes())
    assert r["ops"]["fusion.1"] == [pytest.approx(200e-9), 2]
    assert trace.seconds_where(r["modules"], "fused_step") == (
        pytest.approx(500e-9), 2)
    assert trace.seconds_where(r["ops"], "decode_to_slots_kernel") == (
        pytest.approx(100e-9), 1)
    # gaps [400, 600) and [800, 900), longest first, named by the host
    # event that covers most of each
    assert r["idle_gaps"] == [["PjitFunction(fused_step)",
                               pytest.approx(200e-9)],
                              ["dispatch", pytest.approx(100e-9)]]
    assert trace.top(r["ops"], 1) == [["fusion.2", pytest.approx(250e-9)]]


def test_no_window_is_an_error():
    pl = _planes()
    pl[0]["lines"][0]["events"] = []
    with pytest.raises(ValueError):
        trace.reduce(pl)


RECORDED = sorted(glob.glob(os.path.join(spec.BENCH, "testdata",
                                         "trace_*.json")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_chip_trace(path):
    """A slice of a trace recorded on one v5e chip (bench/testdata)."""
    with open(path) as f:
        rec = json.load(f)
    r = trace.reduce(rec["planes"])
    assert r["devices"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    # the same busy time by a sweep over interval edges
    lo, hi = trace.window(rec["planes"])
    edges = []
    for p in rec["planes"]:
        for ln in p["lines"]:
            if p["name"].startswith(trace.DEVICE_PREFIX) and \
                    ln["name"] == trace.OPS_LINE:
                for _, start, dur in ln["events"]:
                    a, b = max(start, lo), min(start + dur, hi)
                    if b > a:
                        edges += [(a, 1), (b, -1)]
    depth, last, busy = 0, None, 0.0
    for t, step in sorted(edges, key=lambda e: (e[0], -e[1])):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    assert r["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-9)
    sec, n = trace.seconds_where(r["modules"], "fused_step", "arena_step")
    assert n == rec["expect"]["step_modules"] and sec > 0
