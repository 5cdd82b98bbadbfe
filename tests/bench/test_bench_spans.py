"""The device's idle time by serve-loop stage (bench/spans.py), the
`dispatch_us_per_flush` reader, and bench/span_run.py's run on the CPU."""
import glob
import json
import math
import os
import time

import pytest

from bench_small import SEED, small_cell
from bench import harness, span_run, spans, spec, trace  # noqa: E402


def _serve_line():
    """One loop and a bit, in ns: the tail of a flush that started before
    the slice [100, 1100), a wait, a flush whose dispatch returns 10 ns
    before its sync starts, a wait, then nothing."""
    return [["server.step", 40, 80], ["server.dispatch", 40, 40],
            ["server.sync", 80, 40], ["server.reply", 120, 40],
            ["server.wait", 160, 140], ["server.prepare", 300, 50],
            ["server.decode", 350, 50], ["server.step", 400, 400],
            ["server.dispatch", 400, 90], ["server.sync", 500, 300],
            ["server.reply", 800, 50], ["server.wait", 850, 150]]


def _device(ops=((130, 120), (550, 230), (1050, 250))):
    return {"name": "/device:TPU:0", "lines": [
        {"name": trace.OPS_LINE,
         "events": [[f"fusion.{i}", s, d] for i, (s, d) in enumerate(ops)]},
        {"name": trace.MODULES_LINE, "events": [
            ["jit_fused_step(1)", 45, 73], ["jit_fused_step(1)", 520, 270],
            ["jit_other", 600, 10], ["jit_fused_step(1)", 1050, 40]]}]}


def _planes():
    host = {"name": "/host:CPU", "lines": [
        {"name": "main", "events": [[trace.WINDOW, 100, 1000]]},
        {"name": "serve", "events": _serve_line()},
        {"name": "reader", "events": [["PjitFunction(fused_step)", 400,
                                       90]]}]}
    return [host, _device()]


#: idle ns by innermost span: [100, 130) sync 20 + reply 10, [250, 300)
#: wait, prepare, decode, [400, 490) dispatch, [490, 500) step between its
#: children, [500, 550) + [780, 800) sync, [800, 850) reply, [850, 1000)
#: wait, [1000, 1050) under no span
IDLE_NS = {"server.sync": 90, "server.reply": 60, "server.wait": 200,
           "server.prepare": 50, "server.decode": 50,
           "server.dispatch": 90, "server.step": 10, spans.NONE: 50}


def test_idle_by_innermost_span_clipped_to_the_slice():
    r = spans.idle_by_span(_planes())
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["idle_s"] == {k: pytest.approx(v * 1e-9)
                           for k, v in IDLE_NS.items()}
    busy = trace.reduce(_planes())["busy_s"]
    assert sum(r["idle_s"].values()) == pytest.approx(
        r["window_s"] - busy)
    # only the dispatch that starts inside the slice, unclipped
    assert (r["dispatch_n"], r["dispatch_s"]) == (1, pytest.approx(90e-9))


def test_idle_averages_over_devices():
    pl = _planes()
    idle_dev = _device(ops=())
    idle_dev["name"] = "/device:TPU:1"
    pl.append(idle_dev)
    r = spans.idle_by_span(pl)
    alone = spans.idle_by_span([pl[0], idle_dev])
    one = spans.idle_by_span(_planes())
    for k in set(alone["idle_s"]) | set(one["idle_s"]):
        assert r["idle_s"][k] == pytest.approx(
            (alone["idle_s"].get(k, 0.0) + one["idle_s"].get(k, 0.0)) / 2)
    assert sum(alone["idle_s"].values()) == pytest.approx(1000e-9)


def test_labels_pick_the_innermost_and_none():
    s = [(0, 100, "server.step"), (0, 40, "server.dispatch"),
         (50, 100, "server.sync")]
    assert spans._labels(sorted(s), -10, 120) == [
        (-10, 0, spans.NONE), (0, 40, "server.dispatch"),
        (40, 50, "server.step"), (50, 100, "server.sync"),
        (100, 120, spans.NONE)]


def test_shares_add_up_to_idle_share():
    pl = _planes()
    sh = spans.shares(spans.idle_by_span(pl), trace.reduce(pl)["busy_s"])
    assert sh["idle_share"] == pytest.approx(60.0)
    assert sh["idle_wait_share"] == pytest.approx(20.0)
    assert sh["idle_host_share"] == pytest.approx(25.0)     # 50+50+90+60
    assert sh["idle_sync_share"] == pytest.approx(9.0)
    assert sh["idle_none_share"] == pytest.approx(5.0)
    assert sh["idle_other_share"] == pytest.approx(1.0)     # step
    assert sum(v for k, v in sh.items() if k != "idle_share") == \
        pytest.approx(sh["idle_share"])


def test_step_modules_inside_their_flush():
    pl = _planes()
    assert spans.flushes(pl) == [(40, 120), (400, 800)]
    # a dispatch whose sync was still open when the trace stopped
    cut = _planes()
    cut[0]["lines"][1]["events"] = _serve_line()[:9]
    assert spans.flushes(cut) == [(40, 120), (400, math.inf)]
    # [45, 118) starts before the slice; [520, 790) lies in a flush;
    # [1050, 1090) in none
    assert spans.modules_in_flushes(pl, tol_ns=5) == (1, 2)
    pl[1]["lines"][1]["events"][1] = ["jit_fused_step(1)", 397, 300]
    assert spans.modules_in_flushes(pl, tol_ns=5) == (1, 2)
    assert spans.modules_in_flushes(pl, tol_ns=2) == (0, 2)
    # against the flush each overlaps most: [397, 697) starts 3 ns before
    # its dispatch; [1050, 1090) ends 290 ns past the last flush's sync
    assert spans.module_offsets(pl) == [(3, -103), (-650, 290)]


RECORDED = [p for p in sorted(glob.glob(os.path.join(
    spec.BENCH, "testdata", "trace_*.json")))
    if "idle_s" in spec.load_json(p)["expect"]]


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_chip_trace_by_span(path):
    """A slice recorded on one v5e chip with the serve loop's spans."""
    with open(path) as f:
        rec = json.load(f)
    pl, want = rec["planes"], rec["expect"]
    r = spans.idle_by_span(pl)
    assert r["idle_s"] == {k: pytest.approx(v, rel=1e-9)
                           for k, v in want["idle_s"].items()}
    assert (r["dispatch_n"], r["dispatch_s"]) == (
        want["dispatch_n"], pytest.approx(want["dispatch_s"], rel=1e-9))
    red = trace.reduce(pl)
    assert sum(r["idle_s"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)
    assert list(spans.modules_in_flushes(pl)) == want["modules_in_flushes"]
    assert len(spans.module_offsets(pl)) == want["modules_in_flushes"][1] > 0


class _Run:
    def __init__(self, stage_open, stage_close, flushes):
        self.at_open = {"stage_s": stage_open, "flushes": flushes[0]}
        self.at_close = {"stage_s": stage_close, "flushes": flushes[1]}


def test_dispatch_reader():
    read = spec.reader("dispatch_us_per_flush").read
    run = _Run({"dispatch": 1.0, "decode": 0.0},
               {"dispatch": 1.5, "decode": 0.0}, (10, 110))
    assert read(run) == pytest.approx(5000.0)
    assert spec.reader("dispatch_us_per_flush.longgen").read(run) == \
        pytest.approx(5000.0)
    # a program without the stage, or no flush in the window: no reading
    assert read(_Run({"decode": 0.0}, {"decode": 1.0}, (0, 5))) is None
    assert read(_Run({"dispatch": 1.0}, {"dispatch": 1.0}, (5, 5))) is None


def test_span_run_on_the_cpu(tmp_path):
    """bench/span_run.py's watch through the harness at the small size:
    the profiler sink's spans reach the profiler's trace (the CPU has no
    device plane, so nothing idles by span), the counters cover the
    window, and the recorded cut reads back."""
    cell = small_cell("qwen3-8b-l8.randtopk-chat")
    seconds = 3.0
    rec = str(tmp_path / "cut.json")
    watch = span_run.Watch(cell, seconds, True, True, rec)
    out = harness.run(cell, SEED, seconds, False, time.perf_counter(),
                      tamper=watch)
    watch.thread.join(30.0)
    assert not watch.thread.is_alive() and watch.error is None
    assert out["correct"], out
    w = watch.out["window"]
    assert w["flushes"] > 0 and 0 < w["flush_cover"] <= 100
    assert w["dispatch_us_per_flush"] > 0
    sl = watch.out["slice"]
    assert sl["dispatch_n"] > 0 and sl["dispatch_us"] > 0
    assert sl["modules_in_flushes"] == [0, 0]
    assert 0 < sl["counters"]["flush_cover"] <= 100
    with open(rec) as f:
        cut = json.load(f)
    names = {e[0] for p in cut["planes"] for ln in p["lines"]
             for e in ln["events"]}
    assert trace.WINDOW in names and spans.DISPATCH in names
    assert cut["expect"]["dispatch_n"] == spans.idle_by_span(
        cut["planes"])["dispatch_n"]
