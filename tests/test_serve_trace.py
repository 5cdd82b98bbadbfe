"""The serve loop's stage spans, the tracer's profiler sink, and the
resident-session counter (docs/observability.md).

The serve loop's stages (`SERVE_LOOP_SPANS`) tile each loop iteration from
one clock stamp per boundary, which feeds `stage_s`, the in-memory spans
and, with the profiler sink, `jax.profiler` annotations on the device's
clock; every stage span carries the `flush` id of the pickup it serves.
"""
from __future__ import annotations

import glob
import os

import jax
import numpy as np
import pytest

import repro.configs as configs
from repro.core import compressors as C
from repro.core import wire
from repro.models import transformer
from repro.models.config import Runtime, SplitConfig
from repro.obs.export import check_span_nesting, chrome_trace
from repro.obs.registry import MetricsRegistry
from repro.obs.trace import (NULL_TRACER, SERVE_LOOP_SPANS, SERVE_TID,
                             SPAN_DISPATCH, SPAN_QUEUE_WAIT, SPAN_STEP,
                             SPAN_SYNC, SPAN_WAIT, Tracer)
from repro.runtime import run_streaming, steps
from repro.runtime.server import StreamingServer
from repro.split import protocol
from repro.testing import VirtualClock


def _cfg():
    return configs.get("qwen3-8b", smoke=True).with_(
        split=SplitConfig(cut_layer=1, compressor="randtopk", k=8))


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, transformer.init_model(jax.random.key(0), cfg)


class _Endpoint:
    def __init__(self):
        self.sent = []

    def send(self, data: bytes) -> None:
        self.sent.append(data)


def _server(model, capacity, **kw):
    cfg, params = model
    rt = Runtime(mesh=None, training=False)
    return StreamingServer(
        params, steps.make_arena_top_step(cfg, rt, 1),
        lambda: transformer.init_cache(params, cfg, rt, 1, 8),
        max_batch=capacity, capacity=capacity, x_shape=(1, 1, cfg.d_model),
        evict_idle=False, registry=MetricsRegistry(), **kw)


def _frame(model, sid, seq):
    cfg, _ = model
    x = jax.random.normal(jax.random.key(sid * 100 + seq),
                          (1, 1, cfg.d_model))
    p = protocol.client_encode(C.make_compressor("randtopk", k=8), x,
                               key=jax.random.key(seq), training=False)
    frame, _ = wire.decode_frame(wire.encode_payload_frame(sid, seq, p))
    return frame


def _batch(server, model, frames):
    """(session, frame) pairs as the reader threads would enqueue them."""
    out = []
    for sid, seq in frames:
        sess = server._session_for(sid, _Endpoint())
        server._before_enqueue(sess)
        frame = _frame(model, sid, seq)
        server._note_enqueue(sess, frame)
        out.append((sess, frame))
    return out


def _serve_events(tracer):
    return [e for e in tracer.events()
            if e["ph"] == "X" and e["tid"] == SERVE_TID]


def test_resident_sessions_counted_per_stepping_flush(model):
    """Hand-counted: rows stepped over the sessions holding arena slots at
    each flush's pickup, after the eager release of closed ones."""
    server = _server(model, capacity=4)
    reg = server.registry
    # sessions 1 and 2 admitted and sending: 2 resident, 2 rows
    server._process(_batch(server, model, [(1, 1), (2, 1)]))
    # session 3 admitted and sending: 3 resident, 1 row
    server._process(_batch(server, model, [(3, 1)]))
    # session 3 closes, released at the next pickup: 2 resident, 1 row
    server.sessions[3].closed = True
    server._process(_batch(server, model, [(1, 2)]))
    # session 4 admitted; 2 and 4 send: 3 resident, 2 rows
    server._process(_batch(server, model, [(2, 2), (4, 1)]))
    # a replay of the last seq is re-acked, not stepped: not counted
    sess1 = server.sessions[1]
    server._before_enqueue(sess1)
    server._process([(sess1, _frame(model, 1, 2))])
    server._process([])                 # an empty pickup steps nothing
    assert server.batch_sizes == [2, 1, 1, 2]
    assert reg.histogram("flush_fill").sum == 2 + 1 + 1 + 2
    assert reg.counter("flush_resident_total").value == 2 + 3 + 2 + 3
    assert server.pickups == 6


def test_stages_tile_one_stamp_per_boundary(model):
    """The stage spans of one flush abut exactly (one stamp closes a stage
    and opens the next), `server.step` is its two children, and `stage_s`
    holds exactly the spans' durations."""
    tracer = Tracer()
    server = _server(model, capacity=4, tracer=tracer)
    for seq in (1, 2, 3):
        server._process(_batch(server, model, [(1, seq), (2, seq)]))
    ev = _serve_events(tracer)
    by_flush = {}
    for e in ev:
        by_flush.setdefault(e["args"]["flush"], {})[e["name"]] = e
    assert sorted(by_flush) == [0, 1, 2]
    for f, spans in by_flush.items():
        assert set(spans) == set(SERVE_LOOP_SPANS) - {SPAN_WAIT}
        order = ["server.prepare", "server.decode", "server.step",
                 "server.reply"]
        for a, b in zip(order, order[1:]):
            assert spans[a]["ts"] + spans[a]["dur"] == spans[b]["ts"]
        step, disp, sync = (spans[SPAN_STEP], spans[SPAN_DISPATCH],
                            spans[SPAN_SYNC])
        assert disp["ts"] == step["ts"]
        assert disp["ts"] + disp["dur"] == sync["ts"]
        assert sync["ts"] + sync["dur"] == step["ts"] + step["dur"]
        assert all(s["args"]["n"] == 2 for s in spans.values())
    for key in ("prepare", "decode", "step", "dispatch", "sync", "reply"):
        durs = [e["dur"] for e in ev if e["name"] == "server." + key]
        assert server.stage_s[key] == pytest.approx(sum(durs), rel=1e-12,
                                                    abs=1e-15)
    assert server.stage_s["wait"] == 0.0    # no serve loop ran
    # queue-wait spans name the flush that picked their frame up
    qw = [e for e in tracer.events() if e["name"] == SPAN_QUEUE_WAIT]
    assert sorted(e["args"]["flush"] for e in qw) == [0, 0, 1, 1, 2, 2]
    assert check_span_nesting(chrome_trace(tracer)["traceEvents"]) == []


def test_virtual_clock_stages_are_deterministic(model):
    """Under a VirtualClock every stage stamp is the virtual time, so the
    loadgen co-simulation's traces stay a function of the seed."""
    vc = VirtualClock()
    tracer = Tracer(clock=vc)
    server = _server(model, capacity=2, tracer=tracer, clock=vc)
    vc.advance_to(2.5)
    server._process(_batch(server, model, [(1, 1)]))
    ev = _serve_events(tracer)
    assert {e["ts"] for e in ev} == {2.5} and {e["dur"] for e in ev} == {0.0}
    assert set(server.stage_s.values()) == {0.0}


def test_null_tracer_keeps_no_events_and_stage_s_still_counts(model):
    assert NULL_TRACER.span("a") is NULL_TRACER.span("b", x=1)
    span = NULL_TRACER.span("a").begin(1.0)
    span.note(n=3)
    span.end(2.0)
    assert NULL_TRACER.events() == [] and not NULL_TRACER.enabled
    server = _server(model, capacity=2)
    assert server.tracer is NULL_TRACER
    server._process(_batch(server, model, [(1, 1), (2, 1)]))
    assert all(server.stage_s[k] > 0 for k in
               ("prepare", "decode", "step", "dispatch", "sync", "reply"))
    assert server.stage_s["step"] == pytest.approx(
        server.stage_s["dispatch"] + server.stage_s["sync"])


def test_record_false_keeps_nothing_in_memory():
    tracer = Tracer(record=False)
    with tracer.span("server.wait", tid=SERVE_TID, flush=0) as s:
        s.note(n=2)
    tracer.complete("server.queue_wait", 0.0, 1.0)
    tracer.instant("slot.admit")
    tracer.name_track(SERVE_TID, "serve loop")
    assert tracer.events() == [] and len(tracer) == 0


def _profiled_events(logdir):
    from jax.profiler import ProfileData

    [path] = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                    "*.xplane.pb"))
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith("server."):
                    lines.setdefault((plane.name, ln.name), []).append(
                        (e.name, e.start_ns, e.duration_ns, dict(e.stats)))
    return lines


def test_profiler_sink_mirrors_serve_loop_spans(model, tmp_path):
    """A threaded run under the JAX profiler: every serve-loop stage lands
    in the profiler's trace on one host line, laminar, each with its flush
    id; with `record=False` nothing stays in memory."""
    cfg, params = model
    tracer = Tracer(profiler=True, record=False)
    # compile outside the trace
    run_streaming(cfg, n_clients=2, prompt_len=2, gen=2, max_batch=2,
                  params=params)
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = run_streaming(cfg, n_clients=2, prompt_len=2, gen=3,
                            max_batch=2, params=params, tracer=tracer)
    finally:
        jax.profiler.stop_trace()
    assert tracer.events() == []
    lines = _profiled_events(str(tmp_path))
    assert len(lines) == 1, sorted(lines)
    [events] = lines.values()
    names = {e[0] for e in events}
    assert names == set(SERVE_LOOP_SPANS)
    assert all("flush" in e[3] for e in events)
    assert all("n" in e[3] for e in events)
    chrome = [{"ph": "X", "pid": 0, "tid": 0, "name": n, "ts": s / 1e3,
               "dur": d / 1e3} for n, s, d, _ in events]
    assert check_span_nesting(chrome) == []
    # one dispatch per stepping flush, each inside its flush's step
    steps_ = sorted((s, s + d, st["flush"]) for n, s, d, st in events
                    if n == SPAN_STEP)
    disp = sorted((s, s + d, st["flush"]) for n, s, d, st in events
                  if n == SPAN_DISPATCH)
    assert len(disp) == len(steps_) == res["flushes"]
    for (a, b, f), (c, d, g) in zip(steps_, disp):
        assert f == g and a <= c and d <= b
    flushes = [st["flush"] for n, _, _, st in sorted(
        events, key=lambda e: e[1]) if n == SPAN_STEP]
    assert flushes == sorted(flushes)
    assert np.asarray(res["tokens"]).shape == (2, 3)
