"""One run of one cell: set-up, the measured window, the correctness check.

Set-up (all of it is `setup_s`, each part printed on a line of its own):
the weights, made on the device from the seed; the frame pool, made by
the program's feature-owner code (bench/frames.py); the label owner, a
`runtime.server.StreamingServer` built with the jitted step pair of
`runtime.engine._serving_steps` as `engine.run_streaming` builds it, and
warmed for every (payload meta, flush bucket) program (`server.warm`);
then a pre-roll of the cell's traffic, so the arena is at its steady
occupancy when the window opens.

The window: the driver (bench/driver.py) keeps offering the traffic; the
harness only sleeps, and with `--trace 1` profiles a slice of it.

After the window: the sessions are closed, the server stopped and joined,
the peak device memory read, the program's state dropped, and then the
plain reference (bench/reference.py) checks a sample of the positions the
window served.
"""
from __future__ import annotations

import gc
import math
import os
import shutil
import sys
import threading
import time
from typing import Callable, Dict, Optional

import numpy as np

from bench import driver as driver_mod, frames, model, reference
from bench import spec, trace as trace_mod, traffic as traffic_mod

#: served positions the reference checks per run (at least; the longest
#: finished session is always in the sample)
CHECK_STEPS = 600
#: sessions per reference batch (one compiled shape)
CHECK_BATCH = 8
#: a frame unanswered this long at the window's close is a failed session
REPLY_TIMEOUT_S = 30.0


def say(msg: str) -> None:
    print(msg, flush=True)


class CompileLog:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (a cache hit shows as a near-zero compile)."""

    def __init__(self):
        from jax import monitoring

        self.seconds, self.programs, self.hits, self.misses = 0.0, 0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return self.seconds, self.programs, self.hits, self.misses

    def since(self, mark) -> str:
        s, p, h, m = (a - b for a, b in zip(self.mark(), mark))
        return (f"{s:.2f} s backend compile over {p} programs "
                f"(persistent cache: {h} hits, {m} misses)")


def enable_compile_cache() -> str:
    """JAX's persistent cache at `$JAX_COMPILATION_CACHE_DIR`, else at the
    fixed `<checkout>/.jax_cache`; every program is cached."""
    import jax

    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(spec.CHECKOUT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def build_server(cfg, params, serving: dict):
    """The label owner, built as `engine.run_streaming` builds it, with
    the arena and flush sizes of the configuration's deployment. `cfg` and
    `params` are its own layers only (`model.label_owner`), so its step
    runs them from layer 0."""
    from repro.models import transformer
    from repro.models.config import Runtime
    from repro.obs.registry import MetricsRegistry
    from repro.runtime import engine
    from repro.runtime.server import StreamingServer

    rt = Runtime(mesh=None, training=False)
    cap, max_len = serving["capacity"], serving["max_len"]
    server = StreamingServer(
        params, None,
        lambda: transformer.init_cache(params, cfg, rt, 1, max_len),
        max_batch=cap, max_wait=serving["max_wait_s"], dtype=cfg.adtype(),
        capacity=cap, x_shape=(1, 1, cfg.d_model),
        jit_steps=engine._serving_steps(cfg, rt, 0, cfg.dtype, None,
                                        None),
        # the driver admits at most `capacity` live sessions; a new
        # session's first frame may still reach the server just before
        # the closing frame of the session it replaces, and then waits
        # for that slot instead of evicting an idle one to the host
        evict_idle=False, registry=MetricsRegistry())
    # never close on "every session ended": the harness shuts it down
    server.expected_sessions = 1 << 62
    return server


class Run:
    """What the metric readers read (bench/metrics/*.py)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def replies_between(self, lo: float, hi: float):
        t = self.r_t
        return (t >= lo) & (t < hi)


def _percentile(xs, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else None


def _sample(finished, live, rng):
    """Sessions served in the window, as (session, lo, hi): positions lo to
    hi - 1 were served in it. The finished session served most in the
    window, then other finished ones drawn from the seed, until CHECK_STEPS
    positions or CHECK_BATCH sessions; where the finished ones fall short
    (long sessions), the live sessions served most in the window."""
    if finished:
        longest = max(finished, key=lambda c: c[2] - c[1])
        rest = [c for c in finished if c is not longest]
        rng.shuffle(rest)
        finished = [longest] + rest
    out, n = [], 0
    for c in finished + sorted(live, key=lambda c: c[1] - c[2]):
        if n >= CHECK_STEPS or len(out) == CHECK_BATCH:
            break
        out.append(c)
        n += c[2] - c[1]
    return out


def check(conf: dict, params, plan, pool, sessions, window: dict, seed: int,
          control=None) -> dict:
    """The reference over a sample of the sessions served in the window
    (`_sample`; `window` maps a session id to the range of its positions
    whose replies landed in the window): the widest gap of a served token
    below the reference's best logit, over those positions. With `control`
    (a `reference.Reference` quant mode), also the widest gap of the tokens
    that reference, at that precision, puts first at the same positions."""
    rng = np.random.default_rng([seed, 0xC4EC])
    served_in = [(s, *window[s.sid]) for s in sorted(sessions,
                                                      key=lambda s: s.sid)
                 if s.sid in window and not s.failed]
    sample = _sample([c for c in served_in if c[0].done],
                     [c for c in served_in if not c[0].done], rng)
    if not sample:
        return {"sessions": 0, "positions": 0, "gap": math.inf,
                "overrides": 0, "slack": math.nan, "agree": 0.0}
    max_len = conf["serving"]["max_len"]
    tokens = np.zeros((CHECK_BATCH, max_len), np.int32)
    served = np.full((CHECK_BATCH, max_len), -1, np.int32)
    valid = np.zeros((CHECK_BATCH, max_len), bool)
    for b, (s, lo, hi) in enumerate(sample):
        tokens[b] = plan.tokens[s.script]
        served[b, :hi] = s.served[:hi]
        valid[b, lo:hi] = True
    ref = reference.Reference(conf, params)
    top_in, overrides, slack = _top_inputs(ref, tokens, sample, pool)
    gaps = ref.top_gaps(top_in, served)[valid]
    out = {"sessions": len(sample), "positions": int(valid.sum()),
           "gap": float(gaps.max()), "overrides": overrides, "slack": slack,
           "agree": float(np.mean(gaps == 0))}
    if control is not None:
        low = reference.Reference(conf, params, quant=control)
        low_in, _, _ = _top_inputs(low, tokens, sample, pool)
        picked = np.where(valid, low.top_argmax(low_in), -1)
        out["control_gap"] = float(ref.top_gaps(top_in, picked)[valid].max())
    return out


def _top_inputs(ref, tokens, sample, pool):
    x = ref.bottom(tokens)
    top_in = np.zeros_like(x)
    overrides, slack = 0, -np.inf
    for b, (s, _, n) in enumerate(sample):
        m = pool.metas[s.script]
        fr = [reference.parse_payload(m.kind, m.d, m.k, m.bits, body)
              for body in pool.bodies[s.script][:n]]
        top_in[b, :n], o, sl = reference.compress(
            x[b, :n], m.kind, m.k, m.bits, fr)
        overrides += o
        slack = max(slack, sl)
    return top_in, overrides, float(slack)


def run(cell: dict, seed: int, seconds: float, trace: bool,
        t_process: float, *, tamper: Optional[Callable] = None, control=None,
        report: Optional[dict] = None) -> dict:
    """One run; returns the result line as a dict. `tamper(server)` breaks
    the timed path (tests);
    `control` also reads a lower-precision reference in the program's
    place (`check`); `report` receives the reference's readings."""
    import jax
    from repro.runtime import engine

    conf, traffic = cell["conf"], cell["traffic"]
    serving, cut = conf["serving"], conf["cut_layer"]
    log = CompileLog()
    dev = jax.devices()[0]

    t = time.perf_counter()
    mark = log.mark()
    params = model.init_weights(conf, seed)
    say(f"setup weights: {model.nbytes(params) / 1e9:.3f} GB on device in "
        f"{time.perf_counter() - t:.2f} s; {log.since(mark)}")

    t, mark = time.perf_counter(), log.mark()
    from repro.models.config import SplitConfig
    cfg = model.arch_config(conf).with_(split=SplitConfig(cut_layer=cut))
    plan = traffic_mod.plan(traffic, conf["vocab_size"], serving["max_len"],
                            seed, traffic["preroll_s"] + seconds)
    pool = frames.make_pool(cfg, params, plan)
    say(f"setup frame pool: {len(pool.bodies)} scripts, "
        f"{sum(len(b) for b in pool.bodies)} frames, {len(plan.t_due)} "
        f"sessions planned, in {time.perf_counter() - t:.2f} s; "
        f"{log.since(mark)}")

    # the label owner holds its own layers only; the feature owners'
    # weights leave the chip (the reference makes them again after the
    # window)
    t, mark = time.perf_counter(), log.mark()
    cfg_top, top = model.label_owner(conf, params)
    del params
    from repro.core import wire
    server = build_server(cfg_top, top, serving)
    metas = {}
    for i, m in enumerate(pool.metas):
        metas.setdefault(m, pool.bodies[i][0])
    server.warm([wire.decode_payload(b, m, (1, 1)) for m, b in metas.items()])
    server.arena.reset_slot(0)          # the slot-reset program
    jax.block_until_ready(server.arena.cache)
    if tamper is not None:
        tamper(server)
    say(f"setup label owner: {model.nbytes(top) / 1e9:.3f} GB of weights, "
        f"{model.nbytes(server.arena.cache) / 1e9:.3f} GB arena; compile and "
        f"warm-up of {len(metas)} payload metas x {len(server._buckets)} "
        f"flush buckets in {time.perf_counter() - t:.2f} s; "
        f"{log.since(mark)}")

    drv = driver_mod.Driver(server, pool, plan, serving["capacity"])
    serve = threading.Thread(target=server.serve_loop, daemon=True)
    serve.start()
    t0 = time.perf_counter()
    t_open = t0 + traffic["preroll_s"]
    t_close = t_open + seconds
    drv.start(t0, t_close)
    _sleep_until(t_open)
    setup_s = time.perf_counter() - t_process
    at_open = _server_counts(server)
    mark = log.mark()
    say(f"setup pre-roll: {traffic['preroll_s']:.2f} s of traffic, "
        f"{drv.live} live sessions at open; setup_s {setup_s:.3f}")

    tr = None
    if trace:
        tr = _trace_slice(t_open, seconds)
    _sleep_until(t_close)
    at_close = _server_counts(server)
    window_compiles = log.mark()[1] - mark[1]
    finished_loop = drv.join(10.0)

    drv.close_all()
    server.shutdown()
    serve.join(30.0)
    for r in list(server._readers):
        r.join(10.0)
    leftover = ([r for r in server._readers if r.is_alive()]
                + ([serve] if serve.is_alive() else []))
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    errors = list(server.errors) + ([drv.error] if drv.error else [])
    del server, top
    engine.clear_serving_steps()
    gc.collect()

    r_t = np.asarray(drv.r_t)
    r_sid = np.asarray(drv.r_sid, np.int64)
    r_step = np.asarray(drv.r_step, np.int64)
    r_up = np.asarray(drv.r_up, np.int64)
    r_pay = np.asarray(drv.r_pay, np.int64)
    r_comp = np.asarray(drv.r_comp, np.int64)
    sessions = list(drv.sessions.values())
    in_window = [s for s in sessions if t_open <= s.t_due < t_close]
    failed = [s for s in in_window if s.failed or (
        s.up is not None and not s.done
        and t_close - s.t_sent > REPLY_TIMEOUT_S)]
    ttft = [(s.t_first if s.t_first is not None and s.t_first < t_close
             else t_close) - s.t_due for s in in_window]
    late = np.asarray(drv.late)
    say(f"generator lateness: p95 {_fmt(_percentile(late, 95))} s, max "
        f"{_fmt(late.max() if len(late) else None)} s over {len(late)} "
        f"arrivals; admission FIFO depth at open "
        f"{_depth_at(drv.depth, t_open)}, at close "
        f"{_depth_at(drv.depth, t_close)}")
    say(f"compiles inside the window: {window_compiles}")
    say(f"memory: peak {peak} B on device 0 "
        f"({stats.get('bytes_limit', 0)} B limit)")

    t = time.perf_counter()
    chk = check(conf, model.init_weights(conf, seed), plan, pool, sessions,
                _served_in(r_t, r_sid, r_step, t_open, t_close), seed,
                control)
    say(f"reference: {chk['sessions']} sessions, {chk['positions']} "
        f"positions served in the window, widest gap {chk['gap']:.6g} reference-logit std, "
        f"served token is the reference's best at {chk['agree']:.4f} of "
        f"positions, {chk['overrides']} rows where a frame's choice lay "
        f"outside the near-tie band (widest distance past the reference's "
        f"decision {chk['slack']:.4g} row RMS), in {time.perf_counter() - t:.2f} s"
        + (f"; control {control}: widest gap {chk['control_gap']:.6g}"
           if control else ""))

    if report is not None:
        report.update(chk, fifo_open=_depth_at(drv.depth, t_open),
                      fifo_close=_depth_at(drv.depth, t_close),
                      failed=len(failed), attempted=len(in_window))
    pk = spec.peaks(dev.device_kind) if dev.platform == "tpu" else None
    run_rec = Run(conf=conf, traffic=traffic, peaks=pk, seconds=seconds,
                  t_open=t_open, t_close=t_close, setup_s=setup_s,
                  r_t=r_t, r_sid=r_sid, r_step=r_step, r_up=r_up, r_pay=r_pay,
                  r_comp=r_comp,
                  ttft=ttft, gaps=drv.gaps, sessions=sessions,
                  at_open=at_open, at_close=at_close, trace=tr)
    names = cell["per_layer"] if trace else cell["end_to_end"]
    metrics: Dict[str, dict] = {}
    for m in names:
        v = spec.reader(m["name"]).read(run_rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    lim = cell["limits"]["logit_gap"]["limit"]
    checks = {"logit_gap": {"value": chk["gap"], "limit": lim}}
    correct = (not errors and not leftover and finished_loop
               and window_compiles == 0 and chk["sessions"] > 0
               and chk["gap"] <= lim)
    for e in errors:
        say(f"error: {type(e).__name__}: {e}")
    if leftover:
        say(f"error: {len(leftover)} threads still running")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": len(in_window),
           "failed": len(failed), "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        out["breakdown"] = {"device_ops": trace_mod.top(tr["ops"]),
                            "idle_gaps": tr["idle_gaps"]}
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return out


def _served_in(r_t, r_sid, r_step, lo: float, hi: float) -> dict:
    """Session id -> (first, last + 1) of its positions whose replies
    landed in [lo, hi); a session's replies come in position order."""
    out: dict = {}
    keep = (r_t >= lo) & (r_t < hi)
    for sid, step in zip(r_sid[keep].tolist(), r_step[keep].tolist()):
        a, b = out.get(sid, (step, step))
        out[sid] = (min(a, step), max(b, step + 1))
    return out


def _fmt(x):
    return "n/a" if x is None else f"{x:.4f}"


def _depth_at(depth, t):
    d = 0
    for ti, di in depth:
        if ti > t:
            break
        d = di
    return d


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def _server_counts(server) -> dict:
    return {"flushes": len(server.batch_sizes),
            "batch_sizes": list(server.batch_sizes),
            "stage_s": dict(server.stage_s),
            "stage_tokens": server.stage_tokens,
            "queue_wait_ms_p50": server.registry.histogram(
                "queue_wait_ms").quantile(0.5)}


def _trace_slice(t_open: float, seconds: float) -> dict:
    """Profile a steady slice in the middle third of the window."""
    import jax

    logdir = os.path.join(spec.CHECKOUT, ".bench_trace")
    shutil.rmtree(logdir, ignore_errors=True)
    span = max(1.0, min(3.0, seconds / 3))
    _sleep_until(t_open + seconds / 3)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
        _sleep_until(t0 + span)
    t1 = time.perf_counter()
    jax.profiler.stop_trace()
    pl = trace_mod.planes(logdir)
    shutil.rmtree(logdir, ignore_errors=True)
    out = trace_mod.reduce(pl)
    out["t0"], out["t1"] = t0, t1
    return out
