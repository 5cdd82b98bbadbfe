"""Run one cell with the serve loop's spans on the profiler's clock.

    python3 bench/span_run.py --workload <cell> --seed <n> --seconds <s> \\
        [--tracer 0|1] [--profile 0|1] [--record <path>]

From the root of a checkout, on the chip. A diagnostic beside bench/run.py:
the same run (`harness.run`, as `--trace 0` makes it), with the label
owner's tracer set, before the traffic starts, to a
`repro.obs.trace.Tracer` with the profiler sink and no in-memory events
(`--tracer 1`, the default; `--tracer 0` keeps the disabled one).

- `--profile 1` profiles a slice in the middle third of the window, as
  `--trace 1` does, and reports the device's idle time by the serve-loop
  stage the host was in (bench/spans.py), the mean `server.dispatch`, and
  how many step programs ran inside their flush's dispatch..sync. It needs
  `--tracer 1`. `--record <path>` also writes the slice's first 60 ms, in
  the form of bench/testdata/, to `<path>`.
- Every run reports `flush_cover`: rows stepped over the sessions resident
  in the arena, summed over the window's flushes (the server's
  `flush_fill` histogram and `flush_resident_total` counter, read within
  a few ms of the window's edges).

The last line of stdout is the harness's result with a `spans` key added.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (CHECKOUT, os.path.join(CHECKOUT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: the recorded cut's length (bench/testdata)
RECORD_NS = 60e6


def _counts(server) -> dict:
    reg = server.registry
    return {"t": time.perf_counter(), "flushes": len(server.batch_sizes),
            "rows": reg.histogram("flush_fill").sum,
            "resident": reg.counter("flush_resident_total").value,
            "dispatch_s": server.stage_s["dispatch"]}


def _delta(a: dict, b: dict) -> dict:
    n = b["flushes"] - a["flushes"]
    res = b["resident"] - a["resident"]
    return {"seconds": b["t"] - a["t"], "flushes": n,
            "rows": b["rows"] - a["rows"], "resident": res,
            "flush_cover": (100.0 * (b["rows"] - a["rows"]) / res
                            if res else None),
            "dispatch_us_per_flush": (1e6 * (b["dispatch_s"]
                                             - a["dispatch_s"]) / n
                                      if n else None)}


def _outermost(events):
    """The events not inside another one (a loop's body ops lie inside
    the loop's op): the union of their intervals is the same."""
    out, end = [], -math.inf
    for e in sorted(events, key=lambda e: (e[1], -e[2])):
        if e[1] + e[2] > end:
            out.append(e)
            end = e[1] + e[2]
    return out


def _cut(pl, ns: float):
    """The slice's first `ns`: device modules and outermost ops, the window
    and the serve loop's spans, op names cut to the HLO op name."""
    from bench import spans, trace

    lo, _ = trace.window(pl)
    hi = lo + ns
    out = []
    for p in pl:
        dev = p["name"].startswith(trace.DEVICE_PREFIX)
        lines = []
        for ln in p["lines"]:
            ev = []
            for name, s, d in ln["events"]:
                if dev and ln["name"] not in (trace.OPS_LINE,
                                              trace.MODULES_LINE):
                    continue
                if not dev and not (name == trace.WINDOW
                                    or name.startswith(spans.PREFIX)):
                    continue
                if name == trace.WINDOW:
                    ev.append([name, s, ns])
                elif s < hi and s + d > lo:
                    ev.append([trace.op_name(name) if dev else name, s, d])
            if dev and ln["name"] == trace.OPS_LINE:
                ev = _outermost(ev)
            if ev:
                lines.append({"name": ln["name"], "events": ev})
        if lines:
            out.append({"name": p["name"], "lines": lines})
    return out


class Watch:
    """Attached to the server before the traffic starts (`harness.run`'s
    `tamper` hook): reads the counters at the window's edges and, with
    `profile`, traces a slice of it."""

    def __init__(self, cell: dict, seconds: float, tracer: bool,
                 profile: bool, record):
        self.cell = cell["name"]
        self.preroll = cell["traffic"]["preroll_s"]
        self.seconds, self.tracer, self.profile = seconds, tracer, profile
        self.record = record
        self.out: dict = {}
        self.error = None
        self.thread = None

    def __call__(self, server) -> None:
        if self.tracer:
            from repro.obs.trace import Tracer
            server.tracer = Tracer(profiler=True, record=False)
        self.thread = threading.Thread(target=self._run, args=(server,),
                                       daemon=True)
        self.thread.start()

    def _run(self, server) -> None:
        try:
            self._watch(server, time.perf_counter() + self.preroll)
        except Exception as e:          # reported in the result
            self.error = f"{type(e).__name__}: {e}"

    def _watch(self, server, t_open: float) -> None:
        from bench import harness

        harness._sleep_until(t_open)
        at_open = _counts(server)
        if self.profile:
            self.out["slice"] = self._slice(server, t_open)
        harness._sleep_until(t_open + self.seconds)
        self.out["window"] = _delta(at_open, _counts(server))

    def _slice(self, server, t_open: float) -> dict:
        import jax

        from bench import harness, spans, trace

        logdir = os.path.join(CHECKOUT, ".bench_spans")
        shutil.rmtree(logdir, ignore_errors=True)
        span = max(1.0, min(3.0, self.seconds / 3))
        harness._sleep_until(t_open + self.seconds / 3)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=opts)
        a = _counts(server)
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            harness._sleep_until(a["t"] + span)
        b = _counts(server)
        jax.profiler.stop_trace()
        pl = trace.planes(logdir)
        shutil.rmtree(logdir, ignore_errors=True)
        red = trace.reduce(pl)
        by_span = spans.idle_by_span(pl)
        inside, total = spans.modules_in_flushes(pl)
        offsets = spans.module_offsets(pl)
        out = dict(spans.shares(by_span, red["busy_s"]),
                   busy_s=red["busy_s"], window_s=red["window_s"],
                   idle_s=by_span["idle_s"],
                   dispatch_us=(1e6 * by_span["dispatch_s"]
                                / by_span["dispatch_n"]
                                if by_span["dispatch_n"] else None),
                   dispatch_n=by_span["dispatch_n"],
                   modules_in_flushes=[inside, total],
                   module_lead_us=sorted(a * 1e-3 for a, _ in offsets),
                   module_lag_us=sorted(b * 1e-3 for _, b in offsets),
                   counters=_delta(a, b))
        if self.record:
            cut = _cut(pl, RECORD_NS)
            cut_red = trace.reduce(cut)
            dev = jax.devices()[0]
            rec = {"source": f"one {dev.platform} device ({dev.device_kind}),"
                             f" cell {self.cell}, bench/span_run.py "
                             "--tracer 1 --profile 1; the first 60 ms of the "
                             "traced slice; op names cut to the HLO op name",
                   "expect": {"busy_s": cut_red["busy_s"],
                              "step_modules": trace.seconds_where(
                                  cut_red["modules"],
                                  *spans.STEP_MODULES)[1],
                              **spans.idle_by_span(cut),
                              "modules_in_flushes": list(
                                  spans.modules_in_flushes(cut))},
                   "planes": cut}
            os.makedirs(os.path.dirname(os.path.abspath(self.record)),
                        exist_ok=True)
            with open(self.record, "w") as f:
                json.dump(rec, f)
        return out



def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--tracer", type=int, choices=(0, 1), default=1)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=0)
    ap.add_argument("--record", default=None)
    args = ap.parse_args(argv)
    if args.profile and not args.tracer:
        ap.error("--profile 1 needs --tracer 1")

    from bench import harness, spec

    cell = spec.cell(spec.benchmark(), args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    print(f"span run: cell {args.workload}, seed {args.seed}, "
          f"{args.seconds:g} s window, tracer {args.tracer}, profile "
          f"{args.profile}", flush=True)
    watch = Watch(cell, args.seconds, bool(args.tracer), bool(args.profile),
                  args.record)
    out = harness.run(cell, args.seed, args.seconds, False, T_PROCESS,
                      tamper=watch)
    watch.thread.join(60.0)
    out["spans"] = dict(watch.out, tracer=args.tracer, profile=args.profile,
                        error=watch.error)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
