"""Frame-lifecycle tracing — nestable spans, instant events, Chrome export.

A `Tracer` records what the serving/training stack *did* as a flat list of
events that `obs.export` serializes into Chrome-trace-event JSON (loadable
in Perfetto or `chrome://tracing`). Two event shapes cover everything the
runtime needs:

  * spans — an interval with a name, a track, and args. Emitted either via
    the `span()` context manager (timestamps read from the injected
    `testing.clock.Clock` on entry/exit) or via `complete()` with explicit
    start/end times (how the server turns "this frame was enqueued at t0
    and flushed at t1" into a `server.queue_wait` span without the tracer
    ever blocking anything);
  * instants — a point event (`instant()`): QoS rung moves, ARQ
    retransmits/reconnects, admission rejections, slot admit/evict.

Time is *injected*: a tracer built over a `VirtualClock` (the loadgen
co-simulation) stamps virtual seconds, so two runs at the same seed write
byte-identical trace files — the determinism `tests/test_obs.py` pins,
clean and under `FaultInjector` chaos. Under the default `SystemClock` the
stamps are wall monotonic time and the trace shows real durations.

Tracks: Chrome traces group events by (pid, tid). The runtime's convention
(docs/observability.md) puts the serve loop on tid `SERVE_TID` (0) and each
session on `session_tid(sid)` = sid + 1, so one session's whole lifecycle —
encode, send, queue wait, accept, plus its QoS/ARQ instants — reads as one
horizontal track in Perfetto, with the serve loop's stages (`server.wait`
.. `server.reply`, tiling each loop iteration) on the serve track above
it. Events emitted without an explicit `tid` get a stable per-thread id
(assigned in first-use order, offset far above any session track).

The disabled default is `NULL_TRACER`: every method is a no-op and `span()`
returns a single reusable null context manager, so an uninstrumented hot
path pays one attribute check (`tracer.enabled`) or one empty call. The
overhead is measured and gated in `benchmarks/serve_throughput.py` (the
`obs` section of BENCH_serve.json: tracing-on/off throughput ratio).

Profiler sink: `Tracer(profiler=True)` also mirrors every `span()` into a
`jax.profiler.TraceAnnotation` of the same name and args, opened and
closed with the span. While a JAX profiler session runs, the span then
lands in its `.xplane.pb` on the same clock as the device's ops; with no
session running an annotation costs one small object. `record=False`
keeps no in-memory events at all (for long runs that only want the
profiler's view). Explicitly timed `complete()` spans and instants stay
in memory only: their endpoints were observed on other threads or in the
past, where no annotation can be opened. JAX is imported only when a
sink is asked for (see the import-order note below).
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, TYPE_CHECKING

if TYPE_CHECKING:    # deferred at runtime: `repro.testing.__init__` pulls
    # in `testing.faults` -> `runtime.transport` -> `runtime.server`, and
    # importing that chain from here would re-enter a partially-initialized
    # `repro.obs` when obs is the first repro package imported
    from repro.testing.clock import Clock

# -- span taxonomy (docs/observability.md) ------------------------------------
# the seven frame-lifecycle stages, in wire order
SPAN_CLIENT_ENCODE = "client.encode"    # bottom step + payload pull to host
SPAN_WIRE_SEND = "client.send"          # framing + uplink transmission
SPAN_QUEUE_WAIT = "server.queue_wait"   # enqueue -> flush pickup
SPAN_DECODE = "server.decode"           # host staging (+ mixed-meta decodes)
SPAN_STEP = "server.step"               # donated arena / fused top step
SPAN_REPLY = "server.reply"             # token framing + downlink send
SPAN_ARQ_ACCEPT = "client.arq_accept"   # reply classified + accepted by ARQ

LIFECYCLE_SPANS = (SPAN_CLIENT_ENCODE, SPAN_WIRE_SEND, SPAN_QUEUE_WAIT,
                   SPAN_DECODE, SPAN_STEP, SPAN_REPLY, SPAN_ARQ_ACCEPT)

# the serve loop's own stages; with decode/step/reply they tile one loop
# iteration on `SERVE_TID` (docs/observability.md)
SPAN_WAIT = "server.wait"               # blocked on the batching queue
SPAN_PREPARE = "server.prepare"         # queue-wait accounting .. arena ops
SPAN_DISPATCH = "server.dispatch"       # step call until it returns (child
#                                         of server.step)
SPAN_SYNC = "server.sync"               # token rows pulled to host (child
#                                         of server.step)

SERVE_LOOP_SPANS = (SPAN_WAIT, SPAN_PREPARE, SPAN_DECODE, SPAN_STEP,
                    SPAN_DISPATCH, SPAN_SYNC, SPAN_REPLY)

# instant events
EVT_QOS_TRANSITION = "qos.transition"   # (k, bits) rung move
EVT_ARQ_RETRANSMIT = "arq.retransmit"   # timeout/error-triggered replay
EVT_ARQ_RECONNECT = "arq.reconnect"     # fresh connection onto the session
EVT_ADMISSION_REJECT = "admission.reject"   # arrival turned away
EVT_SLOT_ADMIT = "slot.admit"           # session pinned to an arena slot
EVT_SLOT_EVICT = "slot.evict"           # closed session's slot reclaimed

INSTANT_EVENTS = (EVT_QOS_TRANSITION, EVT_ARQ_RETRANSMIT, EVT_ARQ_RECONNECT,
                  EVT_ADMISSION_REJECT, EVT_SLOT_ADMIT, EVT_SLOT_EVICT)

#: the serve loop's track; sessions live on `session_tid(sid)`
SERVE_TID = 0
#: auto-assigned per-thread tracks start here, clear of any session id
_THREAD_TID_BASE = 1_000_000


def session_tid(sid: int) -> int:
    """Track id of session `sid` — one Perfetto row per session."""
    return sid + 1


class _NullSpan:
    """Reusable no-op context manager (`NULL_TRACER.span(...)` result)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def begin(self, t0: float):
        return self

    def note(self, **args) -> None:
        pass

    def end(self, t1: float) -> None:
        pass


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Disabled tracer — the default everywhere. All methods are no-ops;
    hot paths additionally guard arg construction on `tracer.enabled`."""

    enabled = False

    def span(self, name: str, *, cat: str = "lifecycle",
             tid: Optional[int] = None, **args):
        return _NULL_SPAN

    def complete(self, name: str, t0: float, t1: float, *,
                 cat: str = "lifecycle", tid: Optional[int] = None,
                 **args) -> None:
        pass

    def instant(self, name: str, *, cat: str = "event",
                tid: Optional[int] = None, **args) -> None:
        pass

    def name_track(self, tid: int, name: str) -> None:
        pass

    def events(self) -> List[dict]:
        return []


#: process-wide disabled tracer; components default to this
NULL_TRACER = NullTracer()


class _Span:
    """Context manager emitted by `Tracer.span` — stamps entry/exit.

    `begin(t0)` / `end(t1)` open and close it at stamps the caller read
    itself (the serve loop reads each stage boundary once and feeds the
    same stamp to its own accounting); the profiler annotation, if any,
    opens and closes with them."""

    __slots__ = ("_tracer", "_name", "_cat", "_tid", "_args", "_t0",
                 "_mirror")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 tid: Optional[int], args: dict):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._tid = tid
        self._args = args
        self._t0 = 0.0
        self._mirror = None

    def __enter__(self):
        return self.begin(self._tracer._clock.monotonic())

    def __exit__(self, *exc):
        self.end(self._tracer._clock.monotonic())
        return False

    def begin(self, t0: float) -> "_Span":
        self._t0 = t0
        annotation = self._tracer._annotation
        if annotation is not None:
            self._mirror = annotation(self._name, **self._args)
            self._mirror.__enter__()
        return self

    def note(self, **args) -> None:
        """Add args known only after the span opened (the rows a wait
        picked up)."""
        self._args.update(args)
        if self._mirror is not None:
            self._mirror.set_metadata(**args)

    def end(self, t1: float) -> None:
        if self._mirror is not None:
            self._mirror.__exit__(None, None, None)
            self._mirror = None
        self._tracer.complete(self._name, self._t0, t1, cat=self._cat,
                              tid=self._tid, **self._args)


class Tracer:
    """Collects span/instant events against an injected clock.

    Thread-safe: the threaded runtime appends from reader threads, client
    threads, and the serve loop; the single-threaded loadgen appends in
    event-loop order (which, with a `VirtualClock`, makes the exported
    JSON a deterministic function of the seed).

    `profiler=True` adds the profiler sink (module docstring);
    `record=False` drops the in-memory event list.
    """

    enabled = True

    def __init__(self, clock: Optional["Clock"] = None, *, pid: int = 0,
                 profiler: bool = False, record: bool = True):
        if clock is None:
            from repro.testing.clock import SYSTEM_CLOCK
            clock = SYSTEM_CLOCK
        self._clock = clock
        self.pid = pid
        self.record = record
        self._annotation = None
        if profiler:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
        self._events: List[dict] = []
        self._lock = threading.Lock()
        self._thread_tids: Dict[int, int] = {}
        self._named_tracks: Dict[int, str] = {}

    # -- emission ------------------------------------------------------------

    def span(self, name: str, *, cat: str = "lifecycle",
             tid: Optional[int] = None, **args) -> _Span:
        """Nestable span: stamps the clock on enter and exit."""
        return _Span(self, name, cat, tid, args)

    def complete(self, name: str, t0: float, t1: float, *,
                 cat: str = "lifecycle", tid: Optional[int] = None,
                 **args) -> None:
        """Explicitly-timed span [t0, t1] — for intervals whose endpoints
        were observed elsewhere (queue wait, modeled service time)."""
        if not self.record:
            return
        evt = {"name": name, "cat": cat, "ph": "X", "pid": self.pid,
               "tid": self._resolve_tid(tid), "ts": t0,
               "dur": max(0.0, t1 - t0)}
        if args:
            evt["args"] = args
        with self._lock:
            self._events.append(evt)

    def instant(self, name: str, *, cat: str = "event",
                tid: Optional[int] = None, **args) -> None:
        if not self.record:
            return
        evt = {"name": name, "cat": cat, "ph": "i", "s": "t",
               "pid": self.pid, "tid": self._resolve_tid(tid),
               "ts": self._clock.monotonic()}
        if args:
            evt["args"] = args
        with self._lock:
            self._events.append(evt)

    def name_track(self, tid: int, name: str) -> None:
        """Label a (pid, tid) track — rendered as the row name in Perfetto.
        Idempotent: the first name wins."""
        if not self.record:
            return
        with self._lock:
            if tid in self._named_tracks:
                return
            self._named_tracks[tid] = name
            self._events.append({"name": "thread_name", "ph": "M",
                                 "pid": self.pid, "tid": tid, "ts": 0.0,
                                 "args": {"name": name}})

    # -- inspection ----------------------------------------------------------

    def events(self) -> List[dict]:
        """Snapshot of the raw event list (ts/dur in clock seconds)."""
        with self._lock:
            return list(self._events)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    # -- internals -----------------------------------------------------------

    def _resolve_tid(self, tid: Optional[int]) -> int:
        if tid is not None:
            return tid
        ident = threading.get_ident()
        with self._lock:
            got = self._thread_tids.get(ident)
            if got is None:
                got = _THREAD_TID_BASE + len(self._thread_tids)
                self._thread_tids[ident] = got
            return got
