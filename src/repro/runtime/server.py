"""Streaming server — the label owner serving N concurrent sessions.

One reader thread per connection parses `core.wire` frames off the byte
transport and feeds a `BatchingQueue`; the single serve loop flushes the
queue under the max-batch/max-wait policy and drives the device-resident
session-slot arena (`runtime.arena.SlotArena`):

  * each session is pinned to one arena slot at admission — its KV cache
    and position are rows of pre-allocated batched device arrays for the
    session's whole life (reconnects keep the slot; a closed session's slot
    is reset and reused);
  * each flush, payloads are staged into cached per-(meta, bucket) host
    buffers — padded to the nearest power-of-two flush bucket, NOT to
    `max_batch`, so a ragged flush stages < 2x its wire bytes instead of
    the old `max_batch/fill` amplification — and the host touches only
    the compressed wire leaves, never a dense activation;
  * a single-meta flush (every pure-compressor population) runs ONE fused
    decode+step dispatch (`steps.make_fused_decode_step`): the payload
    scatter-decodes into `xbuf[slots]` and the donated whole-arena top
    step runs in the same jit program, with only the token rows coming
    back to host. Mixed-meta flushes fall back to per-meta device decodes
    (`protocol.server_decode_to_slots`) followed by the donated arena
    step — two dispatches, same numerics.

Token replies stream back as frames; per-session byte accounting is taken
from the real frame sizes at receipt. The hot-path design and its
donation/aliasing invariants are documented in docs/performance.md.

Fault tolerance: a malformed frame (typed `wire.WireError` — CRC failure,
bad counts, truncation) no longer kills a reader thread silently. The reader
replies with an `error` frame naming the defect and retires the connection;
the *session* survives, and the client reconnects over a fresh channel and
replays from its last unacknowledged sequence number. Stop-and-wait dedup in
the serve loop (`Session.last_seq` / `last_reply`) re-acks replayed frames
without re-running the top-model step, so a KV cache never double-advances.
"""
from __future__ import annotations

import collections
import threading
from typing import Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import wire
from repro.core.payload import Payload
from repro.obs.registry import DEFAULT_REGISTRY, MetricsRegistry
from repro.obs.trace import (EVT_SLOT_ADMIT, EVT_SLOT_EVICT, NULL_TRACER,
                             SERVE_TID, SPAN_DECODE, SPAN_DISPATCH,
                             SPAN_PREPARE, SPAN_QUEUE_WAIT, SPAN_REPLY,
                             SPAN_STEP, SPAN_SYNC, SPAN_WAIT, session_tid)
from repro.runtime import steps
from repro.runtime.arena import SlotArena
from repro.runtime.batching import BatchingQueue
from repro.runtime.session import Session
from repro.split import protocol
from repro.testing.clock import Clock, SYSTEM_CLOCK

#: `Session.host_state` between the LRU-eviction decision (reader thread,
#: under the server lock) and the serve loop's fetch of the row to host —
#: marks "evicted, state still on device". A frame arriving in that window
#: re-admits the session; FIFO ordering of the arena-op queue guarantees
#: the fetch runs before the restore, so the restore always writes real
#: host state.
_EVICTING = object()


class _Stage:
    """One stage of the serve loop (`server.wait` .. `server.reply`).

    Stages share the server's stamp cursor `_t`: a stage opens at the
    stamp that closed the one before it, and a leaf stage reads the clock
    once as it closes. So each boundary is read once, and that one stamp
    feeds `stage_s`, the tracer's span and its profiler annotation alike;
    the stages tile the loop with no gap. A parent stage (`server.step`)
    closes at its last child's stamp. `stage_s` keys drop the `server.`
    prefix."""

    __slots__ = ("_srv", "_key", "_leaf", "span", "_t0")

    def __init__(self, srv: "StreamingServer", name: str, leaf: bool = True,
                 **args):
        self._srv = srv
        self._key = name.split(".", 1)[1]
        self._leaf = leaf
        self.span = srv.tracer.span(name, tid=SERVE_TID, **args)

    def __enter__(self) -> "_Stage":
        self._t0 = self._srv._t
        self.span.begin(self._t0)
        return self

    def __exit__(self, *exc):
        srv = self._srv
        if self._leaf:
            srv._t = srv.clock.monotonic()
        self.span.end(srv._t)
        srv.stage_s[self._key] += srv._t - self._t0
        return False


def jit_serving_steps(top_step: Callable, *, dtype,
                      backend: Optional[str] = None, mesh=None):
    """The server's jitted step pair: (donated plain arena step, donated
    fused decode+step). Split out so `runtime.engine` can cache the pair
    across `run_streaming` calls — jit compile caches live on the wrapped
    callable, and rebuilding the pair per run re-pays every per-(meta,
    bucket) compile the warm loop just amortized. `mesh` is the one
    `top_step` was built with."""
    top = jax.jit(top_step, donate_argnums=(2,))
    fused = jax.jit(
        steps.make_fused_decode_step(top_step, dtype=dtype, backend=backend,
                                     mesh=mesh),
        donate_argnums=(1, 4))
    return top, fused


class FrameServerBase:
    """Connection plumbing shared by the serving and training servers:
    one reader thread per attached channel, typed rejection of malformed
    frames with an `error` frame + connection retire (never a dead
    thread), a session registry that survives reconnects, and the
    queue-close lifecycle.

    Subclasses call `_init_connections` from __init__, implement
    `_new_session(sid, endpoint)`, and set `direction` (the label protocol
    violations are reported under).
    """

    direction = "serving"

    def _init_connections(self, queue: BatchingQueue,
                          tracer=NULL_TRACER,
                          registry: Optional[MetricsRegistry] = None) -> None:
        self.queue = queue
        self.sessions: Dict[int, Session] = {}
        self._lock = threading.Lock()
        # admissions blocked on a full arena wait here; notified on session
        # close and after every flush's pending-frame drain (both can make
        # a slot reclaimable/evictable)
        self._slot_cv = threading.Condition(self._lock)
        self._readers: List[threading.Thread] = []
        self._open_readers = 0
        self.errors: List[BaseException] = []   # reader / serve failures
        self.faults_detected = 0    # malformed frames rejected (connections
        #                             retired with an error frame, not dead)
        self.expected_sessions: int = 0     # set by the engine; the serve
        #   loop must not stop before this many sessions exist AND closed
        #   (a corrupt first frame can retire a connection before its
        #   session was ever created — the reconnect needs a live queue)
        self.tracer = tracer
        self.registry = registry if registry is not None else DEFAULT_REGISTRY
        # pre-bound per-frame instruments: the reader/serve hot paths pay a
        # lock + add, never a registry dict lookup
        reg = self.registry
        self._m_frames_up = reg.counter("frames_total", party="server",
                                        direction="up")
        self._m_payload_up = reg.counter("payload_bytes_total",
                                         party="server", direction="up")
        self._m_framing_up = reg.counter("framing_bytes_total",
                                         party="server", direction="up")
        self._m_frames_down = reg.counter("frames_total", party="server",
                                          direction="down")
        self._m_bytes_down = reg.counter("wire_bytes_total", party="server",
                                         direction="down")
        self._m_faults = reg.counter("faults_detected_total", party="server")
        self._m_dups = reg.counter("duplicates_total", party="server")
        self._m_fill = reg.histogram("flush_fill")
        self._m_qwait = reg.histogram("queue_wait_ms")
        self._m_depth = reg.gauge("queue_depth")
        # (sid, seq) -> enqueue clock time; popped at flush into the
        # `server.queue_wait` span / `queue_wait_ms` histogram
        self._enq_ts: Dict = {}

    def _new_session(self, sid: int, endpoint) -> Session:
        raise NotImplementedError

    def _before_enqueue(self, sess: Session) -> None:
        """Hook run after a payload frame is accepted, before it enters the
        batching queue. The serving subclass pins the session's device
        residency and bumps its in-flight frame count here; the training
        server needs neither."""

    def _count_frame_up(self, sess: Session, frame) -> None:
        """Byte accounting for one accepted uplink frame: the session's
        legacy `SessionStats` plus the registry's labeled counters."""
        sess.stats.count_up(frame.header_nbytes, frame.payload_nbytes)
        self._m_frames_up.inc()
        self._m_payload_up.inc(frame.payload_nbytes)
        self._m_framing_up.inc(frame.header_nbytes)

    def _note_enqueue(self, sess: Session, frame) -> None:
        """Stamp a successfully-enqueued frame; `_process` pops the stamp
        into the `server.queue_wait` span and `queue_wait_ms` histogram.
        (dict set/pop are GIL-atomic — reader threads write, serve loop
        pops)."""
        self._enq_ts[(sess.id, frame.seq)] = self.queue.clock.monotonic()

    def _count_frame_down(self, sess: Session, nbytes: int) -> None:
        sess.stats.count_down(nbytes)
        self._m_frames_down.inc()
        self._m_bytes_down.inc(nbytes)

    def attach(self, endpoint) -> threading.Thread:
        """Register a client channel and start its frame-reader thread.

        Called once per client at startup and again for each reconnect — a
        resuming client gets a fresh connection onto its existing session.
        """
        with self._lock:
            self._open_readers += 1
        t = threading.Thread(target=self._read_loop, args=(endpoint,),
                             daemon=True)
        self._readers.append(t)
        t.start()
        return t

    def shutdown(self) -> None:
        """Close the admission queue; the serve loop drains, then exits.
        The engine calls this after every client thread has finished — the
        guaranteed stop even if a session's CLOSE frame was lost in chaos."""
        self.queue.close()

    def _reject(self, endpoint, sid_seen, exc: wire.WireError) -> None:
        """Name the defect in an error frame and retire the connection,
        keeping the session (the client reconnects and replays). A fault
        before any valid frame has no session to charge."""
        with self._lock:
            self.faults_detected += 1
            sess = (self.sessions.get(sid_seen)
                    if sid_seen is not None else None)
            if sess is not None:
                sess.stats.faults_detected += 1
        self._m_faults.inc()
        endpoint.send(wire.encode_error_frame(
            sid_seen if sid_seen is not None else 0, 0,
            wire.error_code(exc), str(exc)))

    def _read_loop(self, endpoint) -> None:
        sid_seen = None             # session observed on THIS connection
        try:
            while True:
                try:
                    frame = endpoint.recv_frame(timeout=0.1)
                except wire.WireError as e:
                    self._reject(endpoint, sid_seen, e)
                    return
                if frame is None:
                    continue
                if frame.kind == wire.FRAME_CLOSE:
                    with self._lock:
                        if frame.session in self.sessions:
                            self.sessions[frame.session].closed = True
                        self._slot_cv.notify_all()
                    return
                if frame.kind == wire.FRAME_ERROR:
                    return              # peer abandoned this connection
                if frame.kind != wire.FRAME_PAYLOAD:
                    raise wire.WireError(
                        f"unexpected frame kind {frame.kind} on the "
                        f"{self.direction} up direction")
                sid_seen = frame.session
                sess = self._session_for(frame.session, endpoint)
                self._count_frame_up(sess, frame)
                self._before_enqueue(sess)
                try:
                    self.queue.put((sess, frame))
                except RuntimeError:
                    return              # server shut down under us
                self._note_enqueue(sess, frame)
        except wire.WireError as e:     # protocol violation from a valid frame
            self._reject(endpoint, sid_seen, e)
        except BaseException as e:      # surfaced by the engine
            with self._lock:
                self.errors.append(e)
        finally:
            with self._lock:
                self._open_readers -= 1
                # natural completion: every connection retired AND every
                # expected session exists and closed. A reader retired by a
                # fault (possibly before its session was even created)
                # holds the queue open for the reconnect; the engine's
                # shutdown() after the client joins is the backstop.
                done = (self._open_readers == 0
                        and len(self.sessions) >= self.expected_sessions
                        and all(s.closed for s in self.sessions.values()))
            if done:
                self.queue.close()          # serve loop drains, then exits

    def pump(self, endpoint, sid_seen: Optional[int] = None):
        """Single-threaded counterpart of `_read_loop`: drain every frame
        currently available on `endpoint` without blocking, enqueueing
        payload frames exactly as the reader thread would.

        Returns `(status, sid_seen)` — the caller (a virtual-clock event
        loop, `runtime.loadgen`) owns the connection lifecycle the reader
        thread normally owns: `status` is `"open"` (keep pumping this
        connection later), `"retired"` (a malformed frame was rejected
        with an error frame, or the peer abandoned the connection — stop
        pumping it; the session survives for a reconnect), or `"closed"`
        (the session's CLOSE frame arrived). `sid_seen` must be passed
        back on the next pump of the same connection so a fault is
        charged to the right session, mirroring `_read_loop`'s per-
        connection state.
        """
        while True:
            try:
                frame = endpoint.recv_frame(timeout=0.0)
            except wire.WireError as e:
                self._reject(endpoint, sid_seen, e)
                return "retired", sid_seen
            if frame is None:
                return "open", sid_seen
            if frame.kind == wire.FRAME_CLOSE:
                with self._lock:
                    if frame.session in self.sessions:
                        self.sessions[frame.session].closed = True
                    self._slot_cv.notify_all()
                return "closed", sid_seen
            if frame.kind == wire.FRAME_ERROR:
                return "retired", sid_seen      # peer abandoned this conn
            if frame.kind != wire.FRAME_PAYLOAD:
                e = wire.WireError(
                    f"unexpected frame kind {frame.kind} on the "
                    f"{self.direction} up direction")
                self._reject(endpoint, sid_seen, e)
                return "retired", sid_seen
            sid_seen = frame.session
            sess = self._session_for(frame.session, endpoint)
            self._count_frame_up(sess, frame)
            self._before_enqueue(sess)
            self.queue.put((sess, frame))       # QueueFull surfaces to caller
            self._note_enqueue(sess, frame)

    def _session_for(self, sid: int, endpoint) -> Session:
        with self._lock:
            sess = self.sessions.get(sid)
            if sess is None:
                sess = self._new_session(sid, endpoint)
                self.sessions[sid] = sess
            else:
                sess.endpoint = endpoint    # replies follow the latest conn
            return sess


class StreamingServer(FrameServerBase):
    """Top-model serving engine over framed byte channels.

    `top_step` must be an arena-shaped step (`steps.make_arena_top_step`,
    built with the same `mesh` passed here): it is jitted with the arena
    cache DONATED, so every flush updates the slot arrays in place.
    `capacity` bounds concurrently-RESIDENT sessions; admission beyond it
    reclaims a closed session's slot, then (with `evict_idle`) LRU-evicts
    an idle session's row to host — the evicted session re-admits
    transparently on its next frame — and only blocks/raises
    (`admit_timeout`) when every slot holds an in-flight session. The
    engine sets `capacity` to the expected concurrent client count, at
    which point neither eviction nor blocking ever triggers.
    """

    def __init__(self, params, top_step: Optional[Callable],
                 make_cache: Callable,
                 *, max_batch: int = 8, max_wait: float = 0.01,
                 dtype=jnp.float32, capacity: Optional[int] = None,
                 x_shape=None, backend: Optional[str] = None,
                 jit_steps=None, clock: Clock = SYSTEM_CLOCK,
                 mesh=None, evict_idle: bool = True,
                 admit_timeout: float = 5.0,
                 tracer=NULL_TRACER,
                 registry: Optional[MetricsRegistry] = None):
        self.params = params
        self.clock = clock
        # `jit_steps` (a `jit_serving_steps` pair) lets the engine share
        # compiled programs across runs; direct construction from a bare
        # arena step keeps working and jits here.
        if jit_steps is None:
            jit_steps = jit_serving_steps(top_step, dtype=dtype,
                                          backend=backend, mesh=mesh)
        self.top_step, self._fused_step = jit_steps
        self.dtype = dtype
        self.backend = backend              # sparse-decode backend dispatch
        self.batch_sizes: List[int] = []    # flush fill history
        # serve-loop seconds by stage (`_Stage`, on `clock`): blocked on
        # the queue, prepare, staging (+ mixed-meta decodes), step
        # (= dispatch + sync), reply
        self.stage_s = {"decode": 0.0, "step": 0.0, "reply": 0.0,
                        "wait": 0.0, "prepare": 0.0, "dispatch": 0.0,
                        "sync": 0.0}
        self.stage_tokens = 0               # tokens served by those flushes
        #   (normalizes stage_s to per-token stage costs in the bench)
        self.pickups = 0                    # batches taken off the queue;
        #   a pickup's index is the `flush` arg of its spans
        self._t = 0.0                       # `_Stage` stamp cursor
        self._t_pickup: Optional[float] = None  # stamp that closed the
        #   `server.wait` whose batch `_process` serves next
        self._init_connections(BatchingQueue(max_batch, max_wait,
                                             clock=clock),
                               tracer=tracer, registry=registry)
        # sessions resident in the arena, summed over the flushes that
        # step (over `flush_fill`'s sum of rows: the share of resident
        # sessions each flush serves)
        self._m_resident = self.registry.counter("flush_resident_total")
        if tracer.enabled:
            tracer.name_track(SERVE_TID, "serve loop")
        self.arena: Optional[SlotArena] = None
        self._make_cache = make_cache
        self._capacity = capacity or max_batch
        self._mesh = mesh
        self.evict_idle = evict_idle
        self.admit_timeout = admit_timeout
        if x_shape is not None:             # else: built lazily from the
            self.arena = SlotArena(make_cache, self._capacity, x_shape,
                                   dtype, mesh=mesh)  # first payload's meta.d
        # FIFO free deque: O(1) admission (the old list.pop(0) was
        # O(capacity)) and freed slots cycle to the BACK, so slot reuse
        # walks every row instead of hammering the coldest id — a
        # reuse-after-close bug now surfaces within `capacity` admissions
        self._free_slots: Deque[int] = collections.deque(
            range(self._capacity))
        # ordered arena mutations ("reset" | "fetch" | "restore"), applied
        # by the serve loop before the next flush touches the arena — every
        # row write is serialized with the donated step, and FIFO order
        # guarantees an eviction's fetch lands before any re-admission's
        # restore of the same session
        self._arena_ops: List[Tuple] = []
        # flush-size buckets: powers of two up to max_batch (plus max_batch
        # itself when it is not one) — each (meta, bucket) decode/fused
        # program compiles once, and ragged fills pad < 2x
        self._buckets = sorted(
            {1 << i for i in range(max_batch.bit_length())
             if (1 << i) <= max_batch} | {max_batch})
        self._staging: Dict = {}            # (meta, bucket, leaf) -> np buf
        self.host_bytes = {"staged": 0, "wire": 0}

    def _ensure_arena(self, d: int) -> None:
        if self.arena is None:
            self.arena = SlotArena(self._make_cache, self._capacity,
                                   (1, 1, d), self.dtype, mesh=self._mesh)

    # -- slot lifecycle (admission / reclaim / evict / re-admit) -------------

    def _push_free(self, slot: int) -> None:
        """Freed slots go to the BACK of the deque (cycling; see __init__)."""
        self._free_slots.append(slot)

    def compact_free_slots(self) -> None:
        """Free-list compaction: restore ascending issue order. The serve
        loop runs this whenever the arena goes fully idle, so a long-lived
        server's slot ids don't drift into a permanently shuffled order
        (admission bursts then fill rows — and mesh row shards — from the
        bottom up instead of in historical close order)."""
        with self._lock:
            self._free_slots = collections.deque(sorted(self._free_slots))

    def _assign_slot_locked(self, sid: int) -> int:
        """Take a free slot, else reclaim a closed session's, else
        LRU-evict an idle session's row to host, else block on the slot
        condvar until `admit_timeout` (through `self.clock`, so a
        VirtualClock run degrades to an immediate arena-full error instead
        of deadlocking a single-threaded pump). Called under `self._lock`;
        the wait releases it."""
        deadline = None
        while True:
            if self._free_slots:
                return self._free_slots.popleft()
            for sess in self.sessions.values():
                # reclaim a closed session's slot; the template reset is
                # applied by the serve loop (never raced with the step)
                if sess.closed and sess.slot >= 0:
                    slot, sess.slot = sess.slot, -1
                    self._arena_ops.append(("reset", None, slot))
                    self.registry.counter("slot_reclaims_total").inc()
                    self.tracer.instant(EVT_SLOT_EVICT, tid=SERVE_TID,
                                        sid=sess.id, slot=slot)
                    return slot
            if self.evict_idle:
                cand = None
                for sess in self.sessions.values():
                    # evictable = resident, idle, and fully materialized:
                    # `host_state is not None` means a fetch or restore for
                    # this session is still queued/in flight — re-evicting
                    # now would stamp the sentinel over real saved state
                    # and lose the row (the serve loop clears host_state
                    # when the restore lands)
                    if (sess.slot >= 0 and not sess.closed
                            and sess.pending == 0
                            and sess.host_state is None
                            and sess.id != sid
                            and (cand is None
                                 or sess.last_active < cand.last_active)):
                        cand = sess
                if cand is not None:
                    # LRU eviction: the row moves to host (serve loop runs
                    # the fetch before anything overwrites the row), and
                    # the session re-admits on its next frame
                    slot, cand.slot = cand.slot, -1
                    cand.host_state = _EVICTING
                    self._arena_ops.append(("fetch", cand, slot))
                    self._arena_ops.append(("reset", None, slot))
                    self.registry.counter("slot_evictions_total").inc()
                    self.tracer.instant(EVT_SLOT_EVICT, tid=SERVE_TID,
                                        sid=cand.id, slot=slot)
                    return slot
            now = self.clock.monotonic()
            if deadline is None:
                deadline = now + self.admit_timeout
            if now >= deadline:
                raise RuntimeError(
                    f"session {sid}: arena full ({self._capacity} slots, "
                    f"none closed or idle within {self.admit_timeout:.1f}s)"
                    f" — raise `capacity` toward the expected concurrent "
                    f"session count")
            self.clock.cv_wait(self._slot_cv, deadline - now)

    def _ensure_resident(self, sess: Session) -> None:
        """Re-admit an evicted session (under `self._lock`): assign a row
        (possibly evicting another idle session) and queue the restore —
        FIFO-after its own eviction's fetch, so the serve loop always
        writes back real host state. The restored row carries the exact
        pre-eviction KV/position, and the untouched `last_seq`/`last_reply`
        ARQ state keeps dedup working across the gap: a retransmit of the
        last pre-eviction frame is re-acked from the cached reply, never
        re-stepped — an evicted-then-readmitted cache cannot double-advance.
        """
        if sess.slot >= 0 or sess.closed or sess.host_state is None:
            return
        slot = self._assign_slot_locked(sess.id)
        sess.slot = slot
        self._arena_ops.append(("restore", sess, slot))
        self.registry.counter("slot_readmissions_total").inc()
        self.tracer.instant(EVT_SLOT_ADMIT, tid=SERVE_TID, sid=sess.id,
                            slot=slot)

    def _before_enqueue(self, sess: Session) -> None:
        """Serving-side enqueue hook: pin residency for the frame about to
        enter the queue and count it in flight — `pending > 0` makes the
        session ineligible for eviction until the flush that serves the
        frame drains it."""
        with self._lock:
            self._ensure_resident(sess)
            sess.pending += 1
            sess.last_active = self.clock.monotonic()

    def _new_session(self, sid: int, endpoint) -> Session:
        # called under self._lock (from _session_for)
        slot = self._assign_slot_locked(sid)
        self.registry.counter("slot_admits_total").inc()
        self.tracer.instant(EVT_SLOT_ADMIT, tid=SERVE_TID, sid=sid,
                            slot=slot)
        if self.tracer.enabled:
            self.tracer.name_track(session_tid(sid), f"session {sid}")
        return Session(id=sid, slot=slot, endpoint=endpoint,
                       last_active=self.clock.monotonic())

    def _apply_arena_ops(self, ops) -> None:
        """Run queued row mutations (eviction fetches, template resets,
        re-admission restores) on the serve-loop thread, in FIFO order,
        before the flush's step touches the arena. With no arena yet (no
        payload has sized it), no row was ever written: a fetch degrades
        to a fresh template and reset/restore are no-ops."""
        for kind, sess, slot in ops:
            if self.arena is None:
                if kind == "fetch":
                    sess.host_state = self._make_cache()
                elif kind == "restore":
                    sess.host_state = None
                continue
            if kind == "fetch":
                sess.host_state = self.arena.fetch_slot(slot)
            elif kind == "restore":
                state = sess.host_state
                assert state is not None and state is not _EVICTING, \
                    "restore ordered before its eviction's fetch"
                self.arena.restore_slot(slot, state)
                sess.host_state = None
            else:
                self.arena.reset_slot(slot)

    # -- serving -------------------------------------------------------------

    def serve_loop(self) -> None:
        """Flush/process until every connection has closed and drained.

        A flush that raises ends the loop: the exception goes to `errors`
        (the engine raises it), the queue closes, and every session gets
        an error frame, so a client blocked on its reply fails now instead
        of waiting out its reply timeout."""
        try:
            self._t = self.clock.monotonic()
            while True:
                with _Stage(self, SPAN_WAIT, flush=self.pickups) as wait:
                    batch = self.queue.get_batch(idle_timeout=0.05)
                    while not batch and not self.queue.drained:
                        batch = self.queue.get_batch(idle_timeout=0.05)
                    wait.span.note(n=len(batch))
                if not batch:
                    return
                self._t_pickup = self._t
                self._process(batch)
        except Exception as e:          # surfaced by the engine
            with self._lock:
                self.errors.append(e)
                sessions = list(self.sessions.values())
            self.queue.close()
            msg = f"serve loop failed: {type(e).__name__}"
            for sess in sessions:
                sess.endpoint.send(wire.encode_error_frame(
                    sess.id, 0, wire.ERR_PROTOCOL, msg))
        finally:
            # a stopped server gives the arena's device memory back, even
            # while something (a load generator, a test) still holds it
            if self.arena is not None:
                self.arena.release()

    def warm(self, example_payloads) -> None:
        """Compile every hot-loop jit before the serving clock starts.

        For each example payload (one per distinct client compressor,
        encoded from a probe activation) and each flush-size bucket, runs
        the bucketed group decode aimed entirely at the scratch row AND
        the fused decode+step (all-inactive, so no session state is
        perturbed), then one plain arena step for the mixed-meta path —
        shapes match both serve paths exactly, and the first real flush of
        any fill pays zero compile time.
        """
        for p in example_payloads:
            self._ensure_arena(p.meta.d)
            inactive = jnp.zeros((self.arena.capacity,), bool)
            for size in self._buckets:
                slots = np.full(size, self.arena.capacity, np.int64)
                stacked, slots = self._stack_group(p.meta, [p] * size,
                                                   slots, size)
                self.arena.xbuf = protocol.server_decode_to_slots(
                    self.arena.xbuf, stacked, slots, dtype=self.dtype,
                    backend=self.backend, mesh=self._mesh)
                _, self.arena.xbuf, self.arena.cache = self._fused_step(
                    self.params, self.arena.xbuf, stacked, slots,
                    self.arena.cache, inactive)
        if self.arena is None:
            return
        tokens, self.arena.cache = self.top_step(
            self.params, self.arena.xbuf, self.arena.cache,
            jnp.zeros((self.arena.capacity,), bool))
        jax.block_until_ready(tokens)
        self.host_bytes = {"staged": 0, "wire": 0}   # warm traffic is free

    def _dedup(self, items) -> List:
        """Stop-and-wait ARQ filter: the client never has two frames in
        flight, so any seq above the last processed one is fresh progress
        and anything at or below it is a replay. A replay of the last
        processed seq is re-acked from the cached reply bytes (the step
        must NOT re-run — it would advance the KV cache again); anything
        older is dropped. Both cases count as duplicates.
        """
        fresh = []
        for sess, frame in items:
            if frame.seq > sess.last_seq:
                fresh.append((sess, frame))
                continue
            sess.stats.duplicates += 1
            self._m_dups.inc()
            if frame.seq == sess.last_seq and sess.last_reply is not None:
                sess.endpoint.send(sess.last_reply)
                self._count_frame_down(sess, len(sess.last_reply))
        return fresh

    def _bucket(self, n: int) -> int:
        """Smallest flush-size bucket holding `n` rows."""
        return next(b for b in self._buckets if b >= n)

    def _stack_group(self, meta, group, slots: np.ndarray, size: int):
        """Stack one meta-group's wire leaves into the cached
        (meta, bucket) staging buffers, zero-padding to `size` rows aimed
        at the arena's scratch slot. Returns (stacked Payload, (size,)
        slot vector). Pad rows are zeros, never an alias of a live
        session's arrays (the pre-arena loop duplicated items[0]'s cache
        reference into pad slots — a stale-aliasing footgun this template
        removes). Buffer reuse across flushes is safe: every flush forces
        its token rows to host before returning, which drains the device
        work that read the previous staging contents, and jax copies host
        operands at dispatch."""
        n = len(group)
        leaves = {}
        for name, first in group[0].wire_leaves():
            row0 = np.asarray(first)
            key = (meta, size, name)
            buf = self._staging.get(key)
            if buf is None or buf.shape[1:] != row0.shape:
                buf = self._staging[key] = np.zeros((size,) + row0.shape,
                                                    row0.dtype)
            buf[0] = row0
            for i in range(1, n):
                buf[i] = getattr(group[i], name)
            if n < size:
                buf[n:] = 0
            leaves[name] = buf
            self.host_bytes["staged"] += buf.nbytes
            self.host_bytes["wire"] += n * row0.nbytes
        if n < size:
            padded = np.full(size, self.arena.capacity, np.int64)
            padded[:n] = slots
            slots = padded
        return Payload(meta=meta, **leaves), slots

    def _decode_group(self, meta, group, slots: np.ndarray) -> None:
        """Scatter-decode one meta-group of payloads into the arena rows
        `slots`, on device — the mixed-meta flush path (single-meta
        flushes take the fused step in `_process`). The group is padded to
        its flush bucket, so each (meta, bucket) decode compiles once and
        the dense view never exists host-side. `xbuf` is donated and
        rebound."""
        stacked, slots = self._stack_group(meta, group, slots,
                                           self._bucket(len(group)))
        self.arena.xbuf = protocol.server_decode_to_slots(
            self.arena.xbuf, stacked, slots, dtype=self.dtype,
            backend=self.backend, mesh=self._mesh)

    def _process(self, items) -> None:
        """Serve one batch, picked up at the stamp that closed
        `server.wait` (read here when the caller drives the loop itself,
        as the loadgen does)."""
        t_flush, self._t_pickup = self._t_pickup, None
        if t_flush is None:
            t_flush = self._t = self.clock.monotonic()
        flush = self.pickups
        self.pickups += 1
        trace = self.tracer.enabled
        with _Stage(self, SPAN_PREPARE, flush=flush, n=len(items)):
            # queue-wait accounting for every frame this flush picked up
            # (including replays the dedup below drops — they waited too)
            for sess, frame in items:
                t_enq = self._enq_ts.pop((sess.id, frame.seq), None)
                if t_enq is None:
                    continue
                self._m_qwait.observe((t_flush - t_enq) * 1e3)
                if trace:
                    self.tracer.complete(SPAN_QUEUE_WAIT, t_enq, t_flush,
                                         tid=session_tid(sess.id),
                                         sid=sess.id, seq=frame.seq,
                                         flush=flush)
            self._m_depth.set(len(self.queue))
            all_items = items
            items = self._dedup(items)
            with self._lock:
                # drain the in-flight count for EVERY frame this flush
                # picked up (dedup-dropped replays included — they were
                # enqueued too) and stamp activity for the LRU eviction
                # order
                for sess, _frame in all_items:
                    sess.pending -= 1
                    sess.last_active = t_flush
                # eager slot release: a closed session's row returns to
                # the free deque now, not at the next full-arena admission
                # scan
                for sess in self.sessions.values():
                    if sess.closed and sess.slot >= 0:
                        slot, sess.slot = sess.slot, -1
                        self._arena_ops.append(("reset", None, slot))
                        self._push_free(slot)
                        self.registry.counter("slot_reclaims_total").inc()
                        self.tracer.instant(EVT_SLOT_EVICT, tid=SERVE_TID,
                                            sid=sess.id, slot=slot)
                # every slot not free is held by an open session now
                resident = self._capacity - len(self._free_slots)
                if resident == 0:
                    # fully idle: compact the free list back to ascending order
                    self._free_slots = collections.deque(
                        sorted(self._free_slots))
                ops, self._arena_ops = self._arena_ops, []
                self._slot_cv.notify_all()
                # a reclaimed slot means the session closed; any straggler
                # frame has no device state left and is dropped. The slot
                # is SNAPSHOTTED under the same lock: a reader thread
                # admitting a new session may reclaim a closed session's
                # slot at any moment, and a slot flipping to -1 between
                # the filter and the mask build would corrupt another live
                # slot's row.
                items = [(s, f, s.slot) for s, f in items if s.slot >= 0]
            if items:
                self._ensure_arena(items[0][1].payload.meta.d)
            self._apply_arena_ops(ops)      # serialized with the step here
            n = len(items)
            if n:
                self.batch_sizes.append(n)
                self._m_fill.observe(n)
                self._m_resident.inc(resident)
        if not n:
            return
        stacked = None
        with _Stage(self, SPAN_DECODE, flush=flush, n=n):
            by_meta: Dict = {}
            for i, (_, frame, _slot) in enumerate(items):
                by_meta.setdefault(frame.payload.meta, []).append(i)
            active = np.zeros(self.arena.capacity, bool)
            for _, _, slot in items:
                active[slot] = True
            if len(by_meta) == 1:
                # single-meta flush: staging only — the decode runs inside
                # the fused dispatch below
                [(meta, idxs)] = by_meta.items()
                stacked, slots = self._stack_group(
                    meta, [items[i][1].payload for i in idxs],
                    np.fromiter((self.arena.wire_row(items[i][2])
                                 for i in idxs), np.int64, len(idxs)),
                    self._bucket(len(idxs)))
            else:
                # mixed-meta flush: per-meta device decodes, then the
                # donated plain step below — no cache stack/unstack
                for meta, idxs in by_meta.items():
                    self._decode_group(
                        meta, [items[i][1].payload for i in idxs],
                        np.fromiter((self.arena.wire_row(items[i][2])
                                     for i in idxs), np.int64, len(idxs)))
        with _Stage(self, SPAN_STEP, leaf=False, flush=flush, n=n):
            with _Stage(self, SPAN_DISPATCH, flush=flush, n=n):
                if stacked is not None:
                    # ONE fused dispatch — decode lands in xbuf[slots] and
                    # the donated whole-arena step runs in the same
                    # program; only the (capacity, 1) token rows come back
                    tokens, self.arena.xbuf, self.arena.cache = \
                        self._fused_step(self.params, self.arena.xbuf,
                                         stacked, slots, self.arena.cache,
                                         jnp.asarray(active))
                else:
                    tokens, self.arena.cache = self.top_step(
                        self.params, self.arena.xbuf, self.arena.cache,
                        jnp.asarray(active))
            with _Stage(self, SPAN_SYNC, flush=flush, n=n):
                tokens = np.asarray(tokens)
        with _Stage(self, SPAN_REPLY, flush=flush, n=n):
            for sess, frame, slot in items:
                # with a pod axis, the token row returned on the inverse
                # ring to the slot's ingestion block (SlotArena.wire_row;
                # identity otherwise)
                reply = wire.encode_token_frame(
                    sess.id, frame.seq, tokens[self.arena.wire_row(slot)])
                sess.last_seq, sess.last_reply = frame.seq, reply
                sess.endpoint.send(reply)
                self._count_frame_down(sess, len(reply))
        self.stage_tokens += n
