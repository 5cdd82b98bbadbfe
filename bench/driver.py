"""The feature owners' side of the wire: sessions replaying pool frames.

One thread drives every session. Sessions arrive open loop, at the plan's
due times; when the label owner already holds `capacity` live sessions, an
arrival waits in a FIFO for admission (its time to first token runs from
its due time all the same). A live session is a closed loop of its own:
it sends its next frame when the reply to the last one lands, and ignores
the token it gets back, since its script fixes its inputs.

Each session has its own `runtime.transport.channel_pair`; the uplink
bytes go through the client endpoint into the server's reader thread. The
server's replies for a session land on that session's `_Downlink`, which
queues them, tagged, for this one thread, so it never polls a channel.
"""
from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Dict, List

import numpy as np

from bench import reference


class _Downlink:
    """The label owner's end of one session's channel: frames in from the
    channel, replies out to the driver's queue."""

    def __init__(self, endpoint, sid: int, replies: queue.SimpleQueue):
        self._ep, self._sid, self._replies = endpoint, sid, replies

    def recv_frame(self, timeout=None):
        return self._ep.recv_frame(timeout=timeout)

    def send(self, data: bytes) -> int:
        self._replies.put((self._sid, time.perf_counter(), data))
        return len(data)


class Session:
    __slots__ = ("sid", "script", "steps", "prompt", "t_due",
                 "t_first", "t_last_gen", "t_sent", "next_step", "up",
                 "served", "done", "failed", "closed", "frame_nbytes",
                 "pay_nbytes")

    def __init__(self, sid, script, steps, prompt, t_due):
        self.sid, self.script, self.steps = sid, script, steps
        self.prompt, self.t_due = prompt, t_due
        self.t_first = self.t_last_gen = None
        self.t_sent = 0.0
        self.next_step = 0
        self.up = None
        self.served = np.full(steps, -1, np.int64)
        self.done = self.failed = self.closed = False
        self.frame_nbytes = self.pay_nbytes = 0


class Driver:
    """Runs the plan against `server` from absolute time `t0` until
    `t_stop` (both `time.perf_counter()` seconds)."""

    def __init__(self, server, pool, plan, capacity: int):
        self.server, self.pool, self.plan = server, pool, plan
        self.capacity = capacity
        self.replies: queue.SimpleQueue = queue.SimpleQueue()
        self.sessions: Dict[int, Session] = {}
        self.waiting: collections.deque = collections.deque()
        self.live = 0
        # one entry per reply received: time, session, step, the uplink
        # frame's bytes, its payload's bytes, the session's compressor
        self.r_t: List[float] = []
        self.r_sid: List[int] = []
        self.r_step: List[int] = []
        self.r_up: List[int] = []
        self.r_pay: List[int] = []
        self.r_comp: List[int] = []
        self.gaps: List[tuple] = []         # (t_end, inter-token gap)
        self.late: List[float] = []         # arrival handled - due, seconds
        self.depth: List[tuple] = []        # (t, admission FIFO depth)
        self.error: Exception = None
        self._thread = None

    # -- one session ----------------------------------------------------------

    def _admit(self, s: Session, now: float) -> None:
        from repro.runtime.transport import channel_pair

        cep, sep = channel_pair()
        self.server.attach(_Downlink(sep, s.sid, self.replies))
        s.up = cep
        self.live += 1
        self._send(s, now)

    def _send(self, s: Session, now: float) -> None:
        frame = self.pool.frame(s.sid, s.script, s.next_step)
        s.frame_nbytes = len(frame)
        s.pay_nbytes = len(self.pool.bodies[s.script][s.next_step])
        s.t_sent = now
        s.up.send(frame)

    def _close(self, s: Session) -> None:
        from repro.core import wire

        s.up.send(wire.encode_close_frame(s.sid))
        s.closed = True
        self.live -= 1

    def _reply(self, sid: int, t: float, data: bytes) -> None:
        s = self.sessions[sid]
        kind, _sid, seq, body = reference.parse_frame(data)
        if kind != reference.FRAME_TOKENS or seq != s.next_step:
            s.failed = True
            self._close(s)
            return
        s.served[seq] = int(reference.parse_tokens(body)[0])
        self.r_t.append(t)
        self.r_sid.append(sid)
        self.r_step.append(seq)
        self.r_up.append(s.frame_nbytes)
        self.r_pay.append(s.pay_nbytes)
        self.r_comp.append(int(self.plan.comp[s.script]))
        if seq >= s.prompt - 1:                 # a generated token
            if s.t_first is None:
                s.t_first = t
            else:
                self.gaps.append((t, t - s.t_last_gen))
            s.t_last_gen = t
        s.next_step += 1
        if s.next_step < s.steps:
            self._send(s, time.perf_counter())
        else:
            s.done = True
            self._close(s)

    # -- the loop ---------------------------------------------------------------

    def _loop(self, t0: float, t_stop: float) -> None:
        plan, due = self.plan, self.plan.t_due + t0
        nxt, n = 0, len(due)
        last_depth = -1
        while True:
            now = time.perf_counter()
            if now >= t_stop:
                return
            while nxt < n and due[nxt] <= now:
                script = plan.script(nxt)
                s = Session(nxt + 1, script, plan.steps(script),
                            int(plan.prompt_len[script]), due[nxt])
                self.sessions[s.sid] = s
                self.waiting.append(s)
                self.late.append(now - s.t_due)
                nxt += 1
            while self.waiting and self.live < self.capacity:
                self._admit(self.waiting.popleft(), now)
            if len(self.waiting) != last_depth:
                last_depth = len(self.waiting)
                self.depth.append((now, last_depth))
            wait = min(t_stop, due[nxt] if nxt < n else t_stop) - now
            try:
                item = self.replies.get(timeout=max(0.0, min(wait, 0.05)))
            except queue.Empty:
                continue
            self._reply(*item)
            while True:
                try:
                    item = self.replies.get_nowait()
                except queue.Empty:
                    break
                self._reply(*item)

    def _run(self, t0, t_stop):
        try:
            self._loop(t0, t_stop)
        except Exception as e:          # reported by the harness
            self.error = e

    def start(self, t0: float, t_stop: float) -> None:
        self._thread = threading.Thread(target=self._run, args=(t0, t_stop),
                                        daemon=True)
        self._thread.start()

    def join(self, timeout: float) -> bool:
        self._thread.join(timeout)
        return not self._thread.is_alive()

    def close_all(self) -> None:
        """After the loop: end every live session (the readers exit)."""
        for s in self.sessions.values():
            if s.up is not None and not s.closed:
                self._close(s)
