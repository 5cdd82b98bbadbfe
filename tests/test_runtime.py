"""Streaming runtime: batching-queue flush policy, transport framing, and
end-to-end multi-client serving (byte accounting + local-decode parity)."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.core import wire
from repro.launch.steps import make_serve_step
from repro.models import transformer
from repro.models.config import Runtime, SplitConfig
from repro.runtime import BatchingQueue, channel_pair, run_streaming


# ---------------------------------------------------------------------------
# BatchingQueue flush policy
# ---------------------------------------------------------------------------

def test_queue_empty_times_out():
    q = BatchingQueue(max_batch=4, max_wait=0.05)
    t0 = time.monotonic()
    assert q.get_batch() == []
    assert time.monotonic() - t0 >= 0.04


def test_queue_flushes_full_batch_immediately():
    q = BatchingQueue(max_batch=3, max_wait=10.0)  # max_wait must NOT bind
    for i in range(5):
        q.put(i)
    t0 = time.monotonic()
    assert q.get_batch() == [0, 1, 2]
    assert time.monotonic() - t0 < 1.0
    assert len(q) == 2


def test_queue_max_wait_flushes_partial_batch():
    q = BatchingQueue(max_batch=8, max_wait=0.05)
    q.put("a")
    q.put("b")
    t0 = time.monotonic()
    assert q.get_batch() == ["a", "b"]   # ragged batch after max_wait
    assert 0.03 <= time.monotonic() - t0 < 1.0


def test_queue_fills_from_concurrent_producer():
    q = BatchingQueue(max_batch=3, max_wait=0.5)
    q.put(0)

    def late_puts():
        time.sleep(0.02)
        q.put(1)
        q.put(2)

    t = threading.Thread(target=late_puts)
    t.start()
    batch = q.get_batch()
    t.join()
    assert batch == [0, 1, 2]            # filled before max_wait expired


def test_queue_close_drains_ragged_final_batch():
    q = BatchingQueue(max_batch=8, max_wait=5.0)
    q.put("last")
    q.close()
    assert q.get_batch() == ["last"]     # close flushes without waiting
    assert q.get_batch() == [] and q.drained
    with pytest.raises(RuntimeError):
        q.put("nope")


def test_queue_concurrent_producers_lose_nothing():
    """N producer threads hammering put() against a draining consumer:
    every item comes out exactly once, in batches never exceeding
    max_batch."""
    n_producers, per_producer = 8, 200
    q = BatchingQueue(max_batch=16, max_wait=0.002)

    def produce(pid):
        for i in range(per_producer):
            q.put((pid, i))

    threads = [threading.Thread(target=produce, args=(pid,))
               for pid in range(n_producers)]
    for t in threads:
        t.start()
    got = []
    deadline = time.monotonic() + 30
    while len(got) < n_producers * per_producer:
        assert time.monotonic() < deadline, f"stalled at {len(got)} items"
        batch = q.get_batch(idle_timeout=0.05)
        assert len(batch) <= q.max_batch
        got.extend(batch)
    for t in threads:
        t.join()
    assert q.get_batch(idle_timeout=0.01) == []
    assert sorted(got) == [(p, i) for p in range(n_producers)
                           for i in range(per_producer)]
    # per-producer order is preserved even though batches interleave
    for pid in range(n_producers):
        seq = [i for p, i in got if p == pid]
        assert seq == sorted(seq)


def test_queue_close_during_fill_wait_flushes_promptly():
    """The close-during-flush race: a consumer blocked in the fill wait
    (partial batch, max_wait not yet elapsed) must be woken by close() and
    return the pending items immediately — not after max_wait, and never
    []."""
    q = BatchingQueue(max_batch=8, max_wait=10.0)   # max_wait must NOT bind
    result = {}

    def consume():
        result["batch"] = q.get_batch(idle_timeout=30.0)
        result["t"] = time.monotonic()

    t = threading.Thread(target=consume)
    t.start()
    q.put("a")
    q.put("b")
    time.sleep(0.15)                    # let the consumer enter the fill wait
    t0 = time.monotonic()
    q.close()
    t.join(timeout=5.0)
    assert not t.is_alive(), "consumer still blocked after close()"
    assert result["batch"] == ["a", "b"]
    assert result["t"] - t0 < 1.0       # woke on close, not on max_wait
    assert q.get_batch() == [] and q.drained


def test_queue_concurrent_producers_racing_close():
    """Producers racing close(): items either land in the queue and drain,
    or the put raises — none vanish silently mid-queue."""
    q = BatchingQueue(max_batch=4, max_wait=0.001)
    accepted, rejected = [], []
    lock = threading.Lock()

    def produce(pid):
        for i in range(100):
            try:
                q.put((pid, i))
                with lock:
                    accepted.append((pid, i))
            except RuntimeError:
                with lock:
                    rejected.append((pid, i))

    threads = [threading.Thread(target=produce, args=(pid,))
               for pid in range(4)]
    for t in threads:
        t.start()
    got = []
    for _ in range(30):                 # drain some while producers run
        got.extend(q.get_batch(idle_timeout=0.01))
    q.close()
    for t in threads:
        t.join()
    while True:
        batch = q.get_batch(idle_timeout=0.01)
        if not batch:
            break
        got.extend(batch)
    assert q.drained
    assert sorted(got) == sorted(accepted)
    assert len(got) + len(rejected) == 400


# ---------------------------------------------------------------------------
# Transport
# ---------------------------------------------------------------------------

def test_channel_pair_carries_frames_both_ways():
    cep, sep = channel_pair()
    cep.send(wire.encode_token_frame(1, 0, [7]))
    f = sep.recv_frame(timeout=1.0)
    assert f.tokens.tolist() == [7]
    sep.send(wire.encode_close_frame(1))
    assert cep.recv_frame(timeout=1.0).kind == wire.FRAME_CLOSE
    assert cep.recv_frame(timeout=0.01) is None


# ---------------------------------------------------------------------------
# Out-of-process protocol halves
# ---------------------------------------------------------------------------

def test_protocol_halves_roundtrip_over_wire():
    """client_encode -> frame bytes -> server_decode reproduces the fused
    forward() view exactly, with no compressor object on the server side."""
    from repro.core import compressors as C
    from repro.split import protocol

    comp = C.make_compressor("randtopk_quant", k=4, bits=8)
    x = jnp.asarray(np.random.RandomState(3).randn(2, 1, 32).astype(
        np.float32))
    p = protocol.client_encode(comp, x, key=jax.random.key(0), training=True)
    assert all(isinstance(a, np.ndarray) for _, a in p.wire_leaves())
    frame, _ = wire.decode_frame(wire.encode_payload_frame(0, 0, p))
    y = np.asarray(protocol.server_decode(frame.payload))
    fused, _ = comp.forward(x, key=jax.random.key(0), training=True)
    np.testing.assert_allclose(y, np.asarray(fused), rtol=0, atol=0)


# ---------------------------------------------------------------------------
# End-to-end serving
# ---------------------------------------------------------------------------

def _smoke_cfg(**split_kw):
    split = SplitConfig(cut_layer=1, **split_kw) if split_kw else None
    return configs.get("qwen3-8b", smoke=True).with_(split=split)


def test_streaming_matches_local_decode():
    """Identity compression through the full frame/queue/batch machinery
    must reproduce the plain single-process decode loop token-for-token."""
    cfg = _smoke_cfg()
    params = transformer.init_model(jax.random.key(0), cfg)
    prompt_len, gen = 3, 5
    res = run_streaming(cfg, n_clients=2, prompt_len=prompt_len, gen=gen,
                        max_batch=2, params=params, seed=0)

    rt = Runtime(mesh=None, training=False)
    serve = jax.jit(make_serve_step(cfg, rt))
    prompts = np.asarray(jax.random.randint(
        jax.random.key(1), (2, prompt_len), 0, cfg.vocab))
    for row in range(2):
        cache = transformer.init_cache(params, cfg, rt, 1, prompt_len + gen)
        tok, out = prompts[row:row + 1, :1], []
        for i in range(prompt_len + gen - 1):
            nxt, cache = serve(params, cache, jnp.asarray(tok))
            if i >= prompt_len - 1:
                out.append(int(nxt[0, 0]))
            tok = (prompts[row:row + 1, i + 1:i + 2]
                   if i + 1 < prompt_len else np.asarray(nxt))
        assert res["tokens"][row].tolist() == out


@pytest.mark.slow
def test_streaming_mixed_compressors_byte_accounting():
    """A dense + randtopk session mix: grouped batched decode, and both
    parties' accounting equals the frame sizes the codec predicts."""
    cfg = _smoke_cfg(compressor="randtopk", k=16)
    prompt_len, gen = 2, 4
    res = run_streaming(cfg, n_clients=4, prompt_len=prompt_len, gen=gen,
                        max_batch=4, max_wait=0.05,
                        compressor_mix=["identity", "randtopk:k=16"])
    assert res["tokens"].shape == (4, gen)
    n_frames = prompt_len + gen - 1
    d = cfg.d_model
    r = wire.index_bits(d)
    expect = {"identity": d * 4, "randtopk": 16 * 4 + (16 * r + 7) // 8}
    for name, cs, ss in zip(res["compressors"], res["client_stats"],
                            res["server_stats"]):
        for f in ("frames_up", "payload_bytes_up", "header_bytes_up",
                  "frames_down", "bytes_down"):
            assert cs[f] == ss[f], (f, cs, ss)
        assert cs["frames_up"] == cs["frames_down"] == n_frames
        assert cs["tokens_out"] == gen
        assert cs["payload_bytes_up"] == n_frames * expect[name]
    # the mix really was batched together at least once
    assert max(res["batch_sizes"]) > 1


def test_failing_flush_fails_the_run_fast(monkeypatch):
    """A flush that raises ends the serve loop: the error is recorded,
    the blocked clients are released by error frames, and run_streaming
    raises the original exception well inside the clients' 60 s reply
    timeout."""
    from repro.runtime.server import StreamingServer

    class FlushBoom(Exception):
        pass

    def boom(self, items):
        raise FlushBoom("flush failed")

    monkeypatch.setattr(StreamingServer, "_process", boom)
    cfg = _smoke_cfg(compressor="topk", k=8)
    t0 = time.monotonic()
    with pytest.raises(FlushBoom, match="flush failed"):
        run_streaming(cfg, n_clients=3, prompt_len=2, gen=2, max_batch=2)
    assert time.monotonic() - t0 < 30.0


def test_streaming_sessions_outnumber_max_batch():
    """More sessions than the flush size -> multiple ragged flushes, every
    session still completes with its own cache intact."""
    cfg = _smoke_cfg(compressor="topk", k=8)
    res = run_streaming(cfg, n_clients=5, prompt_len=2, gen=3, max_batch=2,
                        max_wait=0.01)
    assert res["tokens"].shape == (5, 3)
    assert all(1 <= b <= 2 for b in res["batch_sizes"])
    assert sum(res["batch_sizes"]) == 5 * (2 + 3 - 1)
