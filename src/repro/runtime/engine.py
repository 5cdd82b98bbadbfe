"""Orchestration: build a server + N streaming clients and run the sessions.

This is the simulation harness `launch/serve.py`, `benchmarks/
serve_throughput.py`, and `examples/streaming_clients.py` drive: everything
crosses real framed byte channels, compression is applied per client (a
mixed compressor population is supported), and the result carries both
parties' byte accounting so callers can cross-check measured wire sizes
against the Table-2 analytics.
"""
from __future__ import annotations

import threading
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compressors
from repro.models import transformer
from repro.models.config import ArchConfig, Runtime
from repro.obs.registry import MetricsRegistry
from repro.obs.trace import NULL_TRACER
from repro.runtime import steps
from repro.runtime.client import StreamingClient
from repro.runtime.server import StreamingServer, jit_serving_steps
from repro.runtime.transport import channel_pair
from repro.split import protocol


#: cross-run cache of jitted serving step pairs — an explicit dict, not an
#: `functools.lru_cache`: the cached jit wrappers pin compiled executables
#: AND their device buffers (per-device under a sharded arena), and an
#: unbounded-lifetime decorator cache gave no way to release them short of
#: killing the process. `clear_serving_steps()` is the shutdown hook.
_STEP_CACHE: dict = {}

#: overall wall-clock bound on one `run_streaming` session fleet after warm
#: (clients + serve loop together); a run past it raises TimeoutError
_RUN_DEADLINE_S = 900.0


def _serving_steps(cfg: ArchConfig, rt: Runtime, cut: int, dtype_name: str,
                   backend: Optional[str], mesh=None):
    """Cross-run cache of the server's jitted step pair.

    jit compile caches live on the wrapped callable, so handing every
    `run_streaming` call the same pair (keyed by the hashable frozen
    configs + mesh) means a benchmark sweep compiles each (meta, bucket)
    program once per process instead of once per run — the repeated-run
    gate used to re-pay the whole warm loop every repetition. Arena shapes
    (capacity) may differ between runs; the jit object retraces per shape
    and keeps both programs."""
    key = (cfg, rt, cut, dtype_name, backend, mesh)
    pair = _STEP_CACHE.get(key)
    if pair is None:
        top = steps.make_arena_top_step(cfg, rt, cut, mesh=mesh)
        pair = _STEP_CACHE[key] = jit_serving_steps(
            top, dtype=jnp.dtype(dtype_name), backend=backend, mesh=mesh)
    return pair


def clear_serving_steps() -> int:
    """Engine shutdown: drop every cached serving-step pair and the
    compiled executables + device buffers they pin (`jit.clear_cache()`).
    Returns the number of entries released. Long-lived hosts (benchmark
    sweeps over many meshes, embedding servers) call this between
    configurations; within one configuration, keeping the cache warm is
    the whole point of `_serving_steps`."""
    n = len(_STEP_CACHE)
    for top, fused in _STEP_CACHE.values():
        top.clear_cache()
        fused.clear_cache()
    _STEP_CACHE.clear()
    return n


def _client_compressors(cfg: ArchConfig, n_clients: int,
                        mix: Optional[Sequence] = None) -> List:
    """Per-client compressor objects: an explicit mix (spec strings or
    Compressor objects, assigned round-robin) or the config's compressor."""
    if mix is None:
        base = (protocol.make_cut_compressor(cfg.split) if cfg.split
                else compressors.Compressor())
        return [base] * n_clients
    objs = [compressors.make_compressor(m) if isinstance(m, str) else m
            for m in mix]
    return [objs[i % len(objs)] for i in range(n_clients)]


def run_streaming(cfg: ArchConfig, *, n_clients: int = 8, prompt_len: int = 4,
                  gen: int = 8, max_batch: Optional[int] = None,
                  max_wait: float = 0.01, compressor_mix=None, seed: int = 0,
                  params=None, wrap_endpoint=None,
                  retry_timeout: Optional[float] = None,
                  max_retries: int = 16, tracer=None, mesh=None,
                  capacity: Optional[int] = None,
                  release_steps: bool = False,
                  device_encode: bool = True) -> dict:
    """Serve `n_clients` concurrent sessions of `prompt_len + gen` tokens.

    Returns a dict with the generated tokens `(n_clients, gen)`, per-session
    client/server stats dicts, the per-client compressor names, the server's
    batch-fill history, wall-clock throughput, the aggregated
    `fault_counters` (all zero on a clean wire), and a `metrics` snapshot
    of the run's private `MetricsRegistry` (docs/observability.md).

    `wrap_endpoint(cid, endpoint) -> endpoint` intercepts every client-side
    connection — initial and reconnect — which is how
    `repro.testing.faults.FaultInjector` runs the whole stack under seeded
    chaos. `retry_timeout` enables stop-and-wait retransmission (needed for
    drop faults); None keeps the clean-wire single-wait behavior.

    `tracer` (an `obs.trace.Tracer`, default off) records the frame
    lifecycle of every session; `launch/serve.py --trace` exports it as
    Perfetto-loadable Chrome-trace JSON.

    `mesh` (a `jax.sharding.Mesh`) shards the server's arena and runs the
    top step under `shard_map` (docs/sharding.md); tokens are bit-identical
    to `mesh=None` at any shape. `capacity` caps concurrently-RESIDENT
    sessions (default: `n_clients`, so eviction never triggers); setting it
    below `n_clients` exercises the LRU evict-to-host / re-admission path.
    `release_steps` drops the cross-run step cache on exit
    (`clear_serving_steps`) — for sweeps that never revisit a
    configuration.

    `device_encode` (default on) gives every client the
    `steps.make_bottom_step_device` bottom step: the wire bitstream is
    packed on device and the host's per-step encode work is pull +
    truncate + CRC. Frames are byte-identical either way; the result's
    `client_encode_s` / `client_encode_steps` aggregate the per-client
    host pack time (the bench's `encode` µs/token stage), so
    `device_encode=False` is the host-pack baseline the serve bench gates
    against.
    """
    rt = Runtime(mesh=None, training=False)
    # the label owner may serve from a quantized KV arena (int8 codes +
    # f32 scale rows, `ArchConfig.kv_cache_bits`); feature owners always
    # keep their bottom-model caches at the Runtime default (f32)
    rt_top = Runtime(mesh=None, training=False,
                     kv_cache_bits=cfg.kv_cache_bits or rt.kv_cache_bits)
    cut = (cfg.split.cut_layer if cfg.split and cfg.split.cut_layer > 0
           else max(1, cfg.n_layers // 2))
    assert 0 < cut < cfg.n_layers
    if params is None:
        params = transformer.init_model(jax.random.key(seed), cfg)
    max_batch = max_batch or min(8, n_clients)
    max_len = prompt_len + gen
    comps = _client_compressors(cfg, n_clients, compressor_mix)

    # one jitted bottom step per distinct compressor (frozen -> hashable)
    make_bottom = (steps.make_bottom_step_device if device_encode
                   else steps.make_bottom_step)
    bottom_steps = {c: jax.jit(make_bottom(cfg, rt, cut, c))
                    for c in dict.fromkeys(comps)}
    make_cache = lambda: transformer.init_cache(params, cfg, rt, 1, max_len)
    make_top_cache = lambda: transformer.init_cache(params, cfg, rt_top, 1,
                                                    max_len)
    # every session owns a device-resident arena slot for its whole life,
    # so capacity = the expected concurrent session count; the jitted step
    # pair is shared across runs (see _serving_steps)
    tracer = tracer if tracer is not None else NULL_TRACER
    registry = MetricsRegistry()        # per-run, isolated
    # the label owner's copy of the weights: replicated over its mesh once,
    # not re-broadcast by every flush; the feature owners keep theirs on
    # their own (default) device
    top_params = params
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        top_params = jax.device_put(params,
                                    NamedSharding(mesh, PartitionSpec()))
    server = StreamingServer(top_params, None, make_top_cache,
                             max_batch=max_batch,
                             max_wait=max_wait, dtype=cfg.adtype(),
                             capacity=capacity or n_clients,
                             x_shape=(1, 1, cfg.d_model),
                             jit_steps=_serving_steps(
                                 cfg, rt_top, cut, cfg.dtype, None, mesh),
                             mesh=mesh,
                             tracer=tracer, registry=registry)
    server.expected_sessions = n_clients

    prompts = np.asarray(jax.random.randint(
        jax.random.key(seed + 1), (n_clients, prompt_len), 0, cfg.vocab))

    def _connect(cid: int):
        """One client connection: fresh channel pair, server reader attached,
        client half optionally wrapped (fault injection). Also the reconnect
        path — a resuming client calls this for a clean channel onto its
        surviving server-side session."""
        cep, sep = channel_pair()
        server.attach(sep)
        return wrap_endpoint(cid, cep) if wrap_endpoint else cep

    clients: List[StreamingClient] = []
    for cid in range(n_clients):
        clients.append(StreamingClient(
            cid, params, make_cache(), bottom_steps[comps[cid]],
            _connect(cid), prompts[cid], gen,
            retry_timeout=retry_timeout, max_retries=max_retries,
            reconnect=lambda cid=cid: _connect(cid),
            tracer=tracer, registry=registry, device_encode=device_encode))

    # warm every hot-loop jit BEFORE spawning threads (one compile, not a
    # storm — and the serving clock never pays compile time): bottom steps,
    # then the server's per-meta slot decodes + the donated arena step
    tok0 = np.zeros((1, 1), np.int32)
    dummy = {c: step(params, make_cache(), tok0)
             for c, step in bottom_steps.items()}
    examples = [out[0] if device_encode else out for out, _ in dummy.values()]
    server.warm([jax.tree.map(np.asarray, p) for p in examples])

    t0 = time.perf_counter()
    serve_thread = threading.Thread(target=server.serve_loop, daemon=True)
    serve_thread.start()
    threads = [threading.Thread(target=c.run, daemon=True) for c in clients]
    for t in threads:
        t.start()
    # one overall deadline for the whole run; a server failure ends the
    # wait at once (the failed sessions can no longer be answered)
    deadline = t0 + _RUN_DEADLINE_S
    for t in threads:
        while (t.is_alive() and not server.errors
               and time.perf_counter() < deadline):
            t.join(timeout=0.01)
    # guaranteed stop even if a CLOSE frame was lost to injected faults
    server.shutdown()
    serve_thread.join(timeout=max(0.0, deadline - time.perf_counter()))
    wall = time.perf_counter() - t0

    if server.errors:
        raise server.errors[0]          # the first failed flush or reader
    alive = [c.id for c, t in zip(clients, threads) if t.is_alive()]
    if alive or serve_thread.is_alive():
        raise TimeoutError(
            f"run not finished within {_RUN_DEADLINE_S:.0f}s: clients {alive} "
            f"still running, serve loop "
            f"{'alive' if serve_thread.is_alive() else 'done'}")
    errs = [(c.id, c.error) for c in clients if c.error is not None]
    if errs:
        raise RuntimeError(f"client sessions failed: {errs}") from errs[0][1]

    tokens = np.asarray([c.generated for c in clients], np.int32)
    if release_steps:
        clear_serving_steps()
    result = {
        "tokens": tokens,
        "client_stats": [c.stats.as_dict() for c in clients],
        "server_stats": [server.sessions[c.id].stats.as_dict()
                         for c in clients],
        "compressors": [c.name for c in comps],
        "compressor_objs": comps,
        "batch_sizes": server.batch_sizes,
        "fault_counters": fault_summary(server, clients),
        "metrics": registry.snapshot(),
        # serve-loop wall seconds by stage (queue wait / prepare / host
        # staging [+ mixed-meta decode dispatch] / fused-or-plain step =
        # dispatch + token readback sync / reply framing+send), the token
        # count those flushes served (for per-token stage costs), host
        # staging-vs-wire byte totals, and per-client request->token
        # round-trip latencies
        "stage_s": dict(server.stage_s),
        "stage_tokens": server.stage_tokens,
        "host_bytes": dict(server.host_bytes),
        "flushes": len(server.batch_sizes),
        "client_latencies": [list(c.latencies) for c in clients],
        # host-side frame-pack CPU seconds summed over clients (+ the
        # frame count) — the client `encode` stage of
        # gate_stage_us_per_token (thread CPU time: see runtime.client)
        "client_encode_s": sum(c.encode_s for c in clients),
        "client_encode_steps": sum(c.encode_steps for c in clients),
        "device_encode": device_encode,
        "wall_s": wall,
        "tokens_per_s": tokens.size / max(wall, 1e-9),
        "n_clients": n_clients,
        "max_batch": max_batch,
        "cut_layer": cut,
    }
    return result


def fault_summary(server, clients) -> dict:
    """Aggregate recovery counters across both parties — reported by
    `run_streaming`/`run_fedtrain` alongside the byte accounting. All zero
    on a clean wire; under injected chaos, the measured recovery record."""
    out = {"server_faults_detected": server.faults_detected,
           "client_faults_detected": 0, "duplicates": 0, "replays": 0,
           "reconnects": 0}
    for c in clients:
        out["client_faults_detected"] += c.stats.faults_detected
        out["replays"] += c.stats.replays
        out["reconnects"] += c.stats.reconnects
        out["duplicates"] += c.stats.duplicates
    for sess in server.sessions.values():
        out["duplicates"] += sess.stats.duplicates
    return out
