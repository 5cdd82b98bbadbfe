"""Session-slot arena: device-side decode parity with the host-densify
path for every payload kind, zero host-side densification on the serving
and training hot paths, slot stability under chaos/reconnect, slot reuse
after close, and the active-mask no-advance invariant."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.core import compressors as C
from repro.core import wire
from repro.models import transformer
from repro.models.config import Runtime, SplitConfig
from repro.runtime import run_streaming, steps
from repro.runtime.server import StreamingServer
from repro.split import protocol
from repro.testing import FaultInjector, FaultPlan

KIND_COMPRESSORS = [
    ("dense", C.make_compressor("identity")),
    ("slice", C.make_compressor("size_reduction", k=6)),
    ("sparse", C.make_compressor("randtopk", k=6)),
    ("quant", C.make_compressor("quant", bits=4)),
    ("sparse_quant", C.make_compressor("randtopk_quant", k=6, bits=8)),
]


def _smoke_cfg(**split_kw):
    split = SplitConfig(cut_layer=1, **split_kw) if split_kw else None
    return configs.get("qwen3-8b", smoke=True).with_(split=split)


def _wire_payload(comp, x):
    """Encode + full frame round trip — exactly what the server receives."""
    p = protocol.client_encode(comp, x, key=jax.random.key(0), training=True)
    frame, _ = wire.decode_frame(wire.encode_payload_frame(0, 0, p))
    return frame.payload


# ---------------------------------------------------------------------------
# Decode parity: device/slot decode == host densify, for every payload kind
# ---------------------------------------------------------------------------

def _assert_decode_match(kind, host, dev):
    """Sparse/dense/slice decode carries wire floats verbatim — bit-exact
    in every mode. Quant dequant is a multiply-add the compiled path may
    contract into an FMA, so compiled-vs-eager is pinned to <= 1 ulp (and
    test_arena_tokens_match_host_densify_path pins that served tokens do
    not move at all)."""
    if kind in ("quant", "sparse_quant"):
        # one rounding of the (code + 0.5) * step product: bounded by the
        # ulp at the largest decoded magnitude
        atol = float(np.spacing(np.float32(np.abs(host).max())))
        np.testing.assert_allclose(dev, host, rtol=0, atol=atol)
    else:
        np.testing.assert_array_equal(host, dev)


@pytest.mark.parametrize("kind,comp", KIND_COMPRESSORS,
                         ids=[k for k, _ in KIND_COMPRESSORS])
def test_device_decode_matches_host_decode(kind, comp):
    x = jnp.asarray(np.random.RandomState(1).randn(3, 1, 32).astype(
        np.float32))
    p = _wire_payload(comp, x)
    assert p.meta.kind == kind
    host = np.asarray(protocol.server_decode(p))
    dev = np.asarray(protocol.server_decode_device(p))
    _assert_decode_match(kind, host, dev)


@pytest.mark.parametrize("kind,comp", KIND_COMPRESSORS,
                         ids=[k for k, _ in KIND_COMPRESSORS])
def test_slot_decode_matches_host_decode(kind, comp):
    """Scatter-decode into arena rows == host densify, row for row; rows
    not targeted keep their prior contents; the scratch row absorbs pads."""
    n, d, cap = 3, 32, 5
    x = jnp.asarray(np.random.RandomState(2).randn(n, 1, 1, d).astype(
        np.float32))
    p = _wire_payload(comp, x)
    host = np.asarray(protocol.server_decode(p))
    xbuf = jnp.full((cap + 1, 1, 1, d), 7.0, jnp.float32)
    slots = np.array([4, 0, 2])
    out = np.asarray(protocol.server_decode_to_slots(xbuf, p, slots))
    for row, slot in enumerate(slots):
        _assert_decode_match(kind, host[row], out[slot])
    for untouched in (1, 3, 5):
        np.testing.assert_array_equal(out[untouched], 7.0)


def test_scatter_rows_pallas_matches_xla():
    """The Pallas scatter kernel (interpret) == put_along_axis for unique
    supports, across shapes and d not a multiple of the lane width."""
    rng = np.random.RandomState(3)
    for shape, d in [((4, 8), 32), ((2, 3, 5), 70), ((1, 1, 1, 16), 256)]:
        k = shape[-1]
        vals = rng.randn(*shape).astype(np.float32)
        idx = np.stack([rng.choice(d, k, replace=False)
                        for _ in range(int(np.prod(shape[:-1])))])
        idx = idx.reshape(shape).astype(np.uint16)
        meta = C.PayloadMeta("sparse", d=d, k=k)
        p = C.Payload(meta=meta, values=jnp.asarray(vals),
                      indices=jnp.asarray(idx))
        ref = np.asarray(C.payload_to_dense(p, backend="xla"))
        got = np.asarray(C.payload_to_dense(p, backend="pallas"))
        np.testing.assert_array_equal(ref, got)


# ---------------------------------------------------------------------------
# End-to-end: arena-served tokens == the pre-arena host-densify serve loop
# ---------------------------------------------------------------------------

def _reference_tokens(cfg, params, comp, prompts, gen):
    """The pre-arena serving semantics, replayed single-file: bottom step ->
    wire round trip -> HOST densify (`server_decode`) -> flush-shaped
    vmapped top step (`make_top_step`) with a stacked/unstacked cache."""
    rt = Runtime(mesh=None, training=False)
    cut = cfg.split.cut_layer if cfg.split else max(1, cfg.n_layers // 2)
    bottom = jax.jit(steps.make_bottom_step(cfg, rt, cut, comp))
    top = jax.jit(steps.make_top_step(cfg, rt, cut))
    prompt_len = prompts.shape[1]
    out = []
    for row in range(prompts.shape[0]):
        cache_b = transformer.init_cache(params, cfg, rt, 1, prompt_len + gen)
        cache_t = transformer.init_cache(params, cfg, rt, 1, prompt_len + gen)
        token = np.asarray([[prompts[row, 0]]], np.int32)
        toks = []
        for step in range(prompt_len + gen - 1):
            p, cache_b = bottom(params, cache_b, token)
            p = jax.tree.map(np.asarray, p)
            frame, _ = wire.decode_frame(
                wire.encode_payload_frame(row, step, p))
            x = np.asarray(protocol.server_decode(frame.payload,
                                                  dtype=cfg.adtype()))
            stacked = jax.tree.map(lambda a: a[None], cache_t)
            tok, new_stacked = top(params, jnp.asarray(x[None]), stacked)
            cache_t = jax.tree.map(lambda a: a[0], new_stacked)
            nxt = int(np.asarray(tok)[0, 0])
            if step + 1 < prompt_len:
                token = np.asarray([[prompts[row, step + 1]]], np.int32)
            else:
                toks.append(nxt)
                token = np.asarray([[nxt]], np.int32)
        out.append(toks)
    return np.asarray(out, np.int32)


@pytest.mark.parametrize("spec", ["identity", "size_reduction:k=8",
                                  "randtopk:k=8", "quant:bits=4",
                                  "randtopk_quant:k=8,bits=8"])
@pytest.mark.slow
def test_arena_tokens_match_host_densify_path(spec):
    """Slot-decoded, arena-stepped tokens are bit-identical to the old
    host-densify + stack/unstack serve loop, for every payload kind."""
    cfg = _smoke_cfg(compressor="randtopk", k=8)
    params = transformer.init_model(jax.random.key(0), cfg)
    prompt_len, gen, n = 2, 4, 2
    res = run_streaming(cfg, n_clients=n, prompt_len=prompt_len, gen=gen,
                        max_batch=n, params=params, seed=0,
                        compressor_mix=[spec])
    prompts = np.asarray(jax.random.randint(
        jax.random.key(1), (n, prompt_len), 0, cfg.vocab))
    comp = C.make_compressor(spec)
    ref = _reference_tokens(cfg, params, comp, prompts, gen)
    np.testing.assert_array_equal(res["tokens"], ref)


# ---------------------------------------------------------------------------
# Zero host-side densification on the hot paths
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_streaming_serves_without_host_densify():
    """A full mixed-kind serving run performs ZERO host-side dense
    materializations (`protocol.server_decode` stays untouched) and keeps
    no per-session host cache — sessions own arena slots instead."""
    cfg = _smoke_cfg(compressor="randtopk", k=8)
    params = transformer.init_model(jax.random.key(0), cfg)
    with protocol.HOST_DENSIFY_COUNT.watch() as w:
        res = run_streaming(cfg, n_clients=4, prompt_len=2, gen=4,
                            max_batch=4, params=params,
                            compressor_mix=["identity", "randtopk:k=8",
                                            "quant:bits=4",
                                            "randtopk_quant:k=8,bits=8"])
        assert w.delta == 0
    assert res["tokens"].shape == (4, 4)


def test_fedtrain_trains_without_host_densify():
    from repro.data.synthetic import ManyClassDataset
    from repro.fedtrain import run_fedtrain
    from repro.split.tabular import SplitSpec

    ds = ManyClassDataset(n_classes=10, in_dim=16, n_train=256, n_test=128,
                          noise=0.3, seed=0)
    spec = SplitSpec(in_dim=16, hidden=32, cut_dim=32, n_classes=10,
                     method="randtopk", k=3)
    with protocol.HOST_DENSIFY_COUNT.watch() as w:
        r = run_fedtrain(spec, ds, n_clients=1, epochs=1, batch=64, seed=0)
        assert w.delta == 0
    assert r["steps"] > 0


# ---------------------------------------------------------------------------
# Int8 KV arena: opt-in via ArchConfig.kv_cache_bits, pinned accuracy delta
# ---------------------------------------------------------------------------

def test_int8_kv_arena_cache_layout():
    """kv_cache_bits=8 swaps the arena KV leaves to int8 codes plus f32
    per-(token,head) scale rows — the layout `attention` keys its dequant
    branch on (`"k_scale" in cache`)."""
    cfg = _smoke_cfg(compressor="randtopk", k=8)
    params = transformer.init_model(jax.random.key(0), cfg)
    rt8 = Runtime(mesh=None, training=False, kv_cache_bits=8)
    cache = transformer.init_cache(params, cfg, rt8, 1, 8)
    kv = cache["kv"]
    assert kv["k"].dtype == jnp.int8 and kv["v"].dtype == jnp.int8
    assert kv["k_scale"].dtype == jnp.float32
    assert kv["k_scale"].shape == kv["k"].shape[:-1]


@pytest.mark.slow
def test_int8_kv_arena_serving_accuracy_delta():
    """Serving with an int8 server-side KV arena stays within a pinned
    token-agreement margin of the f32 reference. The quantized run must
    also actually diverge somewhere (seed 1, gen 12 does) — otherwise a
    regression that silently ignores `kv_cache_bits` would pass the margin
    trivially. Clients keep f32 bottom caches either way."""
    cfg = _smoke_cfg(compressor="randtopk", k=8)
    assert cfg.kv_cache_bits == 0            # default: Runtime decides
    params = transformer.init_model(jax.random.key(0), cfg)
    kw = dict(n_clients=2, prompt_len=2, gen=12, max_batch=2,
              params=params, seed=1)
    f32 = run_streaming(cfg, **kw)
    q8 = run_streaming(cfg.with_(kv_cache_bits=8), **kw)
    agree = float((f32["tokens"] == q8["tokens"]).mean())
    assert agree >= 0.75                     # measured 0.875
    assert agree < 1.0                       # int8 path demonstrably active


# ---------------------------------------------------------------------------
# Slot lifecycle: stability under chaos, reuse after close, full-arena error
# ---------------------------------------------------------------------------

def test_slots_survive_reconnect_without_double_advance():
    """Chaos (corrupt/drop/duplicate + ARQ retransmission) forces replays
    and reconnects; sessions keep their arena slot throughout and the KV
    cache never double-advances — tokens stay bit-identical to the clean
    run."""
    cfg = _smoke_cfg(compressor="randtopk", k=8)
    params = transformer.init_model(jax.random.key(0), cfg)
    kw = dict(n_clients=3, prompt_len=2, gen=4, max_batch=2, params=params,
              seed=0)
    clean = run_streaming(cfg, **kw)

    inj = FaultInjector(FaultPlan(seed=7, corrupt=0.04, drop=0.04,
                                  duplicate=0.05, max_faults=24))
    chaos = run_streaming(cfg, wrap_endpoint=inj, retry_timeout=0.2, **kw)
    fc = chaos["fault_counters"]
    assert sum(inj.injected().values()) > 0
    assert fc["replays"] + fc["duplicates"] + fc["reconnects"] > 0
    np.testing.assert_array_equal(clean["tokens"], chaos["tokens"])


def _server(capacity, max_batch=2, **kw):
    cfg = _smoke_cfg(compressor="randtopk", k=8)
    params = transformer.init_model(jax.random.key(0), cfg)
    rt = Runtime(mesh=None, training=False)
    make_cache = lambda: transformer.init_cache(params, cfg, rt, 1, 8)
    return StreamingServer(
        params, steps.make_arena_top_step(cfg, rt, 1), make_cache,
        max_batch=max_batch, capacity=capacity,
        x_shape=(1, 1, cfg.d_model), **kw)


def test_slot_reuse_after_close_resets_state():
    """A closed session's slot is reclaimed for the next admission, and the
    serve loop resets its cache row to the fresh template before reuse."""
    server = _server(capacity=1)
    s1 = server._session_for(11, endpoint=None)
    assert s1.slot == 0
    # simulate served progress in slot 0
    server.arena.cache["pos"] = server.arena.cache["pos"].at[0].set(5)
    s1.closed = True
    s2 = server._session_for(22, endpoint=None)
    assert s2.slot == 0 and s1.slot == -1       # reclaimed, not duplicated
    assert ("reset", None, 0) in server._arena_ops
    server._process([])                          # serve loop applies resets
    assert server._arena_ops == []
    assert int(np.asarray(server.arena.cache["pos"])[0]) == 0


def test_arena_full_raises_at_admission():
    # eviction off and a zero admission timeout: the third admission has
    # no free, closed, or evictable slot and must fail loudly
    server = _server(capacity=2, evict_idle=False, admit_timeout=0.0)
    server._session_for(1, endpoint=None)
    server._session_for(2, endpoint=None)
    with pytest.raises(RuntimeError, match="arena full"):
        server._session_for(3, endpoint=None)


def test_full_arena_evicts_lru_idle_session():
    """With eviction on, a full arena LRU-evicts the idlest session's row
    to host (the serve loop fetches it before the row is reused) and a
    later frame from the evicted session re-admits it with its exact
    pre-eviction state."""
    server = _server(capacity=2)
    ev0 = server.registry.counter("slot_evictions_total").value
    re0 = server.registry.counter("slot_readmissions_total").value
    s1 = server._session_for(1, endpoint=None)
    s2 = server._session_for(2, endpoint=None)
    s1.last_active, s2.last_active = 1.0, 2.0           # s1 is the LRU
    # simulate served progress so eviction has real state to preserve
    server.arena.cache["pos"] = server.arena.cache["pos"].at[0].set(5)
    s3 = server._session_for(3, endpoint=None)
    assert s3.slot == 0 and s1.slot == -1               # s1 evicted
    assert s1.host_state is not None                    # sentinel until fetch
    server._process([])                 # serve loop: fetch -> reset
    assert int(np.asarray(s1.host_state["pos"])) == 5   # state reached host
    assert int(np.asarray(server.arena.cache["pos"])[0]) == 0   # row reset
    assert server.registry.counter("slot_evictions_total").value == ev0 + 1
    # s2 closes; s1's re-admission restores its row into the freed slot
    s2.closed = True
    with server._lock:
        server._ensure_resident(s1)
    assert s1.slot >= 0
    server._process([])                 # serve loop: restore
    assert s1.host_state is None
    assert int(np.asarray(server.arena.cache["pos"])[s1.slot]) == 5
    assert server.registry.counter("slot_readmissions_total").value == re0 + 1


def test_stopped_serve_loop_releases_the_arena():
    """Once the serve loop has stopped, the server no longer pins the
    arena's device arrays, though its owner still holds the server."""
    import threading

    server = _server(capacity=2)
    assert server.arena.cache is not None
    loop = threading.Thread(target=server.serve_loop, daemon=True)
    loop.start()
    server.shutdown()
    loop.join(30.0)
    assert not loop.is_alive()
    assert server.arena.cache is None and server.arena.xbuf is None


def test_slot_churn_cycles_and_resets_every_row():
    """Admit/close/admit N >> capacity: the FIFO free deque cycles slot
    reuse through EVERY row (the old `list.pop(0)` + append re-issued the
    coldest id, hiding reuse-after-close bugs), each reused row is
    template-reset exactly when reused, and rows holding live sessions are
    never spuriously reset."""
    cap = 3
    server = _server(capacity=cap, evict_idle=False)
    # pin one live session for the whole churn — its row must never reset
    pinned = server._session_for(1000, endpoint=None)
    server._process([])
    server.arena.cache["pos"] = server.arena.cache["pos"].at[
        pinned.slot].set(99)
    issued = []
    for i in range(10):                     # 10 admissions over 2 free rows
        sess = server._session_for(i, endpoint=None)
        server._process([])                 # serve loop applies the ops
        pos = np.asarray(server.arena.cache["pos"])
        assert pos[sess.slot] == 0, \
            f"row {sess.slot} reused without a template reset"
        issued.append(sess.slot)
        server.arena.cache["pos"] = server.arena.cache["pos"].at[
            sess.slot].set(i + 10)          # marker: this row served i
        sess.closed = True
    free_rows = sorted(set(range(cap)) - {pinned.slot})
    # cycling: every window of len(free_rows) admissions touches them all
    for w in range(len(issued) - len(free_rows) + 1):
        assert sorted(set(issued[w:w + len(free_rows)])) == free_rows, \
            f"slot reuse not cycling: {issued}"
    assert int(np.asarray(server.arena.cache["pos"])[pinned.slot]) == 99


@pytest.mark.slow
def test_repeated_runs_do_not_grow_live_buffers():
    """`engine._serving_steps` pins compiled programs ON PURPOSE (cross-run
    warm cache) — but repeated `run_streaming` calls must not accumulate
    device buffers beyond it, and `clear_serving_steps` must release the
    cache on demand (the old unbounded `functools.lru_cache` could not)."""
    import gc

    from repro.runtime import engine

    cfg = _smoke_cfg(compressor="randtopk", k=8)
    params = transformer.init_model(jax.random.key(0), cfg)
    kw = dict(n_clients=2, prompt_len=2, gen=3, max_batch=2, params=params)
    run_streaming(cfg, **kw)        # populate the cache, pay every compile
    gc.collect()
    n0 = len(jax.live_arrays())
    for _ in range(3):
        run_streaming(cfg, **kw)
    gc.collect()
    n1 = len(jax.live_arrays())
    assert n1 <= n0 + 8, f"live arrays grew {n0} -> {n1} across reruns"
    assert len(engine._STEP_CACHE) >= 1
    released = engine.clear_serving_steps()
    assert released >= 1 and len(engine._STEP_CACHE) == 0
    # the next run recompiles from an empty cache and still serves
    run_streaming(cfg, **kw)
    assert len(engine._STEP_CACHE) == 1


def test_inactive_slots_do_not_advance():
    """The active-slot mask: inactive rows pass through the donated step
    bit-identically — position and KV never move for a slot that received
    no frame in a flush."""
    cfg = _smoke_cfg(compressor="randtopk", k=8)
    params = transformer.init_model(jax.random.key(0), cfg)
    rt = Runtime(mesh=None, training=False)
    step = jax.jit(steps.make_arena_top_step(cfg, rt, 1))
    cache = jax.tree.map(
        lambda a: jnp.stack([a] * 3),
        transformer.init_cache(params, cfg, rt, 1, 8))
    xbuf = jnp.asarray(np.random.RandomState(0).randn(
        4, 1, 1, cfg.d_model).astype(np.float32))
    active = jnp.asarray([True, False, True])
    _, new = step(params, xbuf, cache, active)
    assert np.asarray(new["pos"]).tolist() == [1, 0, 1]
    old_kv = jax.tree.leaves(cache["kv"])
    new_kv = jax.tree.leaves(new["kv"])
    for o, n in zip(old_kv, new_kv):
        np.testing.assert_array_equal(np.asarray(o[1]), np.asarray(n[1]))
        assert not np.array_equal(np.asarray(o[0]), np.asarray(n[0]))


# ---------------------------------------------------------------------------
# In-place arena step: parity with the flush-stacked step, write-only rows
# ---------------------------------------------------------------------------

def _random_arena(params, cfg, rt, capacity, max_len, seed):
    """An arena of `capacity` rows whose every leaf holds random bits and
    whose positions are ragged, several past `max_len` (a wrapped ring)."""
    one = transformer.init_cache(params, cfg, rt, 1, max_len)
    rng = np.random.RandomState(seed)

    def fill(a):
        shape = (capacity,) + a.shape
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
        if a.dtype == jnp.int32:                  # pos
            return jnp.asarray(rng.randint(0, 3 * max_len, shape), jnp.int32)
        return jnp.asarray(rng.randn(*shape), a.dtype)

    arena = jax.tree.map(fill, one)
    for name in ("k_scale", "v_scale"):           # int8 codes' scales
        if name in arena.get("kv", {}):
            arena["kv"][name] = jnp.abs(arena["kv"][name]) * 0.01
    return arena


def _leaf(tree, path):
    for p in path:
        tree = tree[p.key]
    return tree


ARENA_PARITY = [
    # (arch, dtype, kv bits, n_layers, cut): dense and moe write in place,
    # hybrid (mamba state) keeps the masked per-row body
    ("qwen3-8b", "bfloat16", 16, 3, 0),
    ("qwen3-8b", "bfloat16", 16, 3, 1),
    ("qwen3-8b", "bfloat16", 8, 3, 0),
    ("qwen3-8b", "bfloat16", 8, 3, 1),
    ("qwen3-8b", "float32", 16, 3, 2),
    ("granite-moe-1b-a400m", "float32", 16, 2, 1),
    ("zamba2-7b", "float32", 16, 0, 1),
]


@pytest.mark.parametrize("arch,dtype,bits,n_layers,cut", ARENA_PARITY,
                         ids=[f"{a}-{d}-kv{b}-cut{c}"
                              for a, d, b, _, c in ARENA_PARITY])
def test_arena_step_matches_top_step_and_writes_only_active_rows(
        arch, dtype, bits, n_layers, cut):
    """The donated arena step against `make_top_step` (the flush-stacked
    reference) over ragged positions, a wrapped ring and a random active
    mask: active rows' tokens and caches are bit-identical to the
    reference; the only bits that move in an active row are its new
    entries at (layer >= cut, slot pos % size); inactive rows keep every
    bit; positions advance on active rows only."""
    cfg = configs.get(arch, smoke=True)
    cfg = cfg.with_(dtype=dtype, param_dtype=dtype,
                    **({"n_layers": n_layers} if n_layers else {}))
    rt = Runtime(mesh=None, training=False, kv_cache_bits=bits)
    params = transformer.init_model(jax.random.key(0), cfg)
    cap, max_len = 6, 8
    arena = _random_arena(params, cfg, rt, cap, max_len, seed=cut + bits)
    rng = np.random.RandomState(7 + cut)
    active = rng.rand(cap) < 0.5
    active[:2] = [True, False]
    xbuf = jnp.asarray(rng.randn(cap + 1, 1, 1, cfg.d_model), cfg.adtype())
    old = jax.tree.map(np.asarray, arena)

    ref_tok, ref = jax.jit(steps.make_top_step(cfg, rt, cut))(
        params, xbuf[:cap], arena)
    ref = jax.tree.map(np.asarray, ref)
    step = jax.jit(steps.make_arena_top_step(cfg, rt, cut),
                   donate_argnums=(2,))
    tok, new = step(params, xbuf, arena, jnp.asarray(active))
    new = jax.tree.map(np.asarray, new)

    np.testing.assert_array_equal(np.asarray(tok)[active],
                                  np.asarray(ref_tok)[active])
    np.testing.assert_array_equal(new["pos"],
                                  np.where(active, old["pos"] + 1,
                                           old["pos"]))
    for path, n in jax.tree_util.tree_leaves_with_path(new):
        o = _leaf(old, path)
        r = _leaf(ref, path)
        np.testing.assert_array_equal(n[active], r[active], err_msg=str(path))
        np.testing.assert_array_equal(n[~active], o[~active],
                                      err_msg=str(path))
    if "kv" in new and set(new) == {"pos", "kv"}:
        # an active row moves only at its written position of each top layer
        size = new["kv"]["k"].shape[4]
        for name, n in new["kv"].items():
            written = np.zeros(n.shape[:5], bool)
            for row in np.flatnonzero(active):
                written[row, cut:, 0, :, old["pos"][row] % size] = True
            o = old["kv"][name]
            np.testing.assert_array_equal(n[~written], o[~written],
                                          err_msg=name)
            assert not np.array_equal(n[written], o[written]), name


#: ops that would touch a whole arena leaf: a re-select, a layout or
#: scan-buffer copy, a transpose, a zero-filled output, a layer merge
WHOLE_LEAF_OPS = ("select", "copy", "transpose", "broadcast", "concatenate")


def _whole_leaf_ops(hlo: str, n_elems: int):
    """(op, shape) of every instruction in `hlo` (fused computations
    included) whose output has exactly `n_elems` elements."""
    found = []
    for m in re.finditer(r"= \w+\[([\d,]*)\]\S* ([\w-]+)\(", hlo):
        dims = [int(d) for d in m.group(1).split(",") if d]
        if m.group(2) in WHOLE_LEAF_OPS and int(np.prod(dims)) == n_elems:
            found.append((m.group(2), m.group(1)))
    return found


def _unaliased_cache_params(hlo: str):
    """Entry parameters holding a cache leaf that the executable does not
    alias to an output (`input_output_alias`)."""
    aliased = {int(a) for a in re.findall(
        r"\{[\d,]*\}: \((\d+), \{", hlo.split("\n", 1)[0])}
    params = re.findall(
        r"parameter\((\d+)\).*op_name=\"cache\[([^\"]*)\"", hlo)
    assert params, "no cache parameter in the program"
    return [name for num, name in params if int(num) not in aliased]


@pytest.mark.parametrize("bits", [16, 8])
def test_arena_step_programs_touch_no_whole_leaf(bits):
    """The served step programs, as the server jits them (`fused_step`,
    `arena_step`, cache donated), hold no select, copy, transpose,
    broadcast or concatenate the size of an arena KV leaf, and alias
    every cache leaf in place: only the rows' new entries are written."""
    from repro.runtime.server import jit_serving_steps

    cfg = _smoke_cfg(compressor="randtopk", k=8).with_(n_layers=3)
    rt = Runtime(mesh=None, training=False, kv_cache_bits=bits)
    params = transformer.init_model(jax.random.key(0), cfg)
    cap, max_len, rows = 5, 7, 4
    cache = jax.tree.map(lambda a: jnp.stack([a] * cap),
                         transformer.init_cache(params, cfg, rt, 1, max_len))
    leaf = cache["kv"]["k"]
    n_elems = int(np.prod(leaf.shape))
    others = [a for a in jax.tree.leaves(params) if a.size == n_elems]
    assert not others, "the leaf's element count must be unique"
    top, fused = jit_serving_steps(steps.make_arena_top_step(cfg, rt, 1),
                                   dtype=jnp.float32)
    xbuf = jnp.zeros((cap + 1, 1, 1, cfg.d_model), jnp.float32)
    active = jnp.ones((cap,), bool)
    x = jnp.asarray(np.random.RandomState(0).randn(
        rows, 1, 1, cfg.d_model).astype(np.float32))
    payload = _wire_payload(C.make_compressor("randtopk", k=8), x)
    slots = jnp.arange(rows, dtype=jnp.int32)
    programs = {
        "arena_step": top.lower(params, xbuf, cache, active),
        "fused_step": fused.lower(params, xbuf, payload, slots, cache,
                                  active)}
    for name, lowered in programs.items():
        hlo = lowered.compile().as_text()
        assert f"jit_{name}" in hlo.split("\n", 1)[0]
        assert _whole_leaf_ops(hlo, n_elems) == [], name
        assert _unaliased_cache_params(hlo) == [], name
