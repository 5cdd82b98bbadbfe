"""Sharded session arena (docs/sharding.md): shard_map arena step vs the
single-device path — bit-exact tokens for every payload kind at several
mesh shapes, mesh (1,1) == mesh None, eviction/readmission under a mesh,
the inactive-slot freeze, and the pod-ring wire_row mapping.

Multi-device cases run in a subprocess with 8 forced host devices so the
main pytest process keeps its single-device view (the same isolation rule
as tests/test_distributed.py)."""
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

import repro.configs as configs
from repro.models import transformer
from repro.models.config import SplitConfig
from repro.runtime import run_streaming
from repro.runtime.arena import SlotArena


def _run_subprocess(*parts: str):
    code = "\n".join(textwrap.dedent(p) for p in parts)
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo")
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


_PRELUDE = """
    import jax, jax.numpy as jnp
    import numpy as np
    import repro.configs as configs
    from repro.models import transformer
    from repro.models.config import Runtime, SplitConfig
    from repro.launch.mesh import make_serving_mesh
    from repro.runtime import run_streaming, steps

    assert len(jax.devices()) == 8
    cfg = configs.get("qwen3-8b", smoke=True).with_(
        split=SplitConfig(cut_layer=1, compressor="randtopk", k=8))
    params = transformer.init_model(jax.random.key(0), cfg)
"""


def test_mesh_1x1_matches_unsharded():
    """The degenerate (1,1) mesh runs the full shard_map program on the
    single local device and must leave served tokens bit-identical to
    `mesh=None` — the existing parity/golden suites stay authoritative."""
    from repro.launch.mesh import make_serving_mesh
    cfg = configs.get("qwen3-8b", smoke=True).with_(
        split=SplitConfig(cut_layer=1, compressor="randtopk", k=8))
    params = transformer.init_model(jax.random.key(0), cfg)
    kw = dict(n_clients=2, prompt_len=2, gen=4, max_batch=2, params=params,
              seed=0)
    ref = run_streaming(cfg, **kw)
    got = run_streaming(cfg, mesh=make_serving_mesh(1), **kw)
    np.testing.assert_array_equal(ref["tokens"], got["tokens"])


def test_wire_row_is_identity_without_pod_and_a_block_swap_with():
    """Host-side slot -> xbuf/token row mapping: identity without a pod
    axis; with one, slot s in pod p maps to the ring-previous pod's block
    (the sharded step's forward ppermute then lands the activation on the
    slot's own block) — a permutation of the live rows, scratch fixed."""
    make_cache = lambda: {"pos": np.zeros((1,), np.int32)}
    arena = SlotArena(make_cache, 8, (1, 1, 4), np.float32)
    assert [arena.wire_row(s) for s in range(9)] == list(range(9))

    # pod geometry only touches _n_pod/capacity — no devices needed
    arena = SlotArena.__new__(SlotArena)
    arena._n_pod, arena.capacity = 2, 8
    rows = [arena.wire_row(s) for s in range(8)]
    assert rows == [4, 5, 6, 7, 0, 1, 2, 3]        # blocks swapped
    assert sorted(rows) == list(range(8))          # a permutation
    assert arena.wire_row(8) == 8                  # scratch row pinned


@pytest.mark.slow
def test_sharded_step_matches_unsharded_and_freezes_inactive():
    """Direct step drive on 8 forced devices: the shard_map arena step's
    tokens are bit-identical to the mesh-less step and every new-cache
    leaf agrees within a tolerance, at data-only, data x model, and pod
    meshes — and inactive rows never move (bit-identical). The arena
    holds random entries at ragged positions, several past the ring's
    size, so each shard writes its rows' new K/V in place at different
    slots: an active row moves only there, in the layers above the cut.

    Why a tolerance on the KV leaves: each new K/V element is a
    d_model-term f32 dot product, and a shard runs the same per-row
    program over fewer rows, for which XLA may pick another dot
    kernel and summation order. That moves an element by a few ulps of
    the accumulated magnitude (observed: 452 of 16384 elements, at most
    1.4e-6, on XLA:CPU); 1e-5 absolute + 1e-5 relative bounds 256 f32
    terms of unit-scale products with margin."""
    out = _run_subprocess(_PRELUDE, """
        rt = Runtime(mesh=None, training=False)
        cap = 8
        ref_step = jax.jit(steps.make_arena_top_step(cfg, rt, 1))
        rng = np.random.RandomState(3)
        cache0 = jax.tree.map(
            lambda a: jnp.asarray(rng.randint(0, 24, (cap,) + a.shape)
                                  if a.dtype == jnp.int32 else
                                  rng.randn(cap, *a.shape), a.dtype),
            transformer.init_cache(params, cfg, rt, 1, 8))
        pos0 = np.asarray(cache0["pos"])
        assert pos0.max() >= 8                     # a wrapped ring
        size = cache0["kv"]["k"].shape[4]
        written = np.zeros(cache0["kv"]["k"].shape[:5], bool)
        for row in range(0, cap, 2):
            written[row, 1:, 0, :, pos0[row] % size] = True
        xbuf = jnp.asarray(np.random.RandomState(0).randn(
            cap + 1, 1, 1, cfg.d_model).astype(np.float32))
        active = jnp.asarray([True, False] * (cap // 2))
        ref_tok, ref_cache = ref_step(params, xbuf, cache0, active)
        for spec in [dict(), dict(model=4), dict(model=2, pod=2)]:
            mesh = make_serving_mesh(8, **spec)
            step = jax.jit(
                steps.make_arena_top_step(cfg, rt, 1, mesh=mesh))
            # the serve loop stages slot s's activation at wire_row(s) and
            # reads its token back there (SlotArena.wire_row: the
            # ingestion-pod block; identity without a pod axis) — the
            # direct drive must present the same layout
            n_pod = dict(mesh.shape).get("pod", 1)
            block = cap // n_pod
            perm = np.asarray([((s // block - 1) % n_pod) * block
                               + s % block for s in range(cap)])
            xw = np.asarray(xbuf).copy()
            xw[perm] = np.asarray(xbuf)[:cap]
            tok, new = step(params, jnp.asarray(xw), cache0, active)
            np.testing.assert_array_equal(np.asarray(ref_tok),
                                          np.asarray(tok)[perm])
            for r, n in zip(jax.tree.leaves(ref_cache),
                            jax.tree.leaves(new)):
                np.testing.assert_allclose(np.asarray(n), np.asarray(r),
                                           rtol=1e-5, atol=1e-5)
            # frozen rows: bit-identical to the pre-step cache
            for o, n in zip(jax.tree.leaves(cache0), jax.tree.leaves(new)):
                np.testing.assert_array_equal(np.asarray(o)[1::2],
                                              np.asarray(n)[1::2])
            # active rows: only their new entries moved
            for name in ("k", "v"):
                o = np.asarray(cache0["kv"][name])
                n = np.asarray(new["kv"][name])
                np.testing.assert_array_equal(n[~written], o[~written])
            print("mesh", dict(mesh.shape), "ok")
    """)
    assert out.count("ok") == 3


@pytest.mark.slow
def test_sharded_serving_bit_exact_all_payload_kinds():
    """End-to-end `run_streaming` on 8 forced devices: served tokens under
    a data-only (8,1) and a tensor-parallel (2,4) mesh are bit-identical
    to the single-device arena, for all five payload kinds."""
    out = _run_subprocess(_PRELUDE, """
        kinds = ["identity", "size_reduction:k=8", "randtopk:k=8",
                 "quant:bits=4", "randtopk_quant:k=8,bits=8"]
        meshes = [make_serving_mesh(8), make_serving_mesh(8, model=4)]
        kw = dict(n_clients=2, prompt_len=2, gen=4, max_batch=2,
                  params=params, seed=0)
        for spec in kinds:
            ref = run_streaming(cfg, compressor_mix=[spec], **kw)["tokens"]
            for mesh in meshes:
                got = run_streaming(cfg, compressor_mix=[spec], mesh=mesh,
                                    **kw)["tokens"]
                np.testing.assert_array_equal(ref, got)
            print(spec, "ok")
    """)
    assert out.count("ok") == 5


@pytest.mark.slow
def test_pod_mesh_serving_bit_exact_and_uses_ring():
    """A pod mesh (2,2,2) routes the cut activation over the pod ring
    (wire_row + the step's ppermute pair) and still serves bit-identical
    tokens; the lowered program actually contains the ring collective."""
    out = _run_subprocess(_PRELUDE, """
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(8, model=2, pod=2)
        kw = dict(n_clients=3, prompt_len=2, gen=4, max_batch=2,
                  params=params, seed=0)
        ref = run_streaming(cfg, **kw)["tokens"]
        got = run_streaming(cfg, mesh=mesh, **kw)["tokens"]
        np.testing.assert_array_equal(ref, got)
        print("pod-serve PASS")

        rt = Runtime(mesh=None, training=False)
        step = steps.make_arena_top_step(cfg, rt, 1, mesh=mesh)
        cap = 8
        cache = jax.tree.map(
            lambda a: jnp.stack([a] * cap),
            transformer.init_cache(params, cfg, rt, 1, 8))
        xbuf = jnp.zeros((cap + 1, 1, 1, cfg.d_model), jnp.float32)
        txt = jax.jit(step).lower(
            params, xbuf, cache, jnp.ones((cap,), bool)).as_text()
        assert ("collective_permute" in txt or "collective-permute" in txt
                or "ppermute" in txt), "pod ring collective missing"
        print("ring-collective PASS")
    """)
    assert out.count("PASS") == 2


@pytest.mark.slow
def test_sharded_eviction_readmission_token_parity():
    """Capacity pressure under a mesh: 6 clients over 2 resident slots
    forces LRU evict-to-host / restore cycles through the sharded arena,
    and every session's tokens stay bit-identical to the uncontended
    single-device run (dedup + FIFO fetch-before-restore: a KV row never
    double-advances across an evict/readmit)."""
    out = _run_subprocess(_PRELUDE, """
        from repro.runtime.server import StreamingServer, _EVICTING
        from repro.runtime import steps
        mesh = make_serving_mesh(8, model=2)
        kw = dict(n_clients=6, prompt_len=2, gen=4, max_batch=2,
                  params=params, seed=0)
        ref = run_streaming(cfg, **kw)["tokens"]
        got = run_streaming(cfg, mesh=mesh, capacity=2, **kw)
        np.testing.assert_array_equal(ref, got["tokens"])
        snap = got["metrics"]
        ev = snap["slot_evictions_total"]["series"][0]["value"]
        assert ev >= 1, f"no evictions under 6 sessions / 2 slots: {ev}"
        print("evict parity ok", ev,
              snap["slot_readmissions_total"]["series"][0]["value"])

        # deterministic fetch/restore round trip through SHARDED rows:
        # evicted state reaches host bit-exact and restores into a
        # different row of the NamedSharding'd arena
        rt = Runtime(mesh=None, training=False)
        make_cache = lambda: transformer.init_cache(params, cfg, rt, 1, 8)
        server = StreamingServer(
            params, steps.make_arena_top_step(cfg, rt, 1, mesh=mesh),
            make_cache, max_batch=2, capacity=2,
            x_shape=(1, 1, cfg.d_model), mesh=mesh)
        assert server.arena.capacity == 8           # padded to the mesh
        s1 = server._session_for(1, endpoint=None)
        s2 = server._session_for(2, endpoint=None)
        s1.last_active, s2.last_active = 1.0, 2.0
        server.arena.cache["pos"] = server.arena.cache["pos"].at[
            s1.slot].set(5)
        s3 = server._session_for(3, endpoint=None)  # evicts LRU s1
        assert s1.slot == -1 and s1.host_state is _EVICTING
        server._process([])                         # fetch -> reset
        assert int(np.asarray(s1.host_state["pos"])) == 5
        s3.closed = True
        with server._lock:
            server._ensure_resident(s1)
        server._process([])                         # restore
        assert s1.host_state is None and s1.slot >= 0
        assert int(np.asarray(
            server.arena.cache["pos"])[s1.slot]) == 5
        print("sharded evict/restore ok")
    """)
    assert out.count("ok") == 2
