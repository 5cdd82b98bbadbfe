"""Distributed-correctness tests: mesh-vs-single-device exactness for every
block family, the MoE reduce-scatter combine, and chunked-vs-sequential WKV6.

These run in a subprocess with 8 forced host devices so the main pytest
process keeps its single-device view (per the dry-run isolation rule).
"""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.models import rwkv, ssm, transformer
from repro.models.config import Runtime


def _run_subprocess(code: str):
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo")
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


@pytest.mark.slow
def test_mesh_matches_single_device_all_families():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        import repro.configs as configs
        from repro.models import transformer
        from repro.models.config import Runtime
        from repro.data.pipeline import make_lm_batch

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        for arch in ["yi_6b", "qwen3_moe_235b_a22b", "zamba2_7b",
                     "rwkv6_1p6b", "llama_3_2_vision_90b", "whisper_tiny"]:
            cfg = configs.get(arch, smoke=True)
            params = transformer.init_model(jax.random.key(0), cfg)
            batch = make_lm_batch(jax.random.key(1), cfg, 4, 32)
            rt0 = Runtime(mesh=None, training=True, moe_capacity=8.0)
            l0, _ = transformer.forward(params, cfg, rt0, batch)
            with mesh:
                rt = Runtime(mesh=mesh, training=True, moe_capacity=8.0)
                lm, _ = jax.jit(
                    lambda p, b: transformer.forward(p, cfg, rt, b))(params,
                                                                     batch)
            diff = float(jnp.abs(lm - l0).max())
            assert diff < 2e-4, (arch, diff)
            print(arch, "ok", diff)
    """)
    assert out.count("ok") == 6


def test_rwkv_chunk_matches_scan():
    cfg = configs.get("rwkv6_1p6b", smoke=True)
    p = rwkv.init_rwkv_time(jax.random.key(0), cfg)
    x = 0.5 * jax.random.normal(jax.random.key(1), (2, 64, cfg.d_model))
    yc, (Sc, _) = rwkv.rwkv_time_mix(
        p, cfg, Runtime(mesh=None, rwkv_mode="chunk", rwkv_chunk=16), x)
    ys, (Ss, _) = rwkv.rwkv_time_mix(
        p, cfg, Runtime(mesh=None, rwkv_mode="scan", rwkv_chunk=16), x)
    np.testing.assert_allclose(np.asarray(yc), np.asarray(ys), atol=2e-5)
    np.testing.assert_allclose(np.asarray(Sc), np.asarray(Ss), atol=2e-5)


@pytest.mark.slow
def test_rwkv_decode_matches_full_sequence():
    """Token-by-token decode must agree with the full-sequence evaluation."""
    cfg = configs.get("rwkv6_1p6b", smoke=True)
    rt = Runtime(mesh=None, training=False)
    params = transformer.init_model(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab,
                              dtype=jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    full_logits, _ = transformer.forward(params, cfg, rt, batch)
    cache = transformer.init_cache(params, cfg, rt, 2, 16)
    outs = []
    for i in range(8):
        logits, cache = transformer.decode_step(params, cfg, rt,
                                                toks[:, i: i + 1], cache)
        outs.append(logits)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full_logits),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.slow
def test_mamba_decode_matches_full_sequence():
    cfg = configs.get("zamba2_7b", smoke=True)
    rt = Runtime(mesh=None, training=False, ssm_chunk=8)
    params = transformer.init_model(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab,
                              dtype=jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    full_logits, _ = transformer.forward(params, cfg, rt, batch)
    cache = transformer.init_cache(params, cfg, rt, 2, 16)
    outs = []
    for i in range(8):
        logits, cache = transformer.decode_step(params, cfg, rt,
                                                toks[:, i: i + 1], cache)
        outs.append(logits)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full_logits),
                               atol=2e-3, rtol=2e-3)


def test_attention_decode_matches_full_sequence():
    cfg = configs.get("yi_6b", smoke=True)
    rt = Runtime(mesh=None, training=False)
    params = transformer.init_model(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab,
                              dtype=jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    full_logits, _ = transformer.forward(params, cfg, rt, batch)
    cache = transformer.init_cache(params, cfg, rt, 2, 16)
    outs = []
    for i in range(8):
        logits, cache = transformer.decode_step(params, cfg, rt,
                                                toks[:, i: i + 1], cache)
        outs.append(logits)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full_logits),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.slow
def test_sliding_window_masks_old_positions():
    cfg = configs.get("yi_6b", smoke=True).with_(sliding_window=4)
    rt = Runtime(mesh=None, training=False)
    params = transformer.init_model(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (1, 12), 0, cfg.vocab,
                              dtype=jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    logits, _ = transformer.forward(params, cfg, rt, batch)
    # decode with a window-sized rolling cache reproduces the same logits
    cache = transformer.init_cache(params, cfg, rt, 1, 12)
    assert cache["kv"]["k"].shape[3] == 4  # rolling buffer == window
    outs = []
    for i in range(12):
        lg, cache = transformer.decode_step(params, cfg, rt,
                                            toks[:, i: i + 1], cache)
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(logits),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.slow
def test_int8_kv_cache_decode_close_to_fp():
    import dataclasses
    cfg = configs.get("yi_6b", smoke=True)
    params = transformer.init_model(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab,
                              dtype=jnp.int32)
    outs = {}
    for bits in (16, 8):
        rt = Runtime(mesh=None, training=False, kv_cache_bits=bits)
        cache = transformer.init_cache(params, cfg, rt, 2, 16)
        if bits == 8:
            assert cache["kv"]["k"].dtype == jnp.int8
            assert "k_scale" in cache["kv"]
        o = []
        for i in range(8):
            lg, cache = transformer.decode_step(params, cfg, rt,
                                                toks[:, i: i + 1], cache)
            o.append(lg)
        outs[bits] = jnp.concatenate(o, 1)
    rel = float(jnp.abs(outs[16] - outs[8]).max() /
                jnp.abs(outs[16]).max())
    assert rel < 0.05, rel


def test_error_feedback_shapes_and_residual():
    from repro.core.error_feedback import ef_topk_forward
    o = jax.random.normal(jax.random.key(0), (6, 32))
    err = jnp.zeros((4, 32))
    labels = jnp.array([0, 1, 2, 3, 0, 1])
    view, mask, new_err = ef_topk_forward(o, err, labels, 4, 4)
    np.testing.assert_array_equal(np.asarray(mask.sum(-1)), 4)
    # residual = dropped mass, stored per class
    assert float(jnp.abs(new_err).sum()) > 0
    # a second step adds the residual back before selection
    view2, _, _ = ef_topk_forward(o, new_err, labels, 4, 4)
    assert not np.allclose(np.asarray(view), np.asarray(view2))
