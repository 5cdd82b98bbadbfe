"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.randtopk import kernel as tk_kernel, ops as tk_ops, \
    ref as tk_ref
from repro.kernels.quant import kernel as q_kernel, ref as q_ref

SHAPES = [(4, 64), (17, 128), (128, 256), (3, 5, 96), (1, 8192)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_topk_kernel_matches_ref(shape, dtype):
    x = jax.random.normal(jax.random.key(0), shape, dtype)
    k = min(8, shape[-1] - 1)
    mask, thr = tk_kernel.topk_mask_threshold(x, k)
    ref_mask = tk_ref.topk_mask(x, k)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(ref_mask))
    ref_thr = tk_ref.kth_threshold(x, k)
    np.testing.assert_allclose(np.asarray(thr), np.asarray(ref_thr),
                               atol=1e-4, rtol=1e-4)


@given(st.integers(1, 63), st.integers(1, 7), st.integers(0, 1000))
@settings(max_examples=15, deadline=None)
@pytest.mark.slow
def test_topk_kernel_property(k, rows, seed):
    x = jax.random.normal(jax.random.key(seed), (rows, 64))
    mask, _ = tk_kernel.topk_mask_threshold(x, k)
    np.testing.assert_array_equal(np.asarray(mask.sum(-1)), k)
    np.testing.assert_array_equal(
        np.asarray(mask), np.asarray(tk_ref.topk_mask(x, k)))


def test_randtopk_kernel_counts_and_distribution():
    x = jax.random.normal(jax.random.key(0), (8, 64))
    m = tk_ops.randtopk_mask(x, 8, 0.25, jax.random.key(1))
    np.testing.assert_array_equal(np.asarray(m.sum(-1)), 8)
    # alpha=0 must agree with the deterministic kernel mask
    m0 = tk_ops.randtopk_mask(x, 8, 0.0, jax.random.key(2))
    np.testing.assert_array_equal(
        np.asarray(m0), np.asarray(tk_ops.topk_mask(x, 8)))


def test_randtopk_kernel_matches_xla_reference():
    """The in-kernel Eq. (7) selection must reproduce the XLA path draw for
    draw — same key, same Binomial split, same Gumbel race."""
    x = jax.random.normal(jax.random.key(3), (16, 128))
    for alpha in (0.0, 0.3, 1.0):
        for seed in range(3):
            key = jax.random.key(100 + seed)
            mk = tk_ops.randtopk_mask(x, 8, alpha, key)
            mr = tk_ref.randtopk_mask(x, 8, alpha, key)
            np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr),
                                          err_msg=f"alpha={alpha} s={seed}")


def test_randtopk_kernel_alpha_statistics():
    """Non-top-k pick frequency from the fused kernel tracks alpha*k."""
    d, k, alpha = 64, 8, 0.3
    x = jax.random.normal(jax.random.key(0), (1, d))
    is_top = np.asarray(tk_ops.topk_mask(x, k))[0]
    keys = jax.random.split(jax.random.key(7), 300)
    masks = np.stack([np.asarray(tk_ops.randtopk_mask(x, k, alpha, kk))[0]
                      for kk in keys])
    non_top = masks[:, ~is_top].sum(axis=1)
    assert abs(non_top.mean() - alpha * k) < 0.35, non_top.mean()


def test_topk_kernel_ties():
    x = jnp.concatenate([jnp.ones((4, 16)), 2 * jnp.ones((4, 16))], -1)
    mask, _ = tk_kernel.topk_mask_threshold(x, 20)
    np.testing.assert_array_equal(np.asarray(mask.sum(-1)), 20)
    assert bool(mask[:, 16:].all())  # all the 2s selected


@pytest.mark.parametrize("name,x,k", [
    ("ties", jnp.tile(jnp.array([[3.0, 1.0, 1.0, 1.0, 1.0, 2.0, 2.0, 0.5]]),
                      (3, 1)), 4),
    ("all_equal", jnp.full((4, 32), 1.5), 5),
    ("zeros", jnp.zeros((4, 32)), 6),
    ("negatives", -jnp.abs(jax.random.normal(jax.random.key(8), (5, 64))), 7),
    ("mixed_sign_ties", jnp.array([[-2.0, 2.0, -2.0, 1.0, -1.0, 0.0]]), 3),
    ("k_equals_d", jax.random.normal(jax.random.key(9), (3, 16)), 16),
    ("single_spike", jnp.eye(8, 128) * 100.0, 2),
])
def test_topk_kernel_adversarial_parity(name, x, k):
    """Interpret-mode kernel vs selection.topk_mask on adversarial inputs:
    exact ties, all-zero rows, negatives, k = d."""
    from repro.core import selection

    ref = selection.topk_mask(x, k, backend="xla")
    via_dispatch = selection.topk_mask(x, k, backend="pallas")
    np.testing.assert_array_equal(np.asarray(via_dispatch), np.asarray(ref),
                                  err_msg=name)
    if k < x.shape[-1]:
        mask, _ = tk_kernel.topk_mask_threshold(x, k)
        np.testing.assert_array_equal(np.asarray(mask), np.asarray(ref),
                                      err_msg=name)
        np.testing.assert_array_equal(np.asarray(mask.sum(-1)), k)


def test_selection_backend_dispatch():
    """backend='pallas' and backend='xla' agree through the public API; the
    env override REPRO_SELECTION_BACKEND is honored."""
    import os

    from repro.core import selection

    x = jax.random.normal(jax.random.key(10), (6, 96))
    np.testing.assert_array_equal(
        np.asarray(selection.topk_mask(x, 9, backend="pallas")),
        np.asarray(selection.topk_mask(x, 9, backend="xla")))
    key = jax.random.key(11)
    np.testing.assert_array_equal(
        np.asarray(selection.randtopk_mask(x, 9, 0.25, key,
                                           backend="pallas")),
        np.asarray(selection.randtopk_mask(x, 9, 0.25, key, backend="xla")))
    with pytest.raises(ValueError):
        selection.topk_mask(x, 9, backend="cuda")
    os.environ["REPRO_SELECTION_BACKEND"] = "xla"
    try:
        assert selection._resolve_backend(None) == "xla"
    finally:
        del os.environ["REPRO_SELECTION_BACKEND"]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_quant_kernel_matches_ref(shape, bits):
    x = jax.random.normal(jax.random.key(1), shape)
    code, deq, lo, step = q_kernel.quantize(x, bits)
    rc, rdeq, rlo, rstep = q_ref.quantize(x, bits)
    np.testing.assert_array_equal(np.asarray(code), np.asarray(rc))
    np.testing.assert_allclose(np.asarray(deq), np.asarray(rdeq), atol=1e-5)
    np.testing.assert_allclose(np.asarray(lo), np.asarray(rlo).reshape(lo.shape),
                               atol=1e-6)


def test_quant_kernel_constant_rows():
    x = jnp.ones((4, 32))
    code, deq, lo, step = q_kernel.quantize(x, 4)
    assert not bool(jnp.isnan(deq).any())


def test_quant_kernel_bf16():
    x = jax.random.normal(jax.random.key(2), (8, 128), jnp.bfloat16)
    code, deq, _, _ = q_kernel.quantize(x, 8)
    assert deq.dtype == jnp.bfloat16
    rc, rdeq, _, _ = q_ref.quantize(x, 8)
    np.testing.assert_array_equal(np.asarray(code), np.asarray(rc))


# ---------------------------------------------------------------------------
# Flash attention kernel
# ---------------------------------------------------------------------------

from repro.kernels.flashattn import kernel as fa_kernel, ref as fa_ref


@pytest.mark.parametrize("cfg", [
    dict(B=2, S=128, Hq=4, Hkv=2, hd=64, causal=True, window=0),
    dict(B=1, S=256, Hq=8, Hkv=8, hd=32, causal=True, window=0),
    dict(B=2, S=128, Hq=4, Hkv=1, hd=64, causal=False, window=0),
    dict(B=1, S=256, Hq=4, Hkv=2, hd=64, causal=True, window=64),
])
def test_flash_attention_matches_ref(cfg):
    q = jax.random.normal(jax.random.key(0), (cfg["B"], cfg["S"], cfg["Hq"],
                                              cfg["hd"]), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (cfg["B"], cfg["S"], cfg["Hkv"],
                                              cfg["hd"]), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (cfg["B"], cfg["S"], cfg["Hkv"],
                                              cfg["hd"]), jnp.float32)
    o = fa_kernel.flash_attention(q, k, v, causal=cfg["causal"],
                                  window=cfg["window"], bq=64, bk=64)
    r = fa_ref.attention(q, k, v, causal=cfg["causal"], window=cfg["window"])
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=3e-5)


def test_flash_attention_bf16():
    q = jax.random.normal(jax.random.key(0), (1, 128, 4, 64), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), (1, 128, 2, 64), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), (1, 128, 2, 64), jnp.bfloat16)
    o = fa_kernel.flash_attention(q, k, v, bq=64, bk=64)
    r = fa_ref.attention(q, k, v)
    assert o.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(o, dtype=np.float32),
                               np.asarray(r, dtype=np.float32), atol=3e-2)


def test_flash_attention_matches_model_sdpa():
    """The kernel must agree with the model's attention (the path it would
    replace on a TPU runtime)."""
    import repro.configs as configs
    from repro.models import attention as A
    from repro.models.config import Runtime

    cfg = configs.get("yi_6b", smoke=True)
    p = A.init_attention(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 128, cfg.d_model))
    rt = Runtime(mesh=None, attn_chunk=64)
    y_model = A.full_attention(p, cfg, rt, x)
    # rebuild q/k/v exactly as the model does, then apply the kernel
    pos = jnp.arange(128)
    q, k, v = A._project_qkv(p, cfg, x, x, pos[None], pos[None])
    o = fa_kernel.flash_attention(q, k, v, bq=64, bk=64)
    y_kernel = o.reshape(2, 128, -1) @ p["wo"]
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model),
                               atol=3e-4, rtol=3e-4)
