"""Unit + property tests for the paper's selection primitives (Eq. 7)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import selection


@given(st.integers(1, 31), st.integers(2, 5), st.integers(0, 10_000))
@settings(max_examples=40, deadline=None)
def test_topk_mask_selects_exactly_k(k, rows, seed):
    d = 32
    x = jax.random.normal(jax.random.key(seed), (rows, d))
    mask = selection.topk_mask(x, k)
    assert mask.shape == x.shape
    np.testing.assert_array_equal(np.asarray(mask.sum(-1)), k)


@given(st.integers(1, 31), st.floats(0.0, 1.0), st.integers(0, 10_000))
@settings(max_examples=40, deadline=None)
@pytest.mark.slow
def test_randtopk_mask_selects_exactly_k(k, alpha, seed):
    d = 32
    x = jax.random.normal(jax.random.key(seed), (3, d))
    mask = selection.randtopk_mask(x, k, alpha, jax.random.key(seed + 1))
    np.testing.assert_array_equal(np.asarray(mask.sum(-1)), k)


def test_topk_mask_matches_lax_topk():
    x = jax.random.normal(jax.random.key(0), (64, 128))
    mask = selection.topk_mask(x, 7)
    _, idx = jax.lax.top_k(jnp.abs(x), 7)
    ref = np.zeros(x.shape, bool)
    np.put_along_axis(ref, np.asarray(idx), True, axis=-1)
    np.testing.assert_array_equal(np.asarray(mask), ref)


def test_randtopk_alpha0_equals_topk():
    x = jax.random.normal(jax.random.key(0), (16, 64))
    m0 = selection.randtopk_mask(x, 9, 0.0, jax.random.key(1))
    mt = selection.topk_mask(x, 9)
    np.testing.assert_array_equal(np.asarray(m0), np.asarray(mt))


def test_randtopk_alpha_statistics():
    """Non-top-k selection frequency should track alpha (Eq. 7)."""
    d, k, alpha = 64, 8, 0.3
    x = jax.random.normal(jax.random.key(0), (1, d))
    is_top = np.asarray(selection.topk_mask(x, k))[0]
    n_trials = 2000
    keys = jax.random.split(jax.random.key(42), n_trials)
    masks = jax.vmap(lambda kk: selection.randtopk_mask(x, k, alpha, kk))(keys)
    masks = np.asarray(masks)[:, 0, :]
    # expected non-top-k picks per trial = alpha * k
    non_top_picks = masks[:, ~is_top].sum(axis=1)
    assert abs(non_top_picks.mean() - alpha * k) < 0.15, non_top_picks.mean()
    # within the non-top-k pool selection should be ~uniform
    freq = masks[:, ~is_top].mean(axis=0)
    assert freq.std() < 0.05


def test_randtopk_mask_ties():
    x = jnp.ones((2, 16))
    m = selection.randtopk_mask(x, 4, 0.2, jax.random.key(0))
    np.testing.assert_array_equal(np.asarray(m.sum(-1)), 4)


def test_k_equals_d():
    x = jax.random.normal(jax.random.key(0), (4, 8))
    assert bool(selection.topk_mask(x, 8).all())
    assert bool(selection.randtopk_mask(x, 8, 0.5, jax.random.key(1)).all())


def test_kth_threshold():
    x = jax.random.normal(jax.random.key(3), (10, 50))
    thr = selection.kth_magnitude_threshold(x, 5)
    mag = np.abs(np.asarray(x))
    ref = np.sort(mag, axis=-1)[:, -5]
    np.testing.assert_allclose(np.asarray(thr), ref, rtol=1e-6)
