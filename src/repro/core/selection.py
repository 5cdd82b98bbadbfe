"""Element-selection primitives for cut-layer sparsification.

All functions operate on the LAST axis (the instance feature axis `d` in the
paper) and are fully batched over leading axes. Top-k is by magnitude, as in
the paper ("preserve top-k elements ... in terms of magnitude").

TPU adaptation: the randomized selection of Eq. (7) — k sequential draws
without replacement, each draw picking the top-k pool w.p. (1 - alpha) — is
vectorized exactly:

  * the number of non-top-k picks is m ~ Binomial(k, alpha) (the per-draw pool
    choice in Eq. 7 is i.i.d. Bernoulli(alpha); only the *within-pool*
    distribution renormalizes as pools deplete), clipped to the pool sizes;
  * uniform-without-replacement within a pool == Gumbel-top-m on uniform
    weights (exponential race), which is branch-free and layout-friendly.

Backend dispatch: `topk_mask` / `randtopk_mask` accept `backend=`:

  * ``"xla"``    — `jax.lax.top_k`-based reference path (default off-TPU);
  * ``"pallas"`` — the bisection kernel in `kernels/randtopk` (interpret mode
    when not running on a TPU, Mosaic when on one), which also emits the
    Eq. (7) randomized mask in-kernel;
  * ``"auto"``   — pallas on a TPU runtime, xla elsewhere; the default, and
    overridable via the REPRO_SELECTION_BACKEND environment variable.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

_NEG_INF = float("-inf")

BACKENDS = ("auto", "xla", "pallas")


def _resolve_backend(backend):
    backend = backend or os.environ.get("REPRO_SELECTION_BACKEND", "auto")
    if backend not in BACKENDS:
        raise ValueError(f"selection backend {backend!r} not in {BACKENDS}")
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    return backend


def pallas_interpret(interpret=None) -> bool:
    """A kernel's `interpret` flag: an explicit value wins; None is
    interpret mode off a TPU (CPU validation) and Mosaic on one."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def topk_mask(x: jax.Array, k: int, *, backend: str = None) -> jax.Array:
    """Boolean mask of the k largest-|x| elements along the last axis."""
    d = x.shape[-1]
    if k >= d:
        return jnp.ones_like(x, dtype=bool)
    if _resolve_backend(backend) == "pallas":
        from repro.kernels.randtopk import ops as tk_ops

        return tk_ops.topk_mask(x, k)
    mag = jnp.abs(x).astype(jnp.float32)
    kth = jax.lax.top_k(mag, k)[0][..., -1:]
    # Break ties deterministically: strictly-greater always in; equal-to-kth
    # admitted left-to-right until k elements are set.
    gt = mag > kth
    eq = mag == kth
    need = k - jnp.sum(gt, axis=-1, keepdims=True)
    eq_rank = jnp.cumsum(eq.astype(jnp.int32), axis=-1)
    return gt | (eq & (eq_rank <= need))


def mask_from_indices(idx: jax.Array, d: int) -> jax.Array:
    """Scatter boolean mask of shape (..., d) from integer indices (..., k)."""
    onehot = jax.nn.one_hot(idx, d, dtype=bool)
    return jnp.any(onehot, axis=-2)


def pack_mask_words(mask: jax.Array) -> jax.Array:
    """Pack a boolean support mask (..., d) into little-endian uint32 words
    (..., ceil(d/32)) — the device-resident layout of the `mask` payload
    kind (bit j of the row mask is bit j%32 of word j//32)."""
    d = mask.shape[-1]
    nw = (d + 31) // 32
    m = mask.astype(jnp.uint32)
    pad = nw * 32 - d
    if pad:
        m = jnp.pad(m, [(0, 0)] * (m.ndim - 1) + [(0, pad)])
    m = m.reshape(m.shape[:-1] + (nw, 32))
    shifts = jnp.arange(32, dtype=jnp.uint32)
    # set bits are disjoint across the lane axis, so a sum is a bitwise OR
    return jnp.sum(m << shifts, axis=-1, dtype=jnp.uint32)


def unpack_mask_words(words: jax.Array, d: int) -> jax.Array:
    """Inverse of `pack_mask_words`: uint32 words (..., ceil(d/32)) to a
    boolean mask (..., d). Bits at positions >= d are ignored."""
    nw = words.shape[-1]
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (jnp.asarray(words).astype(jnp.uint32)[..., None] >> shifts) \
        & jnp.uint32(1)
    flat = bits.reshape(words.shape[:-1] + (nw * 32,))
    return flat[..., :d].astype(bool)


def _select_m_from_pool(scores: jax.Array, pool: jax.Array, m: jax.Array, k: int):
    """Select exactly `m` elements uniformly w/o replacement from `pool`.

    scores : i.i.d. Gumbel noise, shape (..., d)
    pool   : bool  (..., d)
    m      : int32 (..., 1), 0 <= m <= min(k, pool size)
    Returns a bool mask. Uses the m-th largest in-pool Gumbel as threshold.
    """
    s = jnp.where(pool, scores, _NEG_INF)
    top = jax.lax.top_k(s, k)[0]                      # (..., k) sorted desc
    # threshold = m-th largest (1-based); m == 0 -> select nothing
    gather = jnp.clip(m - 1, 0, k - 1)
    thr = jnp.take_along_axis(top, gather, axis=-1)   # (..., 1)
    sel = s >= thr
    return jnp.where(m > 0, sel, jnp.zeros_like(sel))


def binomial_nontop_count(key: jax.Array, alpha: float, k: int, d: int,
                          batch_shape) -> jax.Array:
    """m ~ Binomial(k, alpha) per instance, clipped to the pool sizes —
    the number of non-top-k picks in Eq. (7). Shape (*batch_shape, 1)."""
    draws = jax.random.bernoulli(key, alpha, tuple(batch_shape) + (k,))
    m = jnp.sum(draws.astype(jnp.int32), axis=-1, keepdims=True)
    return jnp.clip(m, 0, min(k, d - k))


def randtopk_mask(x: jax.Array, k: int, alpha: float, key: jax.Array,
                  *, backend: str = None) -> jax.Array:
    """Randomized top-k selection mask, Eq. (7) of the paper.

    Each of the k draws (without replacement) picks a top-k element with
    probability 1-alpha (uniform within the remaining top-k pool) and a
    non-top-k element with probability alpha (uniform within the remaining
    non-top-k pool). Exactly k elements are selected.
    """
    d = x.shape[-1]
    if k >= d:
        return jnp.ones_like(x, dtype=bool)
    if _resolve_backend(backend) == "pallas":
        from repro.kernels.randtopk import ops as tk_ops

        return tk_ops.randtopk_mask(x, k, alpha, key)
    kb, kg = jax.random.split(key)
    is_top = topk_mask(x, k, backend="xla")
    m = binomial_nontop_count(kb, alpha, k, d, x.shape[:-1])
    g = jax.random.gumbel(kg, x.shape, dtype=jnp.float32)
    sel_top = _select_m_from_pool(g, is_top, k - m, k)
    sel_non = _select_m_from_pool(g, ~is_top, m, k)
    return sel_top | sel_non


def kth_magnitude_threshold(x: jax.Array, k: int) -> jax.Array:
    """|x| value of the k-th largest element (the Pallas kernel's oracle)."""
    mag = jnp.abs(x).astype(jnp.float32)
    return jax.lax.top_k(mag, k)[0][..., -1]
