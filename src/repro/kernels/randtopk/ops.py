"""jit'd public wrappers around the randtopk Pallas kernels.

These are the `backend="pallas"` implementations behind
`core.selection.topk_mask` / `randtopk_mask` (interpret mode off-TPU,
Mosaic on a TPU runtime, per `core.selection.pallas_interpret`). The
deterministic support and the Eq. (7) randomization (Binomial pool split +
Gumbel race) both run in-kernel; only the PRNG draws (Gumbel noise,
Binomial counts) are generated outside with `jax.random` and streamed in
as kernel operands.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.randtopk import kernel


@partial(jax.jit, static_argnames=("k", "interpret"))
def topk_mask(x, k: int, *, interpret=None):
    if k >= x.shape[-1]:
        return jnp.ones_like(x, dtype=bool)
    mask, _ = kernel.topk_mask_threshold(x, k, interpret=interpret)
    return mask


@partial(jax.jit, static_argnames=("k", "alpha", "interpret"))
def randtopk_mask(x, k: int, alpha: float, key, *, interpret=None):
    """Kernel-backed Eq. (7) selection mask (fused top-k + Gumbel race)."""
    from repro.core import selection

    d = x.shape[-1]
    if k >= d:
        return jnp.ones_like(x, dtype=bool)
    kb, kg = jax.random.split(key)
    m = selection.binomial_nontop_count(kb, alpha, k, d, x.shape[:-1])
    g = jax.random.gumbel(kg, x.shape, dtype=jnp.float32)
    return kernel.randtopk_mask_kernel(x, g, m, k, interpret=interpret)
