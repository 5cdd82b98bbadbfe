"""Pallas TPU kernels: per-row top-k threshold/mask and the Eq. (7)
randomized-selection mask, both by bisection.

TPU adaptation of the paper's top-k selection. GPU implementations sort (or
warp-shuffle); sorting is hostile to the VPU/MXU lane layout. Instead we
bisect a score range: 32 rounds of branch-free vectorized compare-and-count
over a VMEM-resident row tile converge the target-count threshold to
~2^-32 of the row range, then a final compare emits the mask. O(32 d)
elementwise work per row, no data movement, fully lane-parallel.

The same count-bisection primitive runs three times for the randomized
selection of Eq. (7): once on |x| for the deterministic top-k pool, then on
i.i.d. Gumbel scores restricted to the top-k pool (k - m picks) and to its
complement (m picks) — uniform-without-replacement via the Gumbel race, with
m ~ Binomial(k, alpha) precomputed per row by the caller. This is the
`backend="pallas"` implementation behind `core.selection.randtopk_mask`.

Exact-count guarantee: after bisection, elements >= hi are always admitted
(provably fewer than the target), elements in the final [lo, hi) band are
admitted left-to-right until the target is met — so every row selects
exactly `target` elements even under ties or unconverged bisection.

Layout: rows tiled over the grid, the feature axis lives in VMEM whole
(d <= 16k floats per row = 64 KiB). Outputs: bool mask (rows, d) and (for
the deterministic kernel) the threshold (rows,) — the wire payload
(values, indices) is extracted by the caller where needed. The in-kernel
band rank is the log-step lane prefix sum `kernels.decode._cumsum_lanes`
(Mosaic has no cumsum lowering).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.selection import pallas_interpret
from repro.kernels.decode.kernel import _cumsum_lanes, _rows_blocks

N_ITERS = 32
_BIG = 1e30  # finite +/- sentinel; keeps bisection arithmetic NaN-free


def _count_select(scores, pool, target):
    """Mask of exactly `target` largest `scores` within `pool`, per row.

    scores : f32 (br, d); pool : bool (br, d); target : int32 (br, 1).
    Bisection invariants: count(s >= lo) >= target, count(s >= hi) < target.
    `target` must not exceed the pool size; target == 0 selects nothing.
    """
    s = jnp.where(pool, scores, -_BIG)
    hi0 = jnp.max(s, axis=-1, keepdims=True)
    lo = jnp.min(jnp.where(pool, scores, _BIG), axis=-1, keepdims=True)
    lo = jnp.minimum(lo, hi0)  # empty pool: collapse to a sane interval
    # start strictly above the max so count(>= hi) == 0 < target holds
    hi = hi0 + (jnp.abs(hi0) + (hi0 - lo) + 1.0) * 1e-6

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((s >= mid).astype(jnp.int32), axis=-1, keepdims=True)
        ge = cnt >= target
        return jnp.where(ge, mid, lo), jnp.where(ge, hi, mid)

    lo, hi = jax.lax.fori_loop(0, N_ITERS, body, (lo, hi))
    # elements above the final band are always in; the band fills the rest
    # left-to-right (exact-k even under ties / unconverged bisection)
    gt = s >= hi
    band = (s >= lo) & ~gt
    need = target - jnp.sum(gt.astype(jnp.int32), axis=-1, keepdims=True)
    band_rank = _cumsum_lanes(band.astype(jnp.int32))
    sel = gt | (band & (band_rank <= need))
    return sel & (target > 0), lo


def _topk_mask_kernel(x_ref, mask_ref, thr_ref, *, k: int):
    x = x_ref[...]                                     # (br, d) in VMEM
    mag = jnp.abs(x.astype(jnp.float32))
    target = jnp.full(mag.shape[:-1] + (1,), k, jnp.int32)
    mask, thr = _count_select(mag, jnp.ones_like(mag, dtype=bool), target)
    mask_ref[...] = mask.astype(jnp.int32)
    thr_ref[...] = thr


def _randtopk_mask_kernel(x_ref, g_ref, m_ref, mask_ref, *, k: int):
    """Eq. (7) in-kernel: top-k pool by |x| bisection, then two Gumbel-race
    pool selections (k - m from the top pool, m from its complement)."""
    x = x_ref[...]
    g = g_ref[...]                                     # i.i.d. Gumbel (br, d)
    m = m_ref[...].astype(jnp.int32)                   # (br, 1) non-top picks
    mag = jnp.abs(x.astype(jnp.float32))
    k_arr = jnp.full(mag.shape[:-1] + (1,), k, jnp.int32)
    is_top, _ = _count_select(mag, jnp.ones_like(mag, dtype=bool), k_arr)
    sel_top, _ = _count_select(g, is_top, k_arr - m)
    sel_non, _ = _count_select(g, ~is_top, m)
    mask_ref[...] = (sel_top | sel_non).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_mask_threshold(x, k: int, *, interpret: Optional[bool] = None):
    """x: (..., d) -> (mask bool (..., d), thr f32 (...,)).

    `interpret` defaults to interpret mode off a TPU (CPU validation) and
    to the Mosaic kernel on one (`core.selection.pallas_interpret`).
    """
    orig_shape, d = x.shape, x.shape[-1]
    rows, br, pad = _rows_blocks(orig_shape[:-1], d)
    x2 = x.reshape(rows, d)
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    grid = (x2.shape[0] // br,)

    # int32 mask and (rows, 1) threshold: Mosaic stores neither i1 tiles
    # nor 1-D row blocks
    mask, thr = pl.pallas_call(
        functools.partial(_topk_mask_kernel, k=k),
        grid=grid,
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                   pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((x2.shape[0], d), jnp.int32),
                   jax.ShapeDtypeStruct((x2.shape[0], 1), jnp.float32)],
        interpret=pallas_interpret(interpret),
    )(x2)
    mask, thr = mask[:rows] != 0, thr[:rows, 0]
    return mask.reshape(orig_shape), thr.reshape(orig_shape[:-1])


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def randtopk_mask_kernel(x, gumbel, m, k: int, *,
                         interpret: Optional[bool] = None):
    """Eq. (7) randomized-selection mask, fused in one Pallas kernel.

    x      : (..., d) activations
    gumbel : (..., d) f32 i.i.d. Gumbel noise
    m      : (..., 1) int32 non-top-k pick counts, pre-clipped to
             [0, min(k, d - k)] (see selection.binomial_nontop_count)
    Returns a bool mask with exactly k selected per row.
    """
    orig_shape, d = x.shape, x.shape[-1]
    rows, br, pad = _rows_blocks(orig_shape[:-1], d)
    x2 = x.reshape(rows, d)
    g2 = gumbel.reshape(rows, d).astype(jnp.float32)
    m2 = m.reshape(rows, 1).astype(jnp.int32)
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
        g2 = jnp.pad(g2, ((0, pad), (0, 0)))
        m2 = jnp.pad(m2, ((0, pad), (0, 0)))
    grid = (x2.shape[0] // br,)

    mask = pl.pallas_call(
        functools.partial(_randtopk_mask_kernel, k=k),
        grid=grid,
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x2.shape[0], d), jnp.int32),
        interpret=pallas_interpret(interpret),
    )(x2, g2, m2)
    return (mask[:rows] != 0).reshape(orig_shape)
