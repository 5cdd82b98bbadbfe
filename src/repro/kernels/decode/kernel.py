"""Fused payload-decode Pallas kernels: dequant + scatter + projection.

One kernel family replaces the decode-side seam that used to be two XLA
passes (dequantize the u8 codes, then scatter/pad into dense rows): for
every payload kind the wire leaves become dense f32 rows in a single
lane-parallel pass over a VMEM-resident row tile, with an optional
cut-projection epilogue (`rows @ w`) fused behind the scatter so the
decoded activation can leave the kernel already projected.

Two entry points:

  * `decode_rows_kernel` — flat (rows, d) decode, gridded over row blocks;
    the `backend="pallas"` implementation behind every kind of
    `core.compressors.payload_to_dense` (the scatter-only kernel in
    `kernels.randtopk` covered just the sparse kinds).
  * `decode_to_slots_kernel` — the serving-arena variant: one grid step per
    flush row, the slot ids streamed in via scalar prefetch
    (`pltpu.PrefetchScalarGridSpec`) drive the OUTPUT block index map, and
    the arena's cut-activation buffer is passed through
    `input_output_aliases` so untouched slot rows keep their contents and
    the decoded rows land in `xbuf[slots]` without a separate scatter pass
    (on TPU the buffer is updated in place; interpret mode copies).

Numerics match the two-pass XLA decode bit-for-bit for dense/slice/sparse
kinds (values cross the kernel verbatim; the compare-and-select scatter
adds exact zeros elsewhere). Quant kinds run the same `lo + (code + 0.5) *
step` multiply-add, which either compiler may contract into an FMA — the
1-ulp convention pinned by tests/test_arena.py and docs/performance.md.

Layout notes: the feature axis lives whole in VMEM (d <= 16k f32), rows
tile over the grid; the k-wide support loop is a branch-free
compare-and-select accumulate. Everything in the kernel bodies lowers
under Mosaic: integer leaves are widened to int32 outside the kernel, a
traced column is read by a masked lane reduction (`_lane`), never a
dynamic lane slice, and the prefix sum is built from lane rotations.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.selection import pallas_interpret

#: wire leaves each payload kind carries, in `payload.WIRE_FIELDS` order
KIND_LEAVES = {
    "dense": ("values",),
    "slice": ("values",),
    "sparse": ("values", "indices"),
    "quant": ("values", "header"),
    "sparse_quant": ("values", "indices", "header"),
    "mask": ("values", "indices"),   # indices = packed u32 bitmask words
}


def _lane(a, j):
    """Column `j` of a (br, w) tile as (br, 1), for a traced `j`: a
    compare-and-select lane reduction (Mosaic lowers no dynamic lane
    slice). Exact — every other lane contributes a zero."""
    cols = jax.lax.broadcasted_iota(jnp.int32, a.shape, a.ndim - 1)
    return jnp.sum(jnp.where(cols == j, a, jnp.zeros_like(a)), axis=-1,
                   keepdims=True)


def _dequant_block(codes, hdr):
    """`lo + (code + 0.5) * step` on a (br, k) tile — identical arithmetic
    to `core.compressors._dequant` (see the 1-ulp FMA note there)."""
    lo, step = hdr[..., 0:1], hdr[..., 1:2]
    return lo + (codes.astype(jnp.float32) + 0.5) * step


def _scatter_block(vals, idx, d: int):
    """Branch-free compare-and-select scatter of a (br, k) support onto
    (br, d) lanes; exact for unique per-row indices (duplicates sum)."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, vals.shape[:-1] + (d,),
                                     vals.ndim - 1)

    def body(j, acc):
        return acc + jnp.where(lanes == _lane(idx, j), _lane(vals, j), 0.0)

    return jax.lax.fori_loop(0, vals.shape[-1], body,
                             jnp.zeros(vals.shape[:-1] + (d,), jnp.float32))


def _mask_bits_block(words, d: int):
    """Per-lane support bits of a (br, W) packed int32 tile -> bool (br, d).

    Lane l's bit lives at bit l%32 of word l//32; the W-step loop broadcasts
    each word across the lanes it owns (compare-and-select, no gather)."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, words.shape[:-1] + (d,),
                                     words.ndim - 1)
    wi = lanes // 32
    sh = lanes % 32

    def body(j, acc):
        # arithmetic shift then & 1 reads bit 31 of a negative word too;
        # the carry is int32 (Mosaic keeps no i1 vector in a loop carry)
        bit = (_lane(words, j) >> sh) & 1
        return acc + jnp.where(wi == j, bit, 0)

    return jax.lax.fori_loop(0, words.shape[-1], body,
                             jnp.zeros(lanes.shape, jnp.int32)) != 0


def _cumsum_lanes(x):
    """Inclusive prefix sum along lanes via log-step shifted adds
    (Hillis-Steele): lane rotations masked below the shift, no scan or
    reduce_window primitive and no dots (the decode roofline budgets zero
    dot-flops)."""
    d = x.shape[-1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    step = 1
    while step < d:
        shifted = pltpu.roll(x, step, x.ndim - 1)
        x = x + jnp.where(lanes >= step, shifted, jnp.zeros_like(x))
        step *= 2
    return x


def _mask_expand_block(vals, words, d: int):
    """Mask-driven expand of a (br, k) value tile onto (br, d) lanes: the
    j-th value lands on the lane of the (j+1)-th set bit (ascending-index
    value order). Set bits beyond k (a hostile mask) expand to zero, exactly
    like `core.compressors.mask_expand_rows`."""
    mask = _mask_bits_block(words, d)
    pos = _cumsum_lanes(mask.astype(jnp.int32)) - 1

    def body(j, acc):
        return acc + jnp.where(mask & (pos == j), _lane(vals, j), 0.0)

    return jax.lax.fori_loop(0, vals.shape[-1], body,
                             jnp.zeros(mask.shape, jnp.float32))


def _decode_block(kind: str, leaves, d: int):
    """Wire-leaf tile(s) -> dense f32 (br, d) tile, dispatched on kind.
    Integer leaves arrive widened to int32 (`_kernel_leaves`)."""
    if kind == "dense":
        (v,) = leaves
        return v.astype(jnp.float32)
    if kind == "slice":
        (v,) = leaves
        v = v.astype(jnp.float32)
        k = v.shape[-1]
        if k == d:
            return v
        return jnp.concatenate(
            [v, jnp.zeros(v.shape[:-1] + (d - k,), jnp.float32)], axis=-1)
    if kind == "sparse":
        v, i = leaves
        return _scatter_block(v.astype(jnp.float32), i, d)
    if kind == "quant":
        c, h = leaves
        return _dequant_block(c, h)
    if kind == "sparse_quant":
        c, i, h = leaves
        return _scatter_block(_dequant_block(c, h), i, d)
    if kind == "mask":
        v, w = leaves
        return _mask_expand_block(v.astype(jnp.float32), w, d)
    raise ValueError(kind)


def _kernel_leaves(leaves):
    """Widen the wire's u8 codes / u16 indices to int32 and reinterpret the
    u32 mask words as int32 before the kernel: Mosaic casts neither u8 nor
    u16 to float, and the kernels read every integer leaf as int32."""
    out = []
    for a in leaves:
        a = jnp.asarray(a)
        if a.dtype == jnp.uint32:
            a = jax.lax.bitcast_convert_type(a, jnp.int32)
        elif jnp.issubdtype(a.dtype, jnp.integer):
            a = a.astype(jnp.int32)
        out.append(a)
    return out


def _make_rows_kernel(kind: str, d: int, project: bool, out_dtype):
    def kernel(*refs):
        if project:
            *leaf_refs, w_ref, o_ref = refs
            rows = _decode_block(kind, [r[...] for r in leaf_refs], d)
            rows = jnp.dot(rows, w_ref[...].astype(jnp.float32),
                           preferred_element_type=jnp.float32)
        else:
            *leaf_refs, o_ref = refs
            rows = _decode_block(kind, [r[...] for r in leaf_refs], d)
        o_ref[...] = rows.astype(out_dtype)

    return kernel


#: f32 elements of one (rows, d) row tile: 512 KiB keeps every row
#: kernel's live temporaries (the selection and mask-encode kernels hold
#: several tiles at once) inside v5e's 16 MiB of scoped VMEM at d = 4096
_TILE_ELEMS = 1 << 17


def _rows_blocks(leading_shape, d: int):
    """(rows, rows per grid step, pad rows) for a (..., d) row kernel: at
    most 128 rows and `_TILE_ELEMS` elements per tile, at least 8 rows (a
    full sublane group), or all rows when there are fewer."""
    assert d <= 16384, "dense row must fit a VMEM row tile"
    rows = 1
    for s in leading_shape:
        rows *= s
    br = min(max(8, min(128, _TILE_ELEMS // d)), rows)
    return rows, br, (-rows) % br


@functools.partial(jax.jit, static_argnames=("kind", "d", "dtype",
                                             "interpret"))
def decode_rows_kernel(leaves, kind: str, d: int, w=None, *,
                       dtype=jnp.float32, interpret: Optional[bool] = None):
    """Fused one-pass decode: wire leaves -> dense (or projected) rows.

    leaves : tuple of wire arrays in `KIND_LEAVES[kind]` order, common
             leading shape (...,) + trailing (k|d|2)
    w      : optional (d, p) cut-projection matrix — fused epilogue, the
             decoded rows never materialize when it is given
    Returns (..., d) [or (..., p)] in `dtype`.
    """
    lead = leaves[0].shape[:-1]
    rows, br, pad = _rows_blocks(lead, d)
    flat = [a.reshape((rows, a.shape[-1])) for a in _kernel_leaves(leaves)]
    if pad:
        flat = [jnp.pad(a, ((0, pad), (0, 0))) for a in flat]
    grid = (flat[0].shape[0] // br,)
    in_specs = [pl.BlockSpec((br, a.shape[-1]), lambda i: (i, 0))
                for a in flat]
    operands = list(flat)
    project = w is not None
    p_out = d
    if project:
        p_out = w.shape[-1]
        in_specs.append(pl.BlockSpec(w.shape, lambda i: (0, 0)))
        operands.append(w)

    out = pl.pallas_call(
        _make_rows_kernel(kind, d, project, jnp.dtype(dtype)),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, p_out), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((flat[0].shape[0], p_out),
                                       jnp.dtype(dtype)),
        interpret=pallas_interpret(interpret),
    )(*operands)
    if pad:
        out = out[:rows]
    return out.reshape(lead + (p_out,))


@functools.partial(jax.jit, static_argnames=("kind", "interpret"))
def decode_to_slots_kernel(xbuf, leaves, slots, kind: str, *,
                           interpret: Optional[bool] = None):
    """Decode flush rows straight into `xbuf[slots]`, one fused pass.

    xbuf   : (C + 1, d) arena cut-activation buffer (last row = scratch);
             ALIASED into the output — untouched rows keep their contents,
             and on TPU the update is in place (pair with a donated jit).
    leaves : tuple of stacked wire arrays, leading dim = flush rows n
    slots  : (n,) int32 arena slot per flush row (scalar-prefetched: the
             slot ids drive the output block index map, so row i's decoded
             tile is written directly to block `slots[i]` — no host-side
             dense staging and no separate scatter pass)

    Rows aimed at the same slot (the scratch-row padding convention) write
    identical zero rows, so duplicate targets are benign.

    Layout: rows live on a leading (C + 1, 1, d) axis, so each grid step's
    (1, 1, d) block spans the whole trailing (sublane, lane) extent — a
    one-row block of a (C + 1, d) array would break Mosaic's (8, 128)
    block tiling. The kernel overwrites its whole output block, so xbuf is
    never read: it stays in HBM (`pl.ANY`) and only aliases the output.
    """
    cap1, d = xbuf.shape
    assert d <= 16384, "dense row must fit a VMEM row tile"
    n = leaves[0].shape[0]
    flat = [a.reshape((n, 1, a.shape[-1])) for a in _kernel_leaves(leaves)]

    def kernel(s_ref, x_ref, *rest):
        *leaf_refs, o_ref = rest
        o_ref[0] = _decode_block(kind, [r[0] for r in leaf_refs],
                                 d).astype(xbuf.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)]
                 + [pl.BlockSpec((1, 1, a.shape[-1]),
                                 lambda i, s: (i, 0, 0))
                    for a in flat],
        out_specs=pl.BlockSpec((1, 1, d), lambda i, s: (s[i], 0, 0)))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((cap1, 1, d), xbuf.dtype),
        input_output_aliases={1: 0},    # xbuf (operand 1, after slots) -> out
        interpret=pallas_interpret(interpret),
    )(jnp.asarray(slots, jnp.int32), xbuf.reshape(cap1, 1, d), *flat)
    return out.reshape(cap1, d)
