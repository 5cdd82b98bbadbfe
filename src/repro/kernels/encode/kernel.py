"""Fused payload-encode Pallas kernels: gather + quantize + bit-pack.

The encode-side mirror of `kernels.decode`. Two kernel families:

  * `encode_rows_kernel` — (rows, d) activation [+ selection mask] -> the
    payload's wire leaves in one lane-parallel VMEM pass per row tile:
    support gather (the transpose of the decode scatter: positions from a
    log-step lane prefix-sum over the mask), in-kernel uniform quantization
    (identical arithmetic to `core.compressors`, same 1-ulp FMA convention
    as the decode side), and for the `mask` kind the packed u32 bitmask
    words. One dispatch per payload kind.
  * `pack_bits_kernel` — the device bit-packer: a flat stream of unsigned
    ints at `width` bits each becomes little-endian u32 words, bit j of the
    stream landing at bit j%32 of word j//32 — the exact bitstream
    `core.wire._pack_bits` produces on host (its two-aligned-word scheme at
    32-bit granularity: 32 values span exactly `width` words, each word a
    masked lane reduction over the values that land in it).

Neither family touches `jnp.dot`, so the compiled encode programs cost
zero dot-flops — `roofline.analysis.serving_encode_costs` budgets them as
pure byte movement, audited in `benchmarks/serve_throughput.py`.

Values cross the gather verbatim (bit-exact vs the XLA encode for
dense/slice/sparse/mask); quant kinds re-run the host's min/max + floor
grid, which either compiler may contract/reassociate — the <= 1-ulp
convention pinned by tests/test_encode_kernels.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.selection import pallas_interpret
from repro.kernels.decode.kernel import _cumsum_lanes, _rows_blocks

#: wire leaves each payload kind's encode kernel emits, in
#: `payload.WIRE_FIELDS` order (dtypes are the kernel-friendly wide forms;
#: `ops.encode_rows` narrows them to the wire dtypes)
KIND_OUTPUTS = {
    "dense": ("values",),
    "slice": ("values",),
    "sparse": ("values", "indices"),
    "quant": ("values", "header"),
    "sparse_quant": ("values", "indices", "header"),
    "mask": ("values", "indices"),
}


def _gather_block(x, mask, k: int):
    """Compact the masked lanes of a (br, d) tile into (br, k) values +
    (br, k) int32 indices, ascending-index order — the transpose of
    `kernels.decode._scatter_block` (compare-and-select, no gather op; the
    j-th column is written by a select, not a dynamic lane update, which
    Mosaic does not lower)."""
    d = x.shape[-1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, x.shape[:-1] + (d,),
                                     x.ndim - 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape[:-1] + (k,),
                                    x.ndim - 1)
    pos = _cumsum_lanes(mask.astype(jnp.int32)) - 1
    hit = mask & (pos < k)

    def body(j, acc):
        vals, idx = acc
        sel = hit & (pos == j)
        vj = jnp.sum(jnp.where(sel, x, 0.0), axis=-1, keepdims=True)
        ij = jnp.sum(jnp.where(sel, lanes, 0), axis=-1, keepdims=True)
        vals = jnp.where(cols == j, vj, vals)
        idx = jnp.where(cols == j, ij, idx)
        return vals, idx

    init = (jnp.zeros(x.shape[:-1] + (k,), jnp.float32),
            jnp.zeros(x.shape[:-1] + (k,), jnp.int32))
    return jax.lax.fori_loop(0, k, body, init)


def _mask_words_block(mask, d: int):
    """Pack a (br, d) boolean tile into (br, ceil(d/32)) int32 words — the
    bit pattern of the `mask` payload's u32 device row layout (bit l%32 of
    word l//32). Set bits are disjoint, so the int32 sum is the bitwise OR
    (bit 31 wraps to the sign bit, the same 32 bits)."""
    nw = (d + 31) // 32
    m = mask.astype(jnp.int32)
    pad = nw * 32 - d
    if pad:
        m = jnp.concatenate(
            [m, jnp.zeros(m.shape[:-1] + (pad,), jnp.int32)], axis=-1)
    shifts = jax.lax.broadcasted_iota(jnp.int32, m.shape[:-1] + (32,),
                                      m.ndim - 1)
    cols = []
    for j in range(nw):
        seg = m[..., 32 * j: 32 * (j + 1)]
        cols.append(jnp.sum(seg << shifts, axis=-1, keepdims=True))
    return jnp.concatenate(cols, axis=-1)


def _quant_block(vals, bits: int, *, selected: bool):
    """In-kernel uniform quantization of a (br, w) tile.

    `selected=False` is `core.compressors._quant_encode` (full-row range,
    degenerate step -> 1.0 via `step <= 0`); `selected=True` is the
    RandTopKQuant variant (range over the selected values, `hi > lo`
    guard). Same formulas, so host and kernel agree to the FMA ulp.
    """
    lo = jnp.min(vals, axis=-1, keepdims=True)
    hi = jnp.max(vals, axis=-1, keepdims=True)
    n_bins = 2 ** bits
    if selected:
        step = jnp.where(hi > lo, (hi - lo) / n_bins, 1.0)
    else:
        step = (hi - lo) / n_bins
        step = jnp.where(step <= 0, 1.0, step)
    code = jnp.clip(jnp.floor((vals - lo) / step), 0, n_bins - 1)
    return code.astype(jnp.int32), jnp.concatenate([lo, step], axis=-1)


def _encode_block(kind: str, x, mask, d: int, k: int, bits: int):
    """(br, d) activation tile -> wire-leaf tile(s), dispatched on kind."""
    if kind == "dense":
        return (x.astype(jnp.float32),)
    if kind == "slice":
        return (x[..., :k].astype(jnp.float32),)
    if kind == "sparse":
        vals, idx = _gather_block(x.astype(jnp.float32), mask, k)
        return vals, idx
    if kind == "quant":
        codes, hdr = _quant_block(x.astype(jnp.float32), bits,
                                  selected=False)
        return codes, hdr
    if kind == "sparse_quant":
        vals, idx = _gather_block(x.astype(jnp.float32), mask, k)
        codes, hdr = _quant_block(vals, bits, selected=True)
        return codes, idx, hdr
    if kind == "mask":
        vals, _ = _gather_block(x.astype(jnp.float32), mask, k)
        return vals, _mask_words_block(mask, d)
    raise ValueError(kind)


def _out_descr(kind: str, d: int, k: int):
    """(width, dtype) per output leaf of `_encode_block`, in order."""
    nw = (d + 31) // 32
    return {
        "dense": ((d, jnp.float32),),
        "slice": ((k, jnp.float32),),
        "sparse": ((k, jnp.float32), (k, jnp.int32)),
        "quant": ((d, jnp.int32), (2, jnp.float32)),
        "sparse_quant": ((k, jnp.int32), (k, jnp.int32), (2, jnp.float32)),
        "mask": ((k, jnp.float32), (nw, jnp.int32)),
    }[kind]


@functools.partial(jax.jit, static_argnames=("kind", "k", "bits",
                                             "interpret"))
def encode_rows_kernel(x, mask=None, *, kind: str, k: int = 0,
                       bits: int = 0, interpret: Optional[bool] = None):
    """Fused one-pass encode: activation rows -> wire-leaf arrays.

    x    : (..., d) activation
    mask : (..., d) selection mask (int32/bool; required for the sparse /
           sparse_quant / mask kinds, ignored otherwise) — produced by
           `core.selection`'s kernels, so mask -> gather -> quantize ->
           (bit)pack never leaves the device
    Returns the tuple of leaf arrays named by `KIND_OUTPUTS[kind]`, common
    leading shape `x.shape[:-1]`.
    """
    d = x.shape[-1]
    lead = x.shape[:-1]
    rows, br, pad = _rows_blocks(lead, d)
    flat = [x.reshape((rows, d))]
    needs_mask = kind in ("sparse", "sparse_quant", "mask")
    if needs_mask:
        assert mask is not None, f"{kind} encode needs a selection mask"
        flat.append(mask.reshape((rows, d)).astype(jnp.int32))
    if pad:
        flat = [jnp.pad(a, ((0, pad), (0, 0))) for a in flat]
    grid = (flat[0].shape[0] // br,)
    descr = _out_descr(kind, d, k)

    def kernel(*refs):
        if needs_mask:
            x_ref, m_ref, *o_refs = refs
            m = m_ref[...] != 0
        else:
            x_ref, *o_refs = refs
            m = None
        outs = _encode_block(kind, x_ref[...], m, d, k, bits)
        for o_ref, o in zip(o_refs, outs):
            o_ref[...] = o.astype(o_ref.dtype)

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((br, a.shape[-1]), lambda i: (i, 0))
                  for a in flat],
        out_specs=[pl.BlockSpec((br, w), lambda i: (i, 0))
                   for w, _ in descr],
        out_shape=[jax.ShapeDtypeStruct((flat[0].shape[0], w), dt)
                   for w, dt in descr],
        interpret=pallas_interpret(interpret),
    )(*flat)
    outs = [o[:rows].reshape(lead + (o.shape[-1],)) if pad
            else o.reshape(lead + (o.shape[-1],)) for o in outs]
    return tuple(outs)


def _pack_block(v, width: int):
    """(bg, 32) int32 value tile -> (bg, width) int32 words (the u32 bit
    patterns). Value i of a group lands at bit (i * width) % 32 of word
    (i * width) // 32, spilling its high bits into the next word —
    `core.wire._pack_bits`'s scheme at 32-bit granularity. Each word is a
    masked lane reduction over the group's shifted values (their bits are
    disjoint, so the sum is the OR), written into its column by a select.
    Do not replace it with per-lane slices ORed into words and
    concatenated: that form is exact in interpret mode but drops bits
    under Mosaic on a v5e."""
    if width < 32:
        v = v & ((1 << width) - 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, v.ndim - 1)
    word, off = (lane * width) // 32, (lane * width) % 32
    low = jax.lax.shift_left(v, off)
    spill = (off > 0) & (off + width > 32)
    high = jax.lax.shift_right_logical(v, jnp.where(spill, 32 - off, 0))
    cols = jax.lax.broadcasted_iota(jnp.int32, v.shape[:-1] + (width,),
                                    v.ndim - 1)
    out = jnp.zeros(cols.shape, jnp.int32)
    for j in range(width):
        part = (jnp.where(word == j, low, 0)
                | jnp.where(spill & (word == j - 1), high, 0))
        out = jnp.where(cols == j, jnp.sum(part, axis=-1, keepdims=True),
                        out)
    return out


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def pack_bits_kernel(vals, width: int, *, interpret: Optional[bool] = None):
    """Device bit-pack: flat unsigned ints -> little-endian u32 words.

    The returned (ceil(n/32) * width,) u32 buffer's first
    `ceil(n * width / 8)` bytes are exactly `core.wire._pack_bits(vals,
    width)` (padding values are zero and land strictly after the real
    bits, so host truncation is a suffix cut).
    """
    assert 1 <= width <= 32
    vals = vals.reshape(-1)
    n = vals.shape[0]
    groups = (n + 31) // 32
    bg = min(256, groups)
    gpad = (-groups) % bg
    v = jnp.pad(vals.astype(jnp.uint32), (0, (groups + gpad) * 32 - n))
    v = jax.lax.bitcast_convert_type(v.reshape(groups + gpad, 32), jnp.int32)

    def kernel(v_ref, o_ref):
        o_ref[...] = _pack_block(v_ref[...], width)

    out = pl.pallas_call(
        kernel,
        grid=((groups + gpad) // bg,),
        in_specs=[pl.BlockSpec((bg, 32), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bg, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((groups + gpad, width), jnp.int32),
        interpret=pallas_interpret(interpret),
    )(v)
    return jax.lax.bitcast_convert_type(out[:groups],
                                        jnp.uint32).reshape(groups * width)
