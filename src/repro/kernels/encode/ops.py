"""jit'd public wrappers around the fused encode kernels.

Three layers, mirroring `kernels.decode.ops` on the opposite side of the
wire:

  * `encode_rows` — the Pallas twin of `Compressor.encode`: activation
    rows [+ selection mask] -> a wire-dtype `Payload` in one fused pass
    (parity vs the XLA compressor encode pinned in
    tests/test_encode_kernels.py).
  * `pack_bits` — device bit-pack of a flat int stream into u32 words
    (`backend=` dispatch per the `core.selection` contract: Pallas kernel
    or the pure-jnp fallback; both produce `core.wire._pack_bits`'s exact
    bitstream).
  * `pack_payload` / `section_nbytes` / `sections_to_bytes` — the device
    wire path: every bit-packed section of `core.wire.encode_payload`'s
    layout is assembled on device as u32 words, so the host's only work
    per frame is pulling the packed buffers, truncating each to its exact
    byte length, and wrapping them in a subheader + CRC
    (`wire.encode_payload_frame_from_bytes`). Byte equality with the host
    codec is pinned in tests.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import wire
from repro.core.payload import Payload, PayloadMeta
from repro.kernels.encode import kernel


#: wire dtype each kernel output leaf narrows to, per kind
_WIRE_DTYPES = {
    "dense": (jnp.float32,),
    "slice": (jnp.float32,),
    "sparse": (jnp.float32, jnp.uint16),
    "quant": (jnp.uint8, jnp.float32),
    "sparse_quant": (jnp.uint8, jnp.uint16, jnp.float32),
    "mask": (jnp.float32, jnp.uint32),
}


def encode_rows(x, kind: str, *, k: int = 0, bits: int = 0, mask=None,
                interpret=None) -> Payload:
    """Fused one-pass encode of activation rows to a wire-dtype Payload.

    `mask` is the (..., d) selection mask (from `core.selection`'s
    kernels) for the sparse / sparse_quant / mask kinds; values come back
    in ascending-index order, matching `Compressor.encode`.
    """
    d = x.shape[-1]
    outs = kernel.encode_rows_kernel(x, mask, kind=kind, k=k, bits=bits,
                                     interpret=interpret)
    # int32 kernel words carry the u32 mask bits: reinterpret, never convert
    outs = tuple(jax.lax.bitcast_convert_type(o, dt) if dt == jnp.uint32
                 else o.astype(dt)
                 for o, dt in zip(outs, _WIRE_DTYPES[kind]))
    meta = PayloadMeta(kind, d=d, k=k if kind != "quant" else 0,
                       bits=bits if kind in ("quant", "sparse_quant")
                       else 0)
    names = kernel.KIND_OUTPUTS[kind]
    return Payload(meta=meta, **dict(zip(names, outs)))


def _pack_words_xla(vals, width: int):
    """Pure-jnp fallback of `kernel.pack_bits_kernel`: same two-aligned-
    word scheme, same (ceil(n/32) * width,) u32 buffer."""
    vals = vals.reshape(-1).astype(jnp.uint32)
    if width < 32:
        vals = vals & jnp.uint32((1 << width) - 1)
    n = vals.shape[0]
    groups = (n + 31) // 32
    v = jnp.pad(vals, (0, groups * 32 - n)).reshape(groups, 32)
    cols = [jnp.zeros((groups, 1), jnp.uint32) for _ in range(width)]
    for i in range(32):
        start = i * width
        j, off = start // 32, start % 32
        vi = v[:, i:i + 1]
        cols[j] = cols[j] | (vi << jnp.uint32(off))
        if off and off + width > 32:
            cols[j + 1] = cols[j + 1] | (vi >> jnp.uint32(32 - off))
    return jnp.concatenate(cols, axis=-1).reshape(groups * width)


def pack_bits(vals, width: int, *, backend=None):
    """Device bit-pack dispatch: flat ints -> u32 words whose first
    `ceil(n * width / 8)` bytes equal `core.wire._pack_bits`."""
    from repro.core import selection

    if selection._resolve_backend(backend) == "pallas":
        return kernel.pack_bits_kernel(vals, width)
    return _pack_words_xla(vals, width)


def _f32_words(a):
    """f32 leaf -> its little-endian u32 bit pattern, flattened."""
    return jax.lax.bitcast_convert_type(
        jnp.asarray(a).astype(jnp.float32), jnp.uint32).reshape(-1)


def pack_payload(p: Payload, *, backend=None):
    """Assemble `encode_payload(p)`'s bitstream on device as u32 sections.

    Sections split exactly where a bit-packed stream ends on a non-word
    byte boundary (so each device buffer's wire bytes are a prefix of its
    own bytes): dense/slice/sparse/quant are ONE buffer (their interior
    section seams are word-aligned), sparse_quant is two (the r-bit index
    stream ends mid-word before the codes), and mask is two (the
    per-instance bitmask rows are byte- but not word-aligned; the second
    section stays (n, W) for the host's per-row byte slice).
    """
    m = p.meta
    kind, d = m.kind, m.d
    if kind in ("dense", "slice"):
        return (_f32_words(p.values),)
    if kind == "sparse":
        idx_words = pack_bits(jnp.asarray(p.indices), wire.index_bits(d),
                              backend=backend)
        return (jnp.concatenate([_f32_words(p.values), idx_words]),)
    if kind == "quant":
        code_words = pack_bits(jnp.asarray(p.values), m.bits,
                               backend=backend)
        return (jnp.concatenate([_f32_words(p.header), code_words]),)
    if kind == "sparse_quant":
        idx_words = pack_bits(jnp.asarray(p.indices), wire.index_bits(d),
                              backend=backend)
        code_words = pack_bits(jnp.asarray(p.values), m.bits,
                               backend=backend)
        return (jnp.concatenate([_f32_words(p.header), idx_words]),
                code_words)
    if kind == "mask":
        n = 1
        for s in p.batch_shape:
            n *= s
        words = jnp.asarray(p.indices).reshape(n, wire.mask_words(d))
        return (_f32_words(p.values), words)
    raise ValueError(kind)


def section_nbytes(meta: PayloadMeta, batch_shape):
    """Exact wire bytes of each `pack_payload` section — their sum is
    `wire.payload_expected_nbytes(meta, batch_shape)`."""
    return _section_nbytes(meta, tuple(batch_shape))


# memoized for the per-frame host pack path (see wire._meta_subheader)
@lru_cache(maxsize=4096)
def _section_nbytes(meta: PayloadMeta, batch_shape):
    n = 1
    for s in batch_shape:
        n *= s
    kind, d, k, r = meta.kind, meta.d, meta.k, wire.index_bits(meta.d)
    if kind == "dense":
        return (4 * n * d,)
    if kind == "slice":
        return (4 * n * k,)
    if kind == "sparse":
        return (4 * n * k + (n * k * r + 7) // 8,)
    if kind == "quant":
        return (8 * n + (n * d * meta.bits + 7) // 8,)
    if kind == "sparse_quant":
        return (8 * n + (n * k * r + 7) // 8, (n * k * meta.bits + 7) // 8)
    if kind == "mask":
        return (4 * n * k, n * wire.mask_row_nbytes(d))
    raise ValueError(kind)


def sections_to_bytes(meta: PayloadMeta, batch_shape, sections) -> bytes:
    """Host side of the device wire path: pull each packed section and
    truncate it to its exact byte length. The result is byte-identical to
    `wire.encode_payload` on the equivalent host payload; frame it with
    `wire.encode_payload_frame_from_bytes`."""
    nbytes = section_nbytes(meta, batch_shape)
    parts = []
    for arr, nb in zip(sections, nbytes):
        a = np.asarray(arr)
        if meta.kind == "mask" and a.ndim == 2:
            parts.append(wire.mask_words_to_bytes(a, meta.d))
        else:
            parts.append(a.tobytes()[:nb])
    return b"".join(parts)
