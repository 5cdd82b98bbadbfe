"""Cut-layer compressors for split learning (paper Sections 3-4).

Each compressor is a frozen config object implementing the packed-payload
codec that defines everything that crosses the cut layer:

    payload = comp.encode(x, key=key, training=True)   # wire-dtype pytree
    y       = comp.decode(payload, shape=x.shape)      # dense far-side view
    y, aux  = comp.forward(x, key=key, training=True)  # decode(encode(x))

`x` is the cut-layer activation `(..., d)`. `encode` produces a
`core.payload.Payload` — float32 values / uint8 codes / uint16 indices /
float32 range headers, exactly what a two-party socket (core.wire) or the
pod-boundary ppermute (split.protocol) moves. `decode` is
compressor-independent: any party holding a payload can reconstruct the
dense view from the payload alone. `forward` is kept as the composition
`decode(encode(x))` for backward compatibility; `aux` carries the support
mask where one exists.

Backward semantics follow the paper exactly:
  * size-reduction / top-k / randtopk: the gradient is masked with the SAME
    support that was used in the forward pass (the label owner sends only the
    k gradient values; indices are already known to the feature owner).
    Realized by gather-from-support in encode + scatter in decode (whose
    adjoints are scatter/gather), or explicitly by `split.protocol`'s
    payload-typed backward rules.
  * quantization: forward quantize-dequantize; the backward gradient is sent
    uncompressed, and the chain through the quantizer is the straight-through
    estimator (identity), via the `_ste` custom_vjp.
  * L1: identity at training time + a `loss_penalty(x)` term; at inference the
    support is the empirically-nonzero set (|x| > tol after training shrinks
    activations toward zero).

Compression ratios are reported by `fwd_bits`/`bwd_bits` (Table 2), which
tests cross-check against the measured `wire.encode_payload` byte counts.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import selection
from repro.core.payload import Payload, PayloadMeta

FLOAT_BITS = 32  # N in the paper
MAX_INDEX = 2 ** 16  # uint16 wire indices


def _index_bits(d: int) -> int:
    return max(1, math.ceil(math.log2(d)))


@jax.custom_vjp
def _ste(x, y):
    """Value `y`, gradient identity to `x` (straight-through estimator)."""
    return y


def _ste_fwd(x, y):
    return y, None


def _ste_bwd(_, g):
    return g, jnp.zeros_like(g)


_ste.defvjp(_ste_fwd, _ste_bwd)


def _scatter_rows(vals, idx, d: int):
    """Dense (..., d) scatter of a sparse support (`put_along_axis`) — the
    XLA reference for `kernels.decode`'s sparse branches."""
    out = jnp.zeros(vals.shape[:-1] + (d,), vals.dtype)
    return jnp.put_along_axis(out, jnp.asarray(idx).astype(jnp.int32), vals,
                              axis=-1, inplace=False)


def mask_expand_rows(vals, words, d: int):
    """Dense (..., d) expansion of a mask payload — the XLA reference for
    `kernels.decode`'s mask branch.

    `vals` holds the k selected values in ascending-index order; `words` the
    packed support bitmask. Each set bit takes the next value in the scan
    (position = cumsum of the mask); rows with extra set bits beyond k (a
    hostile frame) zero the overflow rather than mis-indexing.
    """
    mask = selection.unpack_mask_words(jnp.asarray(words), d)
    k = vals.shape[-1]
    pos = jnp.cumsum(mask.astype(jnp.int32), axis=-1) - 1
    take = jnp.take_along_axis(vals, jnp.clip(pos, 0, k - 1), axis=-1)
    return jnp.where(mask & (pos < k), take, jnp.zeros_like(take))


def payload_to_dense(p: Payload, shape=None, dtype=None, *, backend=None,
                     project=None):
    """Dense view (..., d) of any payload — the label-owner-side Decode.

    Compressor-independent: dispatches on `p.meta.kind` only, so the far
    side of the wire never needs the compressor object itself. `backend`
    follows the `selection` dispatch contract (None/"auto" -> Pallas on
    TPU, XLA elsewhere): ``"pallas"`` runs the fused one-pass
    `kernels.decode` kernel for EVERY kind (dequant + scatter in one VMEM
    pass), ``"xla"`` the two-pass dequant->scatter below. Dense/slice/
    sparse results are bit-identical either way (wire floats verbatim);
    quant kinds may differ by 1 ulp of the dequant product (FMA
    contraction — see `_dequant`).

    `project` is an optional (d, p) cut-projection matrix: the Pallas path
    fuses `rows @ project` as a kernel epilogue (the decoded rows never
    materialize); the XLA path applies the same matmul after decoding.
    """
    dtype = dtype or jnp.float32
    m = p.meta
    if selection._resolve_backend(backend) == "pallas":
        from repro.kernels.decode import ops as dec_ops

        return dec_ops.decode_rows(p, dtype=dtype, project=project)
    if m.kind == "dense":
        out = p.values.astype(dtype)
    elif m.kind == "slice":
        pad = [(0, 0)] * (p.values.ndim - 1) + [(0, m.d - m.k)]
        out = jnp.pad(p.values.astype(dtype), pad)
    elif m.kind == "sparse":
        out = _scatter_rows(p.values.astype(dtype), p.indices, m.d)
    elif m.kind == "mask":
        out = mask_expand_rows(p.values.astype(dtype), p.indices, m.d)
    elif m.kind == "quant":
        out = _dequant(p).astype(dtype)
    elif m.kind == "sparse_quant":
        out = _scatter_rows(_dequant(p).astype(dtype), p.indices, m.d)
    else:
        raise ValueError(m.kind)
    if project is not None:
        out = (out @ project.astype(jnp.float32)).astype(dtype)
    return out


def _dequant(p: Payload):
    """`lo + (code + 0.5) * step`.

    Rounding note: under jit the XLA backend may contract the multiply-add
    into an FMA, so compiled dequant (`protocol.server_decode_device`, the
    fused `cut_boundary` path) can differ from eager/host dequant by 1 ulp
    of the step product. Sparse scatter and dense passthrough carry wire
    values verbatim and are bit-exact in every mode; the dequant ulp is
    pinned (and shown not to move served tokens) in tests/test_arena.py.
    """
    lo, step = p.header[..., :1], p.header[..., 1:]
    return lo + (jnp.asarray(p.values).astype(jnp.float32) + 0.5) * step


@dataclasses.dataclass(frozen=True)
class Compressor:
    """Base: identity (vanilla split learning, 'No compression')."""

    name: str = "identity"
    backend: Optional[str] = None   # selection backend: None->auto, xla, pallas

    wire_kind = "dense"             # payload kind this compressor emits

    # -- codec ---------------------------------------------------------------
    def encode(self, x, *, key=None, training=False) -> Payload:
        return Payload(meta=PayloadMeta("dense", d=x.shape[-1]),
                       values=x.astype(jnp.float32))

    def decode(self, p: Payload, shape=None, dtype=None):
        return payload_to_dense(p, shape=shape, dtype=dtype)

    def forward(self, x, *, key=None, training=False):
        p = self.encode(x, key=key, training=training)
        y = self.decode(p, shape=x.shape, dtype=x.dtype)
        return y, self._aux(p, x, training)

    def _aux(self, p: Payload, x, training) -> dict:
        return {}

    def loss_penalty(self, x):
        return jnp.zeros((), dtype=jnp.float32)

    # -- wire accounting (bits per instance of dimension d) ------------------
    def fwd_bits(self, d: int) -> float:
        return d * FLOAT_BITS

    def bwd_bits(self, d: int) -> float:
        return d * FLOAT_BITS

    def compressed_size(self, d: int) -> float:
        """Mean of forward+backward relative compressed size (inference uses
        fwd only; Table 2 reports the two separately — see wire.table2_row)."""
        return 0.5 * (self.fwd_bits(d) + self.bwd_bits(d)) / (d * FLOAT_BITS)


@dataclasses.dataclass(frozen=True)
class SizeReduction(Compressor):
    """Keep the first k features (mask-based cut-layer slimming, Eq. 1)."""

    k: int = 8
    name: str = "size_reduction"

    wire_kind = "slice"

    def encode(self, x, *, key=None, training=False):
        d = x.shape[-1]
        k = min(self.k, d)
        return Payload(meta=PayloadMeta("slice", d=d, k=k),
                       values=x[..., :k].astype(jnp.float32))

    def _aux(self, p, x, training):
        mask = jnp.arange(p.meta.d) < p.meta.k
        return {"mask": jnp.broadcast_to(mask, x.shape)}

    def fwd_bits(self, d):
        return self.k * FLOAT_BITS

    def bwd_bits(self, d):
        return self.k * FLOAT_BITS


@dataclasses.dataclass(frozen=True)
class TopK(Compressor):
    """Magnitude top-k sparsification (Eq. 3)."""

    k: int = 8
    name: str = "topk"

    wire_kind = "sparse"

    def _mask(self, x, key, training):
        return selection.topk_mask(x, self.k, backend=self.backend)

    def _support(self, x, key, training):
        """uint16 indices of the selected support (stop-gradient),
        ascending-index order — the canonical wire order shared with the
        fused encode kernels (`kernels.encode`), so host and device encodes
        serialize byte-identically."""
        d = x.shape[-1]
        assert d <= MAX_INDEX, "uint16 wire indices need d <= 65536"
        k = min(self.k, d)
        mask = self._mask(x, key, training)
        score = jnp.where(mask, jnp.abs(x.astype(jnp.float32)), -1.0)
        _, idx = jax.lax.top_k(score, k)
        idx = jnp.sort(idx, axis=-1)
        return jax.lax.stop_gradient(idx), mask

    def encode(self, x, *, key=None, training=False):
        d = x.shape[-1]
        idx, _ = self._support(x, key, training)
        vals = jnp.take_along_axis(x, idx, axis=-1).astype(jnp.float32)
        return Payload(meta=PayloadMeta("sparse", d=d, k=idx.shape[-1]),
                       values=vals, indices=idx.astype(jnp.uint16))

    def _aux(self, p, x, training):
        return {"mask": selection.mask_from_indices(
            p.indices.astype(jnp.int32), p.meta.d)}

    def fwd_bits(self, d):
        return self.k * (FLOAT_BITS + _index_bits(d))

    def bwd_bits(self, d):
        # feature owner already holds the indices
        return self.k * FLOAT_BITS


@dataclasses.dataclass(frozen=True)
class RandTopK(TopK):
    """Randomized top-k sparsification — the paper's contribution (Eq. 7).

    alpha=0 -> TopK; alpha=1 -> Dropout-like. Randomness only in training.
    """

    alpha: float = 0.1
    name: str = "randtopk"

    def _mask(self, x, key, training):
        if not training:
            return selection.topk_mask(x, self.k, backend=self.backend)
        if key is None:
            raise ValueError("RandTopK.forward(training=True) needs a PRNG key")
        return selection.randtopk_mask(x, self.k, self.alpha, key,
                                       backend=self.backend)


@dataclasses.dataclass(frozen=True)
class RandTopKMask(RandTopK):
    """RandTopK with a mask-encoded wire format (Zhou et al. 2024,
    ROADMAP item 5): the u16 index stream is replaced by one packed d-bit
    support bitmask per instance, and the k values are shipped in
    ascending-index order (the mask's scan order). Wins over the
    u16-index sparse layout whenever k/d > 16/(32*16) = 1/16 per
    wire.table2_row("randtopk_mask"); selection semantics (Eq. 7) are
    identical to RandTopK, so accuracy is untouched."""

    name: str = "randtopk_mask"

    wire_kind = "mask"

    def encode(self, x, *, key=None, training=False):
        d = x.shape[-1]
        idx, mask = self._support(x, key, training)   # ascending order
        vals = jnp.take_along_axis(x, idx, axis=-1).astype(jnp.float32)
        words = selection.pack_mask_words(jax.lax.stop_gradient(mask))
        return Payload(meta=PayloadMeta("mask", d=d, k=idx.shape[-1]),
                       values=vals, indices=words)

    def _aux(self, p, x, training):
        return {"mask": selection.unpack_mask_words(p.indices, p.meta.d)}

    def fwd_bits(self, d):
        return self.k * FLOAT_BITS + 8 * ((d + 7) // 8)

    def bwd_bits(self, d):
        return self.k * FLOAT_BITS


def _quant_encode(x, bits: int):
    """Uniform quantization (Eq. 2) with per-instance [min, max] range.

    Returns (codes int32, header f32 (..., 2)); both stop-gradient.
    """
    xf = jax.lax.stop_gradient(x.astype(jnp.float32))
    lo = jnp.min(xf, axis=-1, keepdims=True)
    hi = jnp.max(xf, axis=-1, keepdims=True)
    n_bins = 2 ** bits
    step = (hi - lo) / n_bins
    step = jnp.where(step <= 0, 1.0, step)
    code = jnp.clip(jnp.floor((xf - lo) / step), 0, n_bins - 1)
    return code.astype(jnp.int32), jnp.concatenate([lo, step], axis=-1)


@dataclasses.dataclass(frozen=True)
class Quantization(Compressor):
    """b-bit uniform quantization of the forward activation; backward is the
    full-precision gradient (paper applies quantization forward-only, with a
    straight-through estimator through the quantizer)."""

    bits: int = 4
    name: str = "quant"

    wire_kind = "quant"

    def encode(self, x, *, key=None, training=False):
        assert self.bits <= 8, "uint8 wire codes need bits <= 8"
        code, header = _quant_encode(x, self.bits)
        return Payload(meta=PayloadMeta("quant", d=x.shape[-1],
                                        bits=self.bits),
                       values=code.astype(jnp.uint8), header=header)

    def forward(self, x, *, key=None, training=False):
        p = self.encode(x, key=key, training=training)
        y = self.decode(p, shape=x.shape, dtype=x.dtype)
        return _ste(x, y), {}

    def fwd_bits(self, d):
        # codes + the (lo, step) range floats, amortized over the instance
        return d * self.bits + 2 * FLOAT_BITS

    def bwd_bits(self, d):
        return d * FLOAT_BITS


@dataclasses.dataclass(frozen=True)
class L1Reg(Compressor):
    """L1 regularization on the cut activation. Identity transport during
    training (+ penalty in the loss); at inference the wire carries the
    empirically non-zero support."""

    lam: float = 1e-3
    tol: float = 1e-6
    name: str = "l1"

    def encode(self, x, *, key=None, training=False):
        vals = x if training else x * (jnp.abs(x) > self.tol).astype(x.dtype)
        return Payload(meta=PayloadMeta("dense", d=x.shape[-1]),
                       values=vals.astype(jnp.float32))

    def _aux(self, p, x, training):
        if training:
            return {}
        return {"mask": jnp.abs(x) > self.tol}

    def loss_penalty(self, x):
        return self.lam * jnp.sum(jnp.abs(x.astype(jnp.float32))) / x.shape[0]

    def measured_fwd_bits(self, x) -> jax.Array:
        """Data-dependent compressed size (the paper reports its std)."""
        d = x.shape[-1]
        nnz = jnp.sum((jnp.abs(x) > self.tol).astype(jnp.float32), axis=-1)
        return nnz * (FLOAT_BITS + _index_bits(d))

    def fwd_bits(self, d):  # not statically known; report worst case
        return d * (FLOAT_BITS + _index_bits(d))

    def bwd_bits(self, d):
        return d * FLOAT_BITS


@dataclasses.dataclass(frozen=True)
class RandTopKQuant(RandTopK):
    """Beyond-paper: RandTopk + b-bit quantization of the surviving values
    (the combination the paper's conclusion names as promising future work).

    Wire: k codes of `bits` + k uint16 indices + per-instance (lo, step)
    header; at matched bytes this affords a ~(32+r)/(bits+r) times larger
    support k. Backward: gradient on the selected support, full precision
    (masked), STE through the value quantizer.
    """

    bits: int = 8
    name: str = "randtopk_quant"

    wire_kind = "sparse_quant"

    def encode(self, x, *, key=None, training=False):
        assert self.bits <= 8, "uint8 wire codes need bits <= 8"
        d = x.shape[-1]
        idx, _ = self._support(x, key, training)
        vals = jnp.take_along_axis(x, idx, axis=-1).astype(jnp.float32)
        # quantize using the range of the SELECTED values only (tighter bins)
        vals = jax.lax.stop_gradient(vals)
        lo = jnp.min(vals, axis=-1, keepdims=True)
        hi = jnp.max(vals, axis=-1, keepdims=True)
        n_bins = 2 ** self.bits
        step = jnp.where(hi > lo, (hi - lo) / n_bins, 1.0)
        code = jnp.clip(jnp.floor((vals - lo) / step), 0, n_bins - 1)
        return Payload(meta=PayloadMeta("sparse_quant", d=d,
                                        k=idx.shape[-1], bits=self.bits),
                       values=code.astype(jnp.uint8),
                       indices=idx.astype(jnp.uint16),
                       header=jnp.concatenate([lo, step], axis=-1))

    def _aux(self, p, x, training):
        return {"mask": selection.mask_from_indices(
            p.indices.astype(jnp.int32), p.meta.d)}

    def forward(self, x, *, key=None, training=False):
        p = self.encode(x, key=key, training=training)
        y = self.decode(p, shape=x.shape, dtype=x.dtype)
        aux = self._aux(p, x, training)
        maskf = jax.lax.stop_gradient(aux["mask"].astype(x.dtype))
        return _ste(x * maskf, y), aux   # STE on values, masked support

    def fwd_bits(self, d):
        return self.k * (self.bits + _index_bits(d)) + 2 * FLOAT_BITS

    def bwd_bits(self, d):
        return self.k * FLOAT_BITS


def make_compressor(spec: Optional[str], **kw) -> Compressor:
    """Factory: 'randtopk:k=8,alpha=0.1' style strings or kwargs."""
    if spec is None or spec == "none" or spec == "identity":
        return Compressor(**kw)
    if ":" in spec:
        name, args = spec.split(":", 1)
        for item in args.split(","):
            key, val = item.split("=")
            kw.setdefault(key, float(val) if "." in val else int(val))
    else:
        name = spec
    table = {
        "size_reduction": SizeReduction,
        "topk": TopK,
        "randtopk": RandTopK,
        "randtopk_mask": RandTopKMask,
        "quant": Quantization,
        "l1": L1Reg,
        "randtopk_quant": RandTopKQuant,
    }
    if name not in table:
        raise ValueError(f"unknown compressor {name!r}")
    return table[name](**kw)
