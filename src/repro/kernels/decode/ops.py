"""jit'd public wrappers around the fused decode kernels.

These are the `backend="pallas"` implementations behind
`core.compressors.payload_to_dense` (every payload kind, optional fused
cut-projection) and `split.protocol.server_decode_to_slots` (the serving
arena's decode->xbuf seam). Interpret mode off-TPU, Mosaic on a TPU
runtime — `interpret=None` resolves per `core.selection.pallas_interpret`.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.payload import Payload
from repro.kernels.decode import kernel


def _wire_leaves(p: Payload):
    """Payload wire leaves in kernel order, validated against the kind."""
    names = kernel.KIND_LEAVES[p.meta.kind]
    return tuple(jnp.asarray(getattr(p, n)) for n in names)


def decode_rows(p: Payload, *, dtype=None, project=None,
                interpret=None):
    """Fused dequant+scatter decode of any payload to dense (..., d) rows;
    with `project` ((d, p) matrix) the cut-projection epilogue runs inside
    the same kernel and (..., p) comes back instead."""
    dtype = jnp.dtype(dtype or jnp.float32)
    return kernel.decode_rows_kernel(
        _wire_leaves(p), p.meta.kind, p.meta.d, project,
        dtype=dtype.name, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def decode_rows_to_slots(xbuf, p: Payload, slots, *, interpret=None):
    """Decode a stacked flush payload straight into `xbuf[slots]`.

    xbuf is ALIASED through the kernel (`input_output_aliases`): treat the
    input handle as consumed and keep the returned array — the arena's
    donation contract. Rows shape-agnostic: xbuf (C+1, ..., d) is flattened
    to (C+1, d) around the kernel call.
    """
    cap1 = xbuf.shape[0]
    d = p.meta.d
    out = kernel.decode_to_slots_kernel(
        xbuf.reshape(cap1, d), _wire_leaves(p), slots, p.meta.kind,
        interpret=interpret)
    return out.reshape(xbuf.shape)
