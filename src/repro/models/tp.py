"""Explicit tensor-parallel output projection with sequence-parallel
reduce-scatter (Megatron SP).

GSPMD on the host pipeline lowers `psum -> reshard(seq)` as
all-reduce + dynamic-slice; the production pattern is a single
reduce-scatter. We emit it explicitly with shard_map so the dry-run HLO
carries the real collective schedule:

    h (B, S, ff/model) @ w (ff/model, d/data)  ->  y (B, S/model, d)

i.e. each model-rank computes its partial product and `psum_scatter`s it
along the sequence axis. Falls back to a plain matmul (+ GSPMD psum) when
there is no model axis, sequence parallelism is off, or S is indivisible.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import Runtime


def gather_seq_local(y_l, axis_name: str = "model"):
    """Per-shard body of `gather_seq`: all-gather a seq-sharded activation
    along `axis_name` back to full S. Callable from inside any enclosing
    `shard_map` body (the sharded arena step reuses it) as well as from the
    GSPMD wrapper below."""
    return jax.lax.all_gather(y_l, axis_name, axis=1, tiled=True)


def gather_seq(y, rt: Runtime):
    """Explicit bf16 all-gather of a (B, S/model, d) seq-sharded activation
    to full-S replicated. GSPMD left to its own devices hoists this gather
    above the norm's f32->bf16 convert, doubling the bytes; doing it in
    shard_map pins both dtype and placement. Transpose (backward) is the
    matching psum_scatter."""
    mesh = rt.mesh
    B, S, d = y.shape
    usable = (
        mesh is not None and rt.seq_shard and not rt.dp_only
        and "model" in rt.axis_names
        and mesh.shape["model"] > 1 and S % mesh.shape["model"] == 0
    )
    if not usable:
        return rt.shard(y, "batch", None, None)
    batch_axes = rt.batch_axes or ()
    in_spec = P(batch_axes if batch_axes else None, "model", None)
    out_spec = P(batch_axes if batch_axes else None, None, None)

    return jax.shard_map(gather_seq_local, mesh=mesh, in_specs=(in_spec,),
                         out_specs=out_spec, check_vma=False)(y)


def out_proj_rs(h, w, rt: Runtime, *, w_spec=P("model", "data")):
    """h: (B, S, ff) with ff sharded over 'model'; w: (ff, d) sharded w_spec.
    Returns (B, S, d) sharded over 'seq'=model on S."""
    mesh = rt.mesh
    B, S, ff = h.shape
    usable = (
        mesh is not None and rt.seq_shard and not rt.dp_only
        and "model" in rt.axis_names
        and mesh.shape["model"] > 1 and S % mesh.shape["model"] == 0
        and ff % mesh.shape["model"] == 0
    )
    if not usable:
        y = h @ w.astype(h.dtype)
        return rt.shard(y, "batch", "seq", None)

    batch_axes = rt.batch_axes or ()
    h_spec = P(batch_axes if batch_axes else None, None, "model")
    o_spec = P(batch_axes if batch_axes else None, "model", None)

    def f(h_l, w_l):
        return out_proj_rs_local(h_l, w_l, w_spec=w_spec)

    return jax.shard_map(f, mesh=mesh, in_specs=(h_spec, w_spec),
                         out_specs=o_spec)(h, w)


def out_proj_rs_local(h_l, w_l, *, w_spec=P("model", "data"),
                      axis_name: str = "model"):
    """Per-shard body of `out_proj_rs`: partial product over the local ff
    shard, reduce-scattered along the sequence axis. Exposed so an
    enclosing `shard_map` (training/prefill fusions) can emit the same
    collective schedule without nesting shard_maps."""
    if "data" in tuple(w_spec):
        axis = tuple(w_spec).index("data")
        w_l = jax.lax.all_gather(w_l, "data", axis=axis, tiled=True)
    y = h_l @ w_l.astype(h_l.dtype)                # partial over `axis_name`
    return jax.lax.psum_scatter(y, axis_name, scatter_dimension=1,
                                tiled=True)


def vocab_parallel_argmax(logits_l, axis_name: str = "model"):
    """Exact greedy argmax over a vocab-sharded last axis, inside shard_map.

    Each rank holds a contiguous (..., V/model) shard of the logits (the
    unembed matmul with the vocab dimension split is NOT a contraction
    split, so the shards themselves are bit-identical to columns of the
    single-device logits). The global argmax is then recovered without
    materializing full logits anywhere:

      1. per-rank max + argmax over the local shard;
      2. `pmax` for the global max;
      3. every rank whose local max equals the global max proposes its
         local argmax offset by its shard's base column; `pmin` over the
         proposals returns the LOWEST global index attaining the max —
         exactly `jnp.argmax`'s first-occurrence tie-breaking.

    Two scalar-per-row collectives replace an all-gather of the vocab axis.
    Bit-exact at any model-axis size (pmax over disjoint column maxima is
    order-insensitive; index selection never compares floats across ranks
    beyond equality with the global max).
    """
    v_local = logits_l.shape[-1]
    base = jax.lax.axis_index(axis_name).astype(jnp.int32) * v_local
    local_max = jnp.max(logits_l, axis=-1)
    global_max = jax.lax.pmax(local_max, axis_name)
    local_idx = jnp.argmax(logits_l, axis=-1).astype(jnp.int32) + base
    proposal = jnp.where(local_max == global_max, local_idx,
                         jnp.iinfo(jnp.int32).max)
    return jax.lax.pmin(proposal, axis_name).astype(jnp.int32)
