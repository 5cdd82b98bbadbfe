"""jit-able client/server decode steps for the streaming runtime.

The split model's decode caches are stacked per layer (axis 0) and the cut
partitions every cache entry into a bottom prefix and a top suffix along
that axis (the same invariant `split.model.decode_step`'s merge relies on),
so each party updates only its own slice of a full-shaped cache:

  * client (feature owner): embed -> layers [0, cut) -> `Compressor.encode`;
    writes the prefix slice.
  * server (label owner): dense cut view -> layers [cut, L) -> lm head ->
    greedy token; writes the suffix slice. The server step runs over a
    leading session axis so one compiled step serves a whole batch of
    sessions, each row with its own cache and position; the arena step
    writes only each active row's new K/V entries, in place
    (`make_arena_top_step`).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import compressors
from repro.models import transformer
from repro.models.config import ArchConfig, Runtime


def _merge_range(cache, partial, *, prefix: bool):
    """Write a contiguous layer-range partial cache back into the full one.

    `partial` covers the first (prefix=True) or last (prefix=False) entries
    of each cache key along the stacked layer axis; untouched keys (e.g.
    frozen cross-attention KV) pass through. Advances `pos`.
    """
    new = dict(cache)
    for key, val in partial.items():
        def m(o, p):
            if prefix:
                return jnp.concatenate([p, o[p.shape[0]:]], axis=0)
            return jnp.concatenate([o[: o.shape[0] - p.shape[0]], p], axis=0)
        new[key] = jax.tree.map(m, cache[key], val)
    new["pos"] = cache["pos"] + 1
    return new


def make_bottom_step(cfg: ArchConfig, rt: Runtime, cut: int,
                     comp: compressors.Compressor) -> Callable:
    """(params, cache, token (1,1) i32) -> (Payload, new cache). jit-able;
    encode is deterministic (inference-mode compression, RandTopk -> TopK)."""

    def bottom_step(params, cache, token):
        x = transformer.embed(params, cfg, rt, token)
        x, partial = transformer.decode_layers(params, cfg, rt, x, cache,
                                               0, cut)
        payload = comp.encode(x, training=False)
        return payload, _merge_range(cache, partial, prefix=True)

    return bottom_step


def make_bottom_step_device(cfg: ArchConfig, rt: Runtime, cut: int,
                            comp: compressors.Compressor) -> Callable:
    """Device-encode variant of `make_bottom_step`: the wire bitstream is
    packed on device inside the same jit program
    (`split.protocol.client_encode_device`), so the client's only host
    crossing per step is the final packed buffer(s).

    (params, cache, token (1,1) i32) -> ((Payload, sections), new cache).
    The Payload keeps device leaves (shape/meta for the frame subheader);
    `sections` are the packed u32 wire buffers the host truncates with
    `kernels.encode.ops.sections_to_bytes` and frames with
    `wire.encode_payload_frame_from_bytes` — byte-identical to the host
    codec on `make_bottom_step`'s payload.
    """
    from repro.split import protocol

    def bottom_step(params, cache, token):
        x = transformer.embed(params, cfg, rt, token)
        x, partial = transformer.decode_layers(params, cfg, rt, x, cache,
                                               0, cut)
        payload, sections = protocol.client_encode_device(comp, x,
                                                          training=False)
        return (payload, sections), _merge_range(cache, partial, prefix=True)

    return bottom_step


def make_top_step(cfg: ArchConfig, rt: Runtime, cut: int) -> Callable:
    """Vmapped server step: (params, x (S,1,1,d), caches stacked over S) ->
    (tokens (S,1) i32, new caches). One compile serves every batch; padded
    rows (batch fill) are computed and discarded.

    This is the pre-arena flush-shaped step, kept as the reference
    implementation the arena parity tests pin against (`make_arena_top_step`
    is the serving hot path)."""

    def one_session(params, x, cache):
        x, partial = transformer.decode_layers(params, cfg, rt, x, cache,
                                               cut, cfg.n_layers)
        logits = transformer.lm_head(params, cfg, rt, x)
        tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return tok, _merge_range(cache, partial, prefix=False)

    return jax.vmap(one_session, in_axes=(None, 0, 0))


def _make_arena_hidden(cfg: ArchConfig, rt: Runtime, cut: int) -> Callable:
    """The arena step's top-layer pass, token head split out:
    (params, x (C, 1, 1, d), cache arena stacked over C, active (C,) bool)
    -> (hidden (C, 1, 1, d), new arena). Shared by the single-device step
    and the sharded step's per-shard body.

    Where the arena holds only stacked ring `kv` leaves and `pos` (dense,
    moe), each active row's new K/V is written in place
    (`transformer.decode_rows_in_place`): inactive rows' writes are
    dropped, so nothing else of the arena is read back or rewritten.
    Other caches (recurrent `mamba`/`rwkv` state, frozen `cross_kv`) run
    the per-row layer pass over every row and keep the old leaves of
    inactive rows with `where(active, new, old)`: recurrent state is
    rewritten whole each step anyway."""

    def masked_row(params, x, cache, active):
        x, partial = transformer.decode_layers(params, cfg, rt, x, cache,
                                               cut, cfg.n_layers)
        new = _merge_range(cache, partial, prefix=False)
        new = jax.tree.map(lambda n, o: jnp.where(active, n, o), new, cache)
        return x, new

    masked = jax.vmap(masked_row, in_axes=(None, 0, 0, 0))

    def hidden(params, x, cache, active):
        if set(cache) == {"pos", "kv"}:
            return transformer.decode_rows_in_place(
                params, cfg, rt, x, cache, active, cut, cfg.n_layers)
        return masked(params, x, cache, active)

    return hidden


def make_arena_top_step(cfg: ArchConfig, rt: Runtime, cut: int,
                        mesh=None) -> Callable:
    """Whole-arena server step with an active-slot mask.

    (params, xbuf (C+1, 1, 1, d), cache arena stacked over C, active (C,)
    bool) -> (tokens (C, 1) i32, new arena). Row i of the arena is session
    slot i; `xbuf`'s trailing scratch row (the decode-group pad target) is
    sliced off before the step. Position and KV never advance for a slot
    that received no frame this flush: each active row's new K/V is
    scattered to (slot, layer, pos % size) of the arena and an inactive
    row's write is dropped (`_make_arena_hidden`), so under
    `jax.jit(..., donate_argnums=(2,))` (see `runtime.server`) the output
    arena aliases the donated input and only the written entries move.
    Layers below `cut` are never touched.

    Per-row numerics are identical to `make_top_step` (the same per-row
    layer functions, vmapped), so arena-served tokens are bit-identical to
    the flush-stacked path.

    With `mesh` (a `jax.sharding.Mesh`), the step runs under `shard_map`
    with arena rows sharded over every mesh axis and the lm head
    vocab-parallel over 'model' (docs/sharding.md). Every split is a batch
    or output-dim split, never a contraction split; still, a shard runs
    the per-row program over fewer rows, and the compiler may pick another
    dot kernel and summation order for that batch, so new cache leaves
    agree with the mesh-less step to a few ulps, not bit for bit (tests
    pin tokens exact and KV within a tolerance). `mesh=None` is exactly
    the pre-mesh single-device program.
    """
    if mesh is not None:
        return _make_sharded_arena_step(cfg, rt, cut, mesh)
    hidden = _make_arena_hidden(cfg, rt, cut)

    def token(params, h):
        logits = transformer.lm_head(params, cfg, rt, h)
        return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)

    head = jax.vmap(token, in_axes=(None, 0))

    def arena_step(params, xbuf, cache, active):
        h, cache = hidden(params, xbuf[: active.shape[0]], cache, active)
        return head(params, h), cache

    return arena_step


def _make_sharded_arena_step(cfg: ArchConfig, rt: Runtime, cut: int,
                             mesh) -> Callable:
    """The `shard_map` variant of the arena step (docs/sharding.md).

    Decomposition, chosen so every piece preserves bit-exact tokens:

      * arena rows (slots) shard over ALL mesh axes flattened in mesh
        order — 'pod' x 'data' x 'model' — so session capacity scales
        with every device. Row sharding is batch decomposition: each
        device runs the single-device step's top-layer pass
        (`_make_arena_hidden`) over its rows, no contraction is split,
        numerics are untouched.
      * the lm head is tensor-parallel over 'model': each rank first
        all-gathers its row block along 'model' (`tp.gather_seq_local`'s
        collective, norm applied BEFORE the gather in Megatron-SP order),
        then multiplies by its vocab shard of `unembed` — an output-dim
        split, NOT a contraction split, so each logit column is
        bit-identical to the replicated matmul — and the greedy token
        comes out of `tp.vocab_parallel_argmax` (exact first-occurrence
        argmax from two scalar-per-row collectives).
      * with a 'pod' axis, the cut activation crosses the pod ring
        (`protocol.pod_ring_perm`) before the top half runs and the token
        rows return on the inverse ring — the serving-side instance of
        the `split.protocol` ppermute cut boundary. Host-side, `xbuf` and
        token rows for slot s live at `SlotArena.wire_row(s)` (the
        ingestion pod's block); cache rows stay slot-aligned.

    The reduce-scatter output projection (`tp.out_proj_rs`) stays OFF this
    path by design: it splits the ff contraction, which reorders f32
    summation and breaks the bit-exact serving contract (see
    docs/sharding.md); it serves the training/prefill pipeline.
    """
    from jax.sharding import PartitionSpec as P

    from repro.models import common, tp

    axes = tuple(mesh.axis_names)
    sizes = dict(mesh.shape)
    n_model = sizes.get("model", 1)
    n_pod = sizes.get("pod", 1)
    n_rows_shards = 1
    for a in axes:
        n_rows_shards *= sizes[a]
    if cfg.padded_vocab % max(n_model, 1):
        raise ValueError(
            f"padded vocab {cfg.padded_vocab} not divisible by model axis "
            f"{n_model}")

    # the top-layer pass of the single-device step, over this shard's
    # rows; the token head is split out (it needs the cross-rank
    # collectives)
    hidden = _make_arena_hidden(cfg, rt, cut)

    def body(params, x, cache, active):
        if n_pod > 1:
            # cut-boundary crossing: the ingestion pod hands its row block
            # to the pod holding those slots' top-model state
            from repro.split import protocol
            x = jax.lax.ppermute(x, "pod", protocol.pod_ring_perm(n_pod))
        h, new_cache = hidden(params, x, cache, active)
        h = common.apply_norm(h, params["final_norm"], cfg.norm)
        if n_model > 1:
            # reassemble the (pod, data) row block from the model ranks —
            # the Megatron-SP gather (norm first, gather in activation
            # dtype), rows standing in for the sequence axis
            h = tp.gather_seq_local(h.reshape(1, h.shape[0], -1)
                                    ).reshape(-1, *h.shape[1:])
        logits = h @ params["unembed"].astype(h.dtype)   # local vocab shard
        tok = tp.vocab_parallel_argmax(logits[:, :, -1, :], "model")
        if n_pod > 1:
            from repro.split import protocol
            tok = jax.lax.ppermute(
                tok, "pod", protocol.pod_ring_perm(n_pod, inverse=True))
        return tok, new_cache

    rows = axes if len(axes) > 1 else axes[0]

    def row_spec(a):
        return P(rows, *([None] * (a.ndim - 1)))

    # tokens replicate over 'model' (every rank holds its gathered row
    # block's tokens) and shard over the remaining row axes
    tok_axes = tuple(a for a in axes if a != "model")
    tok_spec = P(tok_axes if len(tok_axes) != 1 else tok_axes[0], None) \
        if tok_axes else P(None, None)

    def arena_step(params, xbuf, cache, active):
        if active.shape[0] % n_rows_shards:
            raise ValueError(
                f"arena capacity {active.shape[0]} not divisible by the "
                f"{n_rows_shards}-way row sharding (SlotArena pads for "
                f"this)")
        pspec = jax.tree.map(lambda _: P(), params)
        pspec["unembed"] = P(None, "model")
        cspec = jax.tree.map(row_spec, cache)
        x = xbuf[: active.shape[0]]
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspec, row_spec(x), cspec, row_spec(active)),
            out_specs=(tok_spec, cspec),
            check_vma=False)(params, x, cache, active)

    return arena_step


def make_fused_decode_step(top_step: Callable, *, dtype,
                           backend=None, mesh=None) -> Callable:
    """Fuse the decode->step seam into ONE dispatch.

    (params, xbuf, payload, slots, cache, active) -> (tokens, xbuf, cache):
    scatter-decode the stacked flush payload into `xbuf[slots]`
    (`split.protocol.decode_to_slots_in_jit` — the same trace-time body as
    the standalone slot decode, Pallas or XLA per `backend`), then run the
    arena `top_step` on the updated buffer, all inside one jit program. The
    serving loop's single-meta flushes (every pure-compressor mix) pay one
    dispatch per flush instead of decode + step; jit caches one program per
    (payload meta, flush-rows bucket).

    `mesh` is the one `top_step` was built with (None: one device).

    `xbuf` (arg 1) and `cache` (arg 4) must be DONATED by the jitting
    caller (`runtime.server`): both alias in place on TPU, and the rebound
    outputs carry the arena forward exactly as the two-call path did.
    Numerics are unchanged — decode and step keep their per-row dataflow;
    tokens stay bit-identical to the separate decode + step dispatches
    (pinned for every payload kind by tests/test_arena.py).
    """
    from repro.split import protocol

    dtype_name = jnp.dtype(dtype).name

    def fused_step(params, xbuf, payload, slots, cache, active):
        xbuf = protocol.decode_to_slots_in_jit(
            xbuf, payload, slots, dtype=dtype_name, backend=backend,
            mesh=mesh)
        tokens, cache = top_step(params, xbuf, cache, active)
        return tokens, xbuf, cache

    return fused_step
