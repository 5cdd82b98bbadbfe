"""GQA attention: RoPE, qk-norm, sliding window, KV cache, chunked prefill.

Long-sequence training/prefill uses a query-chunked formulation (scan over
query blocks, full softmax per block over the visible KV range) with per-chunk
rematerialization, bounding peak memory at O(S * chunk) instead of O(S^2).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import common, tp
from repro.models.config import ArchConfig, Runtime


def init_attention(key, cfg: ArchConfig, *, cross=False, gated=False):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = cfg.pdtype()
    ks = jax.random.split(key, 5)
    p = {
        "norm": common.init_norm(d, dt, cfg.norm),
        "wq": common.normal_init(ks[0], (d, hq * hd), dt),
        "wk": common.normal_init(ks[1], (d, hkv * hd), dt),
        "wv": common.normal_init(ks[2], (d, hkv * hd), dt),
        "wo": common.normal_init(ks[3], (hq * hd, d), dt,
                                 scale=0.02 / max(1, cfg.n_layers) ** 0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((hd,), dt)}
        p["k_norm"] = {"scale": jnp.ones((hd,), dt)}
    if gated:
        p["gate"] = jnp.zeros((), dt)
    return p


def attention_spec(cfg: ArchConfig, *, cross=False, gated=False):
    p = {
        "norm": common.norm_spec(cfg.norm),
        "wq": P("data", "model"),
        "wk": P("data", "model"),
        "wv": P("data", "model"),
        "wo": P("model", "data"),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": P()}
        p["k_norm"] = {"scale": P()}
    if gated:
        p["gate"] = P()
    return p


def _project_qkv(p, cfg: ArchConfig, xq, xkv, q_positions, kv_positions, *, rope=True):
    return _qkv_heads(p, cfg, xq @ p["wq"].astype(xq.dtype),
                      xkv @ p["wk"].astype(xkv.dtype),
                      xkv @ p["wv"].astype(xkv.dtype), q_positions,
                      kv_positions, rope=rope)


def _qkv_heads(p, cfg: ArchConfig, q, k, v, q_positions, kv_positions, *,
               rope=True):
    """Projected q, k, v (..., heads * hd) -> per-head, qk-normed, rotated."""
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = q.reshape(*q.shape[:-1], hq, hd)
    k = k.reshape(*k.shape[:-1], hkv, hd)
    v = v.reshape(*v.shape[:-1], hkv, hd)
    if cfg.qk_norm:
        q = common.rms_norm(q, p["q_norm"]["scale"])
        k = common.rms_norm(k, p["k_norm"]["scale"])
    if rope:
        q = common.apply_rope(q, q_positions, cfg.rope_theta)
        k = common.apply_rope(k, kv_positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask, cfg: ArchConfig, kv_axes: str = "bkhd"):
    """q: (B,Sq,Hq,hd), k/v: (B,Skv,Hkv,hd), mask: (B?,1?,Sq,Skv) bool.
    `kv_axes="bhkd"` takes k/v as (B,Hkv,Skv,hd), the decode cache layout.
    The logits scale is the config's head dim; q, k and v may carry zero
    padding past it (the decode cache's lane padding), which the
    output keeps.

    bf16 operands with f32 accumulation (MXU semantics) — avoids hauling
    f32 copies of q/k/v through HBM and collectives."""
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    g = hq // hkv
    B, Sq, width = q.shape[0], q.shape[1], q.shape[-1]
    qg = q.reshape(B, Sq, hkv, g, width)
    logits = jnp.einsum(f"bqhgd,{kv_axes}->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) / (hd ** 0.5)
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(f"bhgqk,{kv_axes}->bqhgd", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, hq, width).astype(q.dtype)


def _causal_mask(q_pos, kv_pos, window: int):
    """(Sq,) x (Skv,) -> (Sq, Skv) bool; window=0 means unbounded."""
    m = kv_pos[None, :] <= q_pos[:, None]
    if window:
        m &= kv_pos[None, :] > q_pos[:, None] - window
    return m


def full_attention(p, cfg: ArchConfig, rt: Runtime, x, *, causal=True, rope=True):
    """Training / prefill self-attention over (B, S, d)."""
    B, S, _ = x.shape
    pos = jnp.arange(S)
    q, k, v = _project_qkv(p, cfg, x, x, pos[None], pos[None], rope=rope)
    q = rt.shard(q, "batch", None, "model", None)
    k = rt.shard(k, "batch", None, None, None)
    v = rt.shard(v, "batch", None, None, None)

    window = cfg.sliding_window
    if S <= rt.attn_chunk or S % rt.attn_chunk != 0:
        mask = _causal_mask(pos, pos, window) if causal else jnp.ones((S, S), bool)
        out = _sdpa(q, k, v, jnp.broadcast_to(mask, (B, S, S)), cfg)
    else:
        c = rt.attn_chunk
        assert S % c == 0, f"seq {S} must divide attn_chunk {c}"
        qs = q.reshape(B, S // c, c, *q.shape[2:]).swapaxes(0, 1)

        def chunk_body(carry, inp):
            i, qc = inp
            qpos = i * c + jnp.arange(c)
            if causal:
                mask = _causal_mask(qpos, pos, window)
            else:
                mask = jnp.ones((c, S), bool)
            o = _sdpa(qc, k, v, jnp.broadcast_to(mask, (B, c, S)), cfg)
            return carry, o

        body = jax.checkpoint(chunk_body) if rt.remat else chunk_body
        _, outs = jax.lax.scan(body, (), (jnp.arange(S // c), qs))
        out = outs.swapaxes(0, 1).reshape(B, S, cfg.n_heads, cfg.hd)

    y = tp.out_proj_rs(out.reshape(B, S, cfg.n_heads * cfg.hd), p["wo"], rt)
    # reduce-scattered into the sequence-parallel domain (Megatron SP)
    return rt.shard(y, "batch", "seq", None)


def cross_attention(p, cfg: ArchConfig, rt: Runtime, x, kv_tokens=None, *,
                    kv_cache=None, gated=False):
    """Cross-attention: q from x (B,S,d); kv from kv_tokens (B,N,d) or a
    precomputed (k, v) cache. No RoPE on cross attention."""
    B, S, _ = x.shape
    if kv_cache is not None:
        k, v = kv_cache
        N = k.shape[1]
        q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, cfg.n_heads, cfg.hd)
        if cfg.qk_norm:
            q = common.rms_norm(q, p["q_norm"]["scale"])
    else:
        N = kv_tokens.shape[1]
        q, k, v = _project_qkv(p, cfg, x, kv_tokens, None, None, rope=False)
    mask = jnp.ones((B, S, N), bool)
    out = _sdpa(q, k, v, mask, cfg)
    y = tp.out_proj_rs(out.reshape(B, S, cfg.n_heads * cfg.hd), p["wo"], rt)
    if gated:
        y = jnp.tanh(p["gate"].astype(jnp.float32)).astype(y.dtype) * y
    return rt.shard(y, "batch", "seq", None)


def cross_kv(p, cfg: ArchConfig, kv_tokens):
    """Precompute the cross-attention KV cache from encoder/image tokens."""
    B, N, _ = kv_tokens.shape
    k = (kv_tokens @ p["wk"].astype(kv_tokens.dtype)).reshape(B, N, cfg.n_kv_heads, cfg.hd)
    v = (kv_tokens @ p["wv"].astype(kv_tokens.dtype)).reshape(B, N, cfg.n_kv_heads, cfg.hd)
    if cfg.qk_norm:
        k = common.rms_norm(k, p["k_norm"]["scale"])
    return k, v


# --------------------------------------------------------------------------
# Decode (single new token against a cache)
# --------------------------------------------------------------------------

#: the TPU's lane count: the decode cache's head dim is zero-padded to a
#: multiple of it
LANES = 128


def kv_width(cfg: ArchConfig) -> int:
    """The decode cache's head dim: `cfg.hd` rounded up to `LANES`."""
    return -(-cfg.hd // LANES) * LANES


def init_kv_cache(cfg: ArchConfig, batch: int, max_len: int,
                  *, bits: int = 16):
    """Rolling cache, (batch, kv heads, positions, `kv_width`); for
    sliding-window archs max_len = window size.

    The layout lets a step write one position of every head in place and
    read each head's (positions, head dim) matrix with no relayout on the
    TPU: heads sit outside the positions, and the head dim is zero-padded
    to whole lanes (at 96, the TPU would otherwise lay the positions out
    minor, and a write of one position would copy the whole cache to
    another layout and back).

    bits=8 stores int8 codes + per-(head, token) f32 scales (symmetric
    quantization) — halves decode HBM footprint; dequantized on read."""
    size = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    shape = (batch, cfg.n_kv_heads, size, kv_width(cfg))
    if bits == 8:
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:3], jnp.float32),
            "v_scale": jnp.zeros(shape[:3], jnp.float32),
        }
    return {
        "k": jnp.zeros(shape, cfg.adtype()),
        "v": jnp.zeros(shape, cfg.adtype()),
    }


def _quantize_kv(x):
    """x: (..., hd) -> (int8 codes, (...) scale), one scale per vector."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    safe = jnp.maximum(scale, 1e-9)
    code = jnp.clip(jnp.round(x.astype(jnp.float32) / safe[..., None]),
                    -127, 127).astype(jnp.int8)
    return code, scale.astype(jnp.float32)


def _dequantize_kv(code, scale, dtype):
    return (code.astype(jnp.float32) * scale[..., None]).astype(dtype)


def kv_cache_spec(rt: Runtime, *, bits: int = 16):
    # flash-decode layout: the cache SEQUENCE dim is sharded over 'model'
    # (GQA kv-head counts of 4-8 cannot split a 16-way axis and would force
    # full replication -> 16x the per-chip cache); each rank attends over its
    # sequence slice and the softmax reductions lower to psums.
    spec = {"k": rt.pspec("batch", None, "flashdecode", None),
            "v": rt.pspec("batch", None, "flashdecode", None)}
    if bits == 8:
        spec["k_scale"] = rt.pspec("batch", None, "flashdecode")
        spec["v_scale"] = rt.pspec("batch", None, "flashdecode")
    return spec


def decode_qkv(p, cfg: ArchConfig, x_tok, pos, *, quant: bool):
    """The new token's query and its cache entries. x_tok: (B, 1, d); pos:
    scalar int32 (absolute position of the new token). Returns (q, entries)
    with q (B, 1, Hq, kv_width) and entries keyed and laid out like the
    cache leaves, one position each: 'k', 'v' (B, Hkv, 1, kv_width), and
    with `quant` the int8 codes plus 'k_scale', 'v_scale' (B, Hkv, 1). The
    head dim's padding is zeros (codes 0; a scale is the real values')."""
    # the projections leave the MXU as they are: past this boundary the
    # TPU would pick a transposed layout for them and re-lay out wq, wk
    # and wv of every layer on every step
    proj = jax.lax.optimization_barrier(
        tuple(x_tok @ p[w].astype(x_tok.dtype) for w in ("wq", "wk", "wv")))
    q, k_new, v_new = _qkv_heads(p, cfg, *proj, jnp.full((1, 1), pos),
                                 jnp.full((1, 1), pos))
    pad = [(0, 0)] * 3 + [(0, kv_width(cfg) - cfg.hd)]
    q, k_new, v_new = (jnp.pad(a, pad) for a in (q, k_new, v_new))
    k_new, v_new = k_new.swapaxes(1, 2), v_new.swapaxes(1, 2)
    if not quant:
        return q, {"k": k_new, "v": v_new}
    kc, ks = _quantize_kv(k_new)
    vc, vs = _quantize_kv(v_new)
    return q, {"k": kc, "v": vc, "k_scale": ks, "v_scale": vs}


def decode_attend(p, cfg: ArchConfig, rt: Runtime, q, cache, pos, dtype):
    """Attend the new token's query (`decode_qkv`'s, lane-padded) over a
    rolling cache that already holds its entries at slot `pos % size`.
    Returns y (B, 1, d) in `dtype`."""
    B = q.shape[0]
    size = cache["k"].shape[2]
    if "k_scale" in cache:
        k = _dequantize_kv(cache["k"], cache["k_scale"], dtype)
        v = _dequantize_kv(cache["v"], cache["v_scale"], dtype)
    else:
        k, v = cache["k"], cache["v"]
    k = rt.shard(k, "batch", None, "flashdecode", None)
    v = rt.shard(v, "batch", None, "flashdecode", None)

    # valid slots: absolute positions of each slot given the ring layout
    slot = (pos % size).astype(jnp.int32)
    idx = jnp.arange(size)
    wraps = jnp.where(idx <= slot, pos - slot, pos - size - slot)
    abs_pos = idx + wraps              # absolute position stored in each slot
    valid = (abs_pos >= 0) & (abs_pos <= pos)
    if cfg.sliding_window:
        valid &= abs_pos > pos - cfg.sliding_window
    mask = jnp.broadcast_to(valid[None, None, :], (B, 1, size))
    out = _sdpa(q, k, v, mask, cfg, kv_axes="bhkd")[..., :cfg.hd]
    y = out.reshape(B, 1, cfg.n_heads * cfg.hd) @ p["wo"].astype(dtype)
    return rt.shard(y, "batch", None, None)


def decode_attention(p, cfg: ArchConfig, rt: Runtime, x_tok, cache, pos):
    """x_tok: (B, 1, d); cache: {'k','v'} rolling buffers; pos: scalar int32
    (absolute position of the new token). Returns (y, new_cache)."""
    q, entries = decode_qkv(p, cfg, x_tok, pos, quant="k_scale" in cache)
    slot = (pos % cache["k"].shape[2]).astype(jnp.int32)
    new_cache = {
        name: jax.lax.dynamic_update_slice(
            cache[name], e, (0, 0, slot) + (0,) * (e.ndim - 3))
        for name, e in entries.items()}
    y = decode_attend(p, cfg, rt, q, new_cache, pos, x_tok.dtype)
    return y, new_cache
