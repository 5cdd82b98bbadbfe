"""Unified model: embeds -> family-specific layer stack(s) -> LM head.

Layers are stacked and driven by `lax.scan` (compile time is O(1) in depth).
Heterogeneous stacks (zamba2 hybrid, VLM cross-attn interleave, whisper
enc-dec) scan over their repeating group. The split-learning cut is a
first-class residual-stream boundary: `apply_layers(..., lo, hi)` runs any
contiguous layer range, and the SplitModel (repro.split) composes
bottom-range -> compress -> transfer -> top-range.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import attention, common, mlp, moe, rwkv, ssm
from repro.models.config import ArchConfig, Runtime


# ==========================================================================
# Init / specs
# ==========================================================================

def _layer_init(key, cfg: ArchConfig):
    """One decoder layer's params for dense/moe families."""
    k1, k2 = jax.random.split(key)
    p = {"attn": attention.init_attention(k1, cfg)}
    if cfg.family == "moe":
        p["moe"] = moe.init_moe(k2, cfg)
    else:
        p["mlp"] = mlp.init_mlp(k2, cfg)
    return p


def _layer_spec(cfg: ArchConfig):
    p = {"attn": attention.attention_spec(cfg)}
    if cfg.family == "moe":
        p["moe"] = moe.moe_spec(cfg)
    else:
        p["mlp"] = mlp.mlp_spec(cfg)
    return p


def init_model(key, cfg: ArchConfig):
    keys = jax.random.split(key, 8)
    dt = cfg.pdtype()
    params: Dict[str, Any] = {
        "embed": common.normal_init(keys[0], (cfg.padded_vocab, cfg.d_model),
                                    dt),
        "final_norm": common.init_norm(cfg.d_model, dt, cfg.norm),
        "unembed": common.normal_init(keys[1], (cfg.d_model, cfg.padded_vocab),
                                      dt),
    }
    L = cfg.n_layers

    def stack(init_fn, n, key):
        return common.stack_layer_params(
            [init_fn(k) for k in jax.random.split(key, n)])

    if cfg.family in ("dense", "moe"):
        params["layers"] = stack(lambda k: _layer_init(k, cfg), L, keys[2])
    elif cfg.family == "hybrid":
        params["layers"] = stack(lambda k: ssm.init_mamba(k, cfg), L, keys[2])
        params["shared_attn"] = attention.init_attention(keys[3], cfg)
        params["shared_mlp"] = mlp.init_mlp(keys[4], cfg)
    elif cfg.family == "ssm":  # rwkv6
        params["layers"] = stack(
            lambda k: {"time": rwkv.init_rwkv_time(jax.random.fold_in(k, 0), cfg),
                       "chan": rwkv.init_rwkv_channel(jax.random.fold_in(k, 1), cfg)},
            L, keys[2])
    elif cfg.family == "vlm":
        n_cross = L // cfg.cross_attn_every
        n_self = L - n_cross
        params["layers"] = stack(lambda k: _layer_init(k, cfg), n_self, keys[2])
        params["cross_layers"] = stack(
            lambda k: {"attn": attention.init_attention(
                           jax.random.fold_in(k, 0), cfg, cross=True, gated=True),
                       "mlp": mlp.init_mlp(jax.random.fold_in(k, 1), cfg, gated=True)},
            n_cross, keys[3])
    elif cfg.family == "audio":
        params["enc_layers"] = stack(lambda k: {
            "attn": attention.init_attention(jax.random.fold_in(k, 0), cfg),
            "mlp": mlp.init_mlp(jax.random.fold_in(k, 1), cfg)},
            cfg.n_enc_layers, keys[2])
        params["enc_norm"] = common.init_norm(cfg.d_model, dt, cfg.norm)
        params["layers"] = stack(lambda k: {
            "attn": attention.init_attention(jax.random.fold_in(k, 0), cfg),
            "cross": attention.init_attention(jax.random.fold_in(k, 1), cfg),
            "mlp": mlp.init_mlp(jax.random.fold_in(k, 2), cfg)},
            L, keys[3])
    else:
        raise ValueError(cfg.family)
    return params


def param_spec(cfg: ArchConfig) -> Dict[str, Any]:
    spec: Dict[str, Any] = {
        "embed": P("model", "data"),
        "final_norm": common.norm_spec(cfg.norm),
        "unembed": P("data", "model"),
    }
    st = common.stacked_spec
    if cfg.family in ("dense", "moe"):
        spec["layers"] = st(_layer_spec(cfg))
    elif cfg.family == "hybrid":
        spec["layers"] = st(ssm.mamba_spec(cfg))
        spec["shared_attn"] = attention.attention_spec(cfg)
        spec["shared_mlp"] = mlp.mlp_spec(cfg)
    elif cfg.family == "ssm":
        spec["layers"] = st({"time": rwkv.rwkv_time_spec(cfg),
                             "chan": rwkv.rwkv_channel_spec(cfg)})
    elif cfg.family == "vlm":
        spec["layers"] = st(_layer_spec(cfg))
        spec["cross_layers"] = st({
            "attn": attention.attention_spec(cfg, cross=True, gated=True),
            "mlp": mlp.mlp_spec(cfg, gated=True)})
    elif cfg.family == "audio":
        spec["enc_layers"] = st({"attn": attention.attention_spec(cfg),
                                 "mlp": mlp.mlp_spec(cfg)})
        spec["enc_norm"] = common.norm_spec(cfg.norm)
        spec["layers"] = st({"attn": attention.attention_spec(cfg),
                             "cross": attention.attention_spec(cfg),
                             "mlp": mlp.mlp_spec(cfg)})
    return spec


def _norm(cfg, rt: Runtime = None):
    """Pre-norm in the sequence-sharded domain; the normalized bf16 output is
    then gathered to full-S (Megatron SP ordering: AG happens AFTER the norm
    and in the activation dtype, not on an f32 upcast of the residual)."""
    if rt is None:
        return lambda x, p: common.apply_norm(x, p, cfg.norm)

    from repro.models import tp

    def nf(x, p):
        y = common.apply_norm(x, p, cfg.norm)
        if x.ndim == 3 and x.shape[1] > 1:
            y = tp.gather_seq(y, rt)
        return y

    return nf


def _tree_slice(tree, lo, hi):
    return jax.tree_util.tree_map(lambda a: a[lo:hi], tree)


# ==========================================================================
# Full-sequence forward (training / prefill)
# ==========================================================================

def _dense_layer_fwd(pl, cfg, rt, x, extras):
    nf = _norm(cfg, rt)
    x = x + attention.full_attention(pl["attn"], cfg, rt,
                                     nf(x, pl["attn"]["norm"]))
    if cfg.family == "moe" and "moe" in pl:
        y, aux = moe.moe(pl["moe"], cfg, rt, nf(x, pl["moe"]["norm"]))
        return x + y, aux
    x = x + mlp.mlp(pl["mlp"], cfg, rt, nf(x, pl["mlp"]["norm"]))
    return x, jnp.zeros((), jnp.float32)


def _scan_layers(body, params_stack, x, rt: Runtime):
    """scan body(x, layer_params) -> (x, aux); accumulates aux."""
    def f(carry, pl):
        x, aux = carry
        # sequence-parallel boundary: saved (rematerialization-checkpoint)
        # activations are sharded over 'model' instead of replicated
        x = rt.shard(x, "batch", "seq", None)
        x2, a = body(x, pl)
        return (x2, aux + a), None

    wrapped = jax.checkpoint(f) if rt.remat else f
    (x, aux), _ = jax.lax.scan(wrapped, (x, jnp.zeros((), jnp.float32)),
                               params_stack)
    return x, aux


def apply_layers(params, cfg: ArchConfig, rt: Runtime, x, extras, lo: int,
                 hi: int):
    """Run layers [lo, hi) over x: (B, S, d). Returns (x, aux_loss)."""
    nf = _norm(cfg, rt)
    aux0 = jnp.zeros((), jnp.float32)
    if cfg.family in ("dense", "moe"):
        stack = _tree_slice(params["layers"], lo, hi)
        return _scan_layers(
            lambda x, pl: _dense_layer_fwd(pl, cfg, rt, x, extras),
            stack, x, rt)

    if cfg.family == "hybrid":
        flags = jnp.array([(i + 1) % cfg.attn_every == 0
                           for i in range(cfg.n_layers)])[lo:hi]
        stack = _tree_slice(params["layers"], lo, hi)
        sa, sm = params["shared_attn"], params["shared_mlp"]

        def body(x, inp):
            pl, flag = inp
            x = x + ssm.mamba(pl, cfg, rt, nf(x, pl["norm"]))

            def with_attn(x):
                h = x + attention.full_attention(sa, cfg, rt,
                                                 nf(x, sa["norm"]))
                return h + mlp.mlp(sm, cfg, rt, nf(h, sm["norm"]))

            x = jax.lax.cond(flag, with_attn, lambda x: x, x)
            return x, jnp.zeros((), jnp.float32)

        return _scan_layers(body, (stack, flags), x, rt)

    if cfg.family == "ssm":
        stack = _tree_slice(params["layers"], lo, hi)

        def body(x, pl):
            y, _ = rwkv.rwkv_time_mix(pl["time"], cfg, rt,
                                      nf(x, pl["time"]["norm"]))
            x = x + y
            y2, _ = rwkv.rwkv_channel_mix(pl["chan"], cfg, rt,
                                          nf(x, pl["chan"]["norm"]))
            return x + y2, jnp.zeros((), jnp.float32)

        return _scan_layers(body, stack, x, rt)

    if cfg.family == "vlm":
        g = cfg.cross_attn_every
        assert lo % g == 0 and hi % g == 0, "vlm cut must align to groups"
        glo, ghi = lo // g, hi // g
        n_groups = ghi - glo
        self_stack = jax.tree_util.tree_map(
            lambda a: a.reshape(cfg.n_layers // g, g - 1, *a.shape[1:])
                       [glo:ghi], params["layers"])
        cross_stack = _tree_slice(params["cross_layers"], glo, ghi)
        patches = extras["patches"]

        def body(x, inp):
            selfs, crossp = inp

            def inner(x, pl):
                y, _ = _dense_layer_fwd(pl, cfg, rt, x, extras)
                return y, None

            x, _ = jax.lax.scan(inner, x, selfs)
            h = nf(x, crossp["attn"]["norm"])
            x = x + attention.cross_attention(crossp["attn"], cfg, rt, h,
                                              patches, gated=True)
            x = x + mlp.mlp(crossp["mlp"], cfg, rt,
                            nf(x, crossp["mlp"]["norm"]), gated=True)
            return x, jnp.zeros((), jnp.float32)

        return _scan_layers(body, (self_stack, cross_stack), x, rt)

    if cfg.family == "audio":
        enc_out = extras["enc_out"]
        stack = _tree_slice(params["layers"], lo, hi)

        def body(x, pl):
            x = x + attention.full_attention(pl["attn"], cfg, rt,
                                             nf(x, pl["attn"]["norm"]))
            x = x + attention.cross_attention(pl["cross"], cfg, rt,
                                              nf(x, pl["cross"]["norm"]),
                                              enc_out)
            x = x + mlp.mlp(pl["mlp"], cfg, rt, nf(x, pl["mlp"]["norm"]))
            return x, jnp.zeros((), jnp.float32)

        return _scan_layers(body, stack, x, rt)

    raise ValueError(cfg.family)


def run_encoder(params, cfg: ArchConfig, rt: Runtime, frames):
    """Whisper encoder over stubbed frame embeddings (B, F, d)."""
    pos = common.sinusoidal_positions(frames.shape[1], cfg.d_model)
    x = frames + pos[None].astype(frames.dtype)
    nf = _norm(cfg, rt)

    def body(x, pl):
        x = x + attention.full_attention(pl["attn"], cfg, rt,
                                         nf(x, pl["attn"]["norm"]),
                                         causal=False, rope=False)
        x = x + mlp.mlp(pl["mlp"], cfg, rt, nf(x, pl["mlp"]["norm"]))
        return x, jnp.zeros((), jnp.float32)

    x, _ = _scan_layers(body, params["enc_layers"], x, rt)
    return common.apply_norm(x, params["enc_norm"], cfg.norm)


def embed(params, cfg: ArchConfig, rt: Runtime, tokens):
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.adtype())
    return rt.shard(x, "batch", None, None)


def lm_head(params, cfg: ArchConfig, rt: Runtime, x):
    x = common.apply_norm(x, params["final_norm"], cfg.norm)
    logits = x @ params["unembed"].astype(x.dtype)
    return rt.shard(logits, "batch", None, "model")


def make_extras(params, cfg: ArchConfig, rt: Runtime, batch):
    """Family-specific side inputs from the batch dict."""
    if cfg.family == "vlm":
        return {"patches": batch["patches"]}
    if cfg.family == "audio":
        return {"enc_out": run_encoder(params, cfg, rt, batch["frames"])}
    return {}


def forward(params, cfg: ArchConfig, rt: Runtime, batch,
            *, key=None) -> Tuple[jax.Array, jax.Array]:
    """Full forward (no split). Returns (logits, aux_loss)."""
    extras = make_extras(params, cfg, rt, batch)
    x = embed(params, cfg, rt, batch["tokens"])
    x, aux = apply_layers(params, cfg, rt, x, extras, 0, cfg.n_layers)
    return lm_head(params, cfg, rt, x), aux


def cross_entropy(logits, labels, rt: Runtime):
    """CE with model-sharded vocab; reductions lower to psums."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


# ==========================================================================
# Decode (one token against a cache)
# ==========================================================================

def init_cache(params, cfg: ArchConfig, rt: Runtime, batch: int, max_len: int,
               extras_batch: Optional[dict] = None):
    """Build the decode cache pytree (zeros; caches are donated each step)."""
    L = cfg.n_layers
    mk_kv = lambda n: jax.vmap(
        lambda _: attention.init_kv_cache(
            cfg, batch, max_len, bits=rt.kv_cache_bits))(jnp.arange(n))
    cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.family in ("dense", "moe"):
        cache["kv"] = mk_kv(L)
    elif cfg.family == "hybrid":
        n_sites = sum((i + 1) % cfg.attn_every == 0 for i in range(L))
        cache["mamba"] = jax.vmap(
            lambda _: ssm.init_mamba_cache(cfg, batch))(jnp.arange(L))
        cache["kv"] = mk_kv(n_sites)
    elif cfg.family == "ssm":
        cache["rwkv"] = jax.vmap(
            lambda _: rwkv.init_rwkv_cache(cfg, batch))(jnp.arange(L))
    elif cfg.family == "vlm":
        g = cfg.cross_attn_every
        n_groups = L // g
        cache["kv"] = mk_kv(L - n_groups)
        patches = (extras_batch or {}).get(
            "patches", jnp.zeros((batch, cfg.n_image_tokens, cfg.d_model),
                                 cfg.adtype()))
        cache["cross_kv"] = jax.vmap(
            lambda pl: jnp.stack(attention.cross_kv(pl["attn"], cfg, patches)))(
            params["cross_layers"])
    elif cfg.family == "audio":
        cache["kv"] = mk_kv(L)
        enc = (extras_batch or {}).get(
            "enc_out", jnp.zeros((batch, cfg.n_frames, cfg.d_model),
                                 cfg.adtype()))
        cache["cross_kv"] = jax.vmap(
            lambda pl: jnp.stack(attention.cross_kv(pl["cross"], cfg, enc)))(
            params["layers"])
    return cache


def cache_spec(cfg: ArchConfig, rt: Runtime):
    kv = common.stacked_spec(attention.kv_cache_spec(
        rt, bits=rt.kv_cache_bits))
    spec: Dict[str, Any] = {"pos": P()}
    if cfg.family in ("dense", "moe"):
        spec["kv"] = kv
    elif cfg.family == "hybrid":
        spec["mamba"] = common.stacked_spec(
            {"h": P(*rt.pspec("batch", "model", None, None)),
             "conv": P(*rt.pspec("batch", None, None))})
        spec["kv"] = kv
    elif cfg.family == "ssm":
        spec["rwkv"] = common.stacked_spec(
            {"S": P(*rt.pspec("batch", "model", None, None)),
             "x_tm": P(*rt.pspec("batch", None)),
             "x_cm": P(*rt.pspec("batch", None))})
    elif cfg.family == "vlm":
        spec["kv"] = kv
        spec["cross_kv"] = P(None, None, *rt.pspec("batch", "flashdecode", None,
                                                    None))
    elif cfg.family == "audio":
        spec["kv"] = kv
        spec["cross_kv"] = P(None, None, *rt.pspec("batch", "flashdecode", None,
                                                   None))
    return spec


def _decode_ffn(pl, cfg: ArchConfig, rt: Runtime, x):
    """The feed-forward half of one dense/moe decode layer, residual added."""
    nf = _norm(cfg)
    if cfg.family == "moe":
        y, _ = moe.moe(pl["moe"], cfg, rt, nf(x, pl["moe"]["norm"]))
    else:
        y = mlp.mlp(pl["mlp"], cfg, rt, nf(x, pl["mlp"]["norm"]))
    return x + y


def decode_layers(params, cfg: ArchConfig, rt: Runtime, x, cache, lo, hi):
    """One-token pass through layers [lo, hi). Returns (x, partial caches)."""
    nf = _norm(cfg)
    pos = cache["pos"]
    new_cache: Dict[str, Any] = {}

    if cfg.family in ("dense", "moe"):
        stack = _tree_slice(params["layers"], lo, hi)
        kv = _tree_slice(cache["kv"], lo, hi)

        def body(x, inp):
            pl, kvl = inp
            y, kv_new = attention.decode_attention(
                pl["attn"], cfg, rt, nf(x, pl["attn"]["norm"]), kvl, pos)
            return _decode_ffn(pl, cfg, rt, x + y), kv_new

        x, kv_out = jax.lax.scan(body, x, (stack, kv))
        new_cache["kv"] = kv_out
        return x, new_cache

    if cfg.family == "hybrid":
        flags = [(i + 1) % cfg.attn_every == 0 for i in range(cfg.n_layers)]
        site_of = []
        s = 0
        for f in flags:
            site_of.append(s if f else -1)
            s += int(f)
        stack = _tree_slice(params["layers"], lo, hi)
        mcache = _tree_slice(cache["mamba"], lo, hi)
        sites = [site_of[i] for i in range(lo, hi) if flags[i]]
        s_lo, s_hi = (sites[0], sites[-1] + 1) if sites else (0, 0)
        kv = _tree_slice(cache["kv"], s_lo, s_hi)
        sa, sm = params["shared_attn"], params["shared_mlp"]
        flag_arr = jnp.array(flags[lo:hi])
        site_arr = jnp.array([max(site_of[i] - s_lo, 0) for i in range(lo, hi)])

        def body(carry, inp):
            x, kv_all = carry
            pl, mc, flag, site = inp
            y, mc_new = ssm.mamba_decode(pl, cfg, rt, nf(x, pl["norm"]), mc)
            x = x + y

            def with_attn(x, kv_all):
                kvl = jax.tree_util.tree_map(lambda a: a[site], kv_all)
                y, kv_new = attention.decode_attention(
                    sa, cfg, rt, nf(x, sa["norm"]), kvl, pos)
                h = x + y
                h = h + mlp.mlp(sm, cfg, rt, nf(h, sm["norm"]))
                kv_all = jax.tree_util.tree_map(
                    lambda a, n: a.at[site].set(n), kv_all, kv_new)
                return h, kv_all

            x, kv_all = jax.lax.cond(flag, with_attn,
                                     lambda x, kv: (x, kv), x, kv_all)
            return (x, kv_all), mc_new

        (x, kv_out), mc_out = jax.lax.scan(
            body, (x, kv), (stack, mcache, flag_arr, site_arr))
        new_cache["mamba"] = mc_out
        new_cache["kv"] = kv_out
        return x, new_cache

    if cfg.family == "ssm":
        stack = _tree_slice(params["layers"], lo, hi)
        rcache = _tree_slice(cache["rwkv"], lo, hi)

        def body(x, inp):
            pl, rc = inp
            x, rc_new = rwkv.rwkv_decode(pl["time"], pl["chan"], cfg, rt,
                                         x, rc, _norm(cfg))
            return x, rc_new

        x, rc_out = jax.lax.scan(body, x, (stack, rcache))
        new_cache["rwkv"] = rc_out
        return x, new_cache

    if cfg.family == "vlm":
        g = cfg.cross_attn_every
        glo, ghi = lo // g, hi // g
        self_stack = jax.tree_util.tree_map(
            lambda a: a.reshape(cfg.n_layers // g, g - 1, *a.shape[1:])
                       [glo:ghi], params["layers"])
        cross_stack = _tree_slice(params["cross_layers"], glo, ghi)
        kv = jax.tree_util.tree_map(
            lambda a: a.reshape(cfg.n_layers // g, g - 1, *a.shape[1:])
                       [glo:ghi], cache["kv"])
        cross_kv = cache["cross_kv"][glo:ghi]

        def body(x, inp):
            selfs, crossp, kvg, ckv = inp

            def inner(x, inp2):
                pl, kvl = inp2
                y, kv_new = attention.decode_attention(
                    pl["attn"], cfg, rt, nf(x, pl["attn"]["norm"]), kvl, pos)
                x = x + y
                x = x + mlp.mlp(pl["mlp"], cfg, rt, nf(x, pl["mlp"]["norm"]))
                return x, kv_new

            x, kv_new = jax.lax.scan(inner, x, (selfs, kvg))
            h = nf(x, crossp["attn"]["norm"])
            x = x + attention.cross_attention(
                crossp["attn"], cfg, rt, h, kv_cache=(ckv[0], ckv[1]),
                gated=True)
            x = x + mlp.mlp(crossp["mlp"], cfg, rt,
                            nf(x, crossp["mlp"]["norm"]), gated=True)
            return x, kv_new

        x, kv_out = jax.lax.scan(body, x, (self_stack, cross_stack, kv,
                                           cross_kv))
        new_cache["kv"] = jax.tree_util.tree_map(
            lambda a: a.reshape(-1, *a.shape[2:]), kv_out)
        return x, new_cache

    if cfg.family == "audio":
        stack = _tree_slice(params["layers"], lo, hi)
        kv = _tree_slice(cache["kv"], lo, hi)
        ckv = cache["cross_kv"][lo:hi]

        def body(x, inp):
            pl, kvl, ck = inp
            y, kv_new = attention.decode_attention(
                pl["attn"], cfg, rt, nf(x, pl["attn"]["norm"]), kvl, pos)
            x = x + y
            x = x + attention.cross_attention(
                pl["cross"], cfg, rt, nf(x, pl["cross"]["norm"]),
                kv_cache=(ck[0], ck[1]))
            x = x + mlp.mlp(pl["mlp"], cfg, rt, nf(x, pl["mlp"]["norm"]))
            return x, kv_new

        x, kv_out = jax.lax.scan(body, x, (stack, kv, ckv))
        new_cache["kv"] = kv_out
        return x, new_cache

    raise ValueError(cfg.family)


@partial(jax.jit, static_argnames=("cfg", "rt"))
def _decode_layer_in_place(layers, x, kv, pos, rows, ring, layer, *,
                           cfg: ArchConfig, rt: Runtime):
    """One layer of `decode_rows_in_place`. `layer` is traced, so every
    layer (and every program that steps the same arena) reuses one trace;
    XLA inlines the call and folds the constant index."""
    nf = _norm(cfg)
    pl = jax.tree_util.tree_map(lambda a: a[layer], layers)

    def qkv(x, pos):
        return attention.decode_qkv(pl["attn"], cfg,
                                    nf(x, pl["attn"]["norm"]), pos,
                                    quant="k_scale" in kv)

    def rest(x, q, kvl, pos):
        y = attention.decode_attend(pl["attn"], cfg, rt, q, kvl, pos,
                                    x.dtype)
        return _decode_ffn(pl, cfg, rt, x + y)

    q, entries = jax.vmap(qkv)(x, pos)
    heads = jnp.arange(cfg.n_kv_heads)[None, :]
    # (row, layer, batch 0, head, ring slot) <- (C, Hkv[, hd])
    kv = {name: leaf.at[rows, layer, 0, heads, ring].set(
              entries[name][:, 0, :, 0], mode="drop")
          for name, leaf in kv.items()}
    x = jax.vmap(rest)(x, q, {n: a[:, layer] for n, a in kv.items()}, pos)
    # the residual stream is rounded to its dtype between layers, as
    # `decode_layers`' scan carry is: no fusion across the boundary may
    # keep it at a wider precision
    return jax.lax.optimization_barrier(x), kv


def decode_rows_in_place(params, cfg: ArchConfig, rt: Runtime, x, cache,
                         active, lo: int, hi: int):
    """One-token pass of dense/moe layers [lo, hi) over a stack of
    independent rows, each with its own ring KV cache and position, whose
    new K/V entries are written into the row-stacked cache in place.

    x: (C, 1, 1, d); cache: {'pos': (C,) int32, 'kv': leaves stacked
    (C, L, 1, Hkv, size, ...)}, as `SlotArena` stacks `init_cache(batch=1)`;
    active: (C,) bool. Returns (x, new cache).

    Each layer projects every row's q and K/V, scatters the active rows'
    entries to (row, layer, pos % size) (an inactive row's index is out of
    range and its write is dropped), then attends each row over its layer
    of the updated cache. Per row the math is `decode_layers`' (the same
    per-row functions, vmapped), so tokens and written entries match it;
    the cache moves only where a row writes, so under a donated jit the
    leaves update in place with no whole-cache select or copy. Layers
    outside [lo, hi), inactive rows and every other position keep their
    bits; `pos` advances on active rows only. The layer loop is unrolled
    (the layer index is a constant to XLA), so no scan slices or restacks
    the cache.
    """
    pos = cache["pos"]
    kv = dict(cache["kv"])
    n_rows, size = pos.shape[0], kv["k"].shape[4]
    # scatter index per (row, head): an inactive row's is out of range
    rows = jnp.where(active, jnp.arange(n_rows), n_rows)[:, None]
    ring = (pos % size).astype(jnp.int32)[:, None]
    for layer in range(lo, hi):
        x, kv = _decode_layer_in_place(params["layers"], x, kv, pos, rows,
                                       ring, jnp.int32(layer), cfg=cfg,
                                       rt=rt)
    return x, {**cache, "kv": kv, "pos": jnp.where(active, pos + 1, pos)}


def decode_step(params, cfg: ArchConfig, rt: Runtime, token, cache):
    """token: (B, 1) int32. Returns (logits (B, 1, V), new cache)."""
    x = embed(params, cfg, rt, token)
    x, new_partial = decode_layers(params, cfg, rt, x, cache, 0, cfg.n_layers)
    logits = lm_head(params, cfg, rt, x)
    new_cache = dict(cache)
    new_cache.update(new_partial)
    new_cache["pos"] = cache["pos"] + 1
    return logits, new_cache
