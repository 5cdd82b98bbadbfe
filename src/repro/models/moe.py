"""Mixture-of-Experts block with expert parallelism over the `model` axis.

Design (TPU-native, no (T, E, C) one-hot):
  * expert weights are sharded E -> 'model' (E_loc per rank) and d -> 'data'
    (FSDP); inside `shard_map` the d shards are all-gathered per use;
  * activations enter replicated across 'model' (standard Megatron residual
    stream); every rank computes only the tokens routed to ITS local experts
    via a capacity-C gather (sorted by intra-expert arrival order), grouped
    einsum, scatter-add, then a psum over 'model' combines expert outputs;
  * router is computed redundantly on every rank (cheap, avoids a broadcast).

Falls back to the identical local computation without collectives when no
mesh / no 'model' axis is present (single-device smoke tests).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import common
from repro.models.config import ArchConfig, Runtime


def init_moe(key, cfg: ArchConfig):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = cfg.pdtype()
    ks = jax.random.split(key, 4)
    return {
        "norm": common.init_norm(d, dt, cfg.norm),
        "router": common.normal_init(ks[0], (d, E), dt),
        "w_gate": common.normal_init(ks[1], (E, d, ff), dt),
        "w_up": common.normal_init(ks[2], (E, d, ff), dt),
        "w_down": common.normal_init(ks[3], (E, ff, d), dt,
                                     scale=0.02 / max(1, cfg.n_layers) ** 0.5),
    }


def moe_spec(cfg: ArchConfig):
    return {
        "norm": common.norm_spec(cfg.norm),
        "router": P(None, None),
        "w_gate": P("model", "data", None),
        "w_up": P("model", "data", None),
        "w_down": P("model", None, "data"),
    }


def _local_moe(x_flat, router_w, wg, wu, wd, *, cfg: ArchConfig, e_offset,
               capacity: int):
    """Per-rank MoE over local experts. x_flat: (T, d) [replicated copy].

    Returns (partial_y (T, d), router_probs (T, E)).
    """
    T, d = x_flat.shape
    E, topk = cfg.n_experts, cfg.topk_experts
    E_loc = wg.shape[0]

    logits = (x_flat @ router_w.astype(x_flat.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    top_p, top_i = jax.lax.top_k(probs, topk)                   # (T, topk)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)      # renormalize

    def one_expert(e_local):
        gid = e_offset + e_local
        hit_slots = top_i == gid                                # (T, topk)
        hit = jnp.any(hit_slots, axis=-1)                       # (T,)
        w_tok = jnp.sum(jnp.where(hit_slots, top_p, 0.0), axis=-1)
        order_rank = jnp.cumsum(hit.astype(jnp.int32)) - 1      # arrival order
        prio = jnp.where(hit, order_rank, T + 1)
        order = jnp.argsort(prio)[:capacity]                    # (C,) token ids
        valid = jnp.take(prio, order) <= capacity - 1
        scatter_to = jnp.where(valid, order, T)                 # T -> dropped
        return order, scatter_to, (jnp.take(w_tok, order) * valid)

    order, scatter_to, w_tok = jax.vmap(one_expert)(jnp.arange(E_loc))
    x_e = jnp.take(x_flat, order.reshape(-1), axis=0)
    x_e = x_e.reshape(E_loc, capacity, d)                       # (E_loc, C, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x_e, wg.astype(x_e.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", x_e, wu.astype(x_e.dtype))
    out = jnp.einsum("ecf,efd->ecd", h, wd.astype(x_e.dtype))
    out = out * w_tok[..., None].astype(out.dtype)
    y = jnp.zeros((T, d), out.dtype).at[scatter_to.reshape(-1)].add(
        out.reshape(-1, d), mode="drop")
    return y, probs


def _capacity(t_local: int, cfg: ArchConfig, factor: float) -> int:
    c = math.ceil(t_local * cfg.topk_experts / cfg.n_experts * factor)
    return min(t_local, max(4, c))  # decode floor of 4, never above T_local


def moe(p, cfg: ArchConfig, rt: Runtime, x):
    """x: (B, S, d) replicated over 'model', batch-sharded. Returns (y, aux)."""
    B, S, d = x.shape
    topk = cfg.topk_experts

    if (rt.mesh is not None and rt.has_model_axis
            and rt.mesh.shape["model"] > 1 and not rt.dp_only):
        mesh = rt.mesh
        n_model = mesh.shape["model"]
        assert cfg.n_experts % n_model == 0, "experts must divide model axis"
        batch_axes = rt.batch_axes or ()
        n_batch = 1
        for a in batch_axes:
            n_batch *= mesh.shape[a]
        if B % n_batch != 0:  # tiny decode batches: replicate over data
            batch_axes, n_batch = (), 1
        t_loc = (B * S) // n_batch
        cap = _capacity(t_loc, cfg, rt.moe_capacity)
        bspec = P(batch_axes if batch_axes else None, None, None)

        n_model_ax = mesh.shape["model"]
        scatter_seq = (rt.seq_shard and S > 1
                       and (B * S) % (n_batch * n_model_ax) == 0)

        def ranked(xb, router_w, wg, wu, wd):
            e_loc = wg.shape[0]
            rank = jax.lax.axis_index("model")
            wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, "data", axis=2, tiled=True)
            xf = xb.reshape(-1, d)
            y, probs = _local_moe(xf, router_w, wg, wu, wd, cfg=cfg,
                                  e_offset=rank * e_loc, capacity=cap)
            if scatter_seq:
                # combine experts with a reduce-scatter into the sequence-
                # parallel domain (matches attention/MLP output projections);
                # a full psum here costs 16x the link bytes. Scatter along
                # the SEQUENCE axis (scattering the flat (b,s) axis would
                # permute batch rows across ranks).
                y = y.reshape(xb.shape[0], -1, d)
                y = jax.lax.psum_scatter(y, "model", scatter_dimension=1,
                                         tiled=True)
            else:
                y = jax.lax.psum(y, "model")
            # aux loss from the (replicated) router stats, averaged over batch
            _, top_i = jax.lax.top_k(probs, topk)
            f = jnp.mean(jnp.sum(jax.nn.one_hot(top_i, cfg.n_experts,
                                                dtype=jnp.float32), axis=1), axis=0)
            pbar = jnp.mean(probs, axis=0)
            aux = cfg.n_experts * jnp.sum(f * pbar)
            if batch_axes:
                aux = jax.lax.pmean(aux, batch_axes)
            if scatter_seq:
                return y, aux
            return y.reshape(xb.shape), aux

        out_bspec = (P(batch_axes if batch_axes else None, "model", None)
                     if scatter_seq else bspec)
        y, aux = jax.shard_map(
            ranked, mesh=mesh,
            in_specs=(bspec, P(None, None), P("model", "data", None),
                      P("model", "data", None), P("model", None, "data")),
            out_specs=(out_bspec, P()), check_vma=False,
        )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    else:
        cap = _capacity(B * S, cfg, rt.moe_capacity)
        xf = x.reshape(-1, d)
        y, probs = _local_moe(xf, p["router"], p["w_gate"], p["w_up"],
                              p["w_down"], cfg=cfg, e_offset=0, capacity=cap)
        _, top_i = jax.lax.top_k(probs, topk)
        f = jnp.mean(jnp.sum(jax.nn.one_hot(top_i, cfg.n_experts,
                                            dtype=jnp.float32), axis=1), axis=0)
        aux = cfg.n_experts * jnp.sum(f * jnp.mean(probs, axis=0))
        y = y.reshape(B, S, d)

    return rt.shard(y, "batch", "seq", None), aux
