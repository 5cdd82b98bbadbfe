"""A configuration file as the program's `ArchConfig`, and the benchmark's
own random weights in the program's parameter layout.

The weights are the benchmark's, not the program's: made from the seed on
the device in one jitted program, in the dtype they are served in, and
handed both to the program and to the plain reference. The layout is the
program's (`repro.models.transformer.init_model`); `init_weights` checks
its tree against the program's shapes and fails loudly where they part.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def arch_config(conf: dict):
    """The program's config object for a configuration file."""
    from repro.models.config import ArchConfig

    if conf["architecture"] != "dense_gqa":
        raise ValueError(f"architecture {conf['architecture']!r}")
    dt = conf["torch_dtype"]
    return ArchConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        qk_norm=conf["qk_norm"], rope_theta=float(conf["rope_theta"]),
        param_dtype=dt, dtype=dt)


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also one over 32 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def padded_vocab(conf: dict) -> int:
    return (conf["vocab_size"] + 255) // 256 * 256


def _weights(key, conf: dict):
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    hq, hkv, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                   conf["head_dim"])
    L, V, Vp = conf["num_hidden_layers"], conf["vocab_size"], padded_vocab(conf)
    dt = jnp.dtype(conf["torch_dtype"])
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return (std * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dt)

    def scale(shape):
        # norm gains near 1, not 1: a program that skips a gain shows
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dt)

    live = (jnp.arange(Vp) < V)
    attn = {"norm": {"scale": scale((L, d))},
            "wq": normal((L, d, hq * hd), d ** -0.5),
            "wk": normal((L, d, hkv * hd), d ** -0.5),
            "wv": normal((L, d, hkv * hd), d ** -0.5),
            "wo": normal((L, hq * hd, d), (hq * hd) ** -0.5)}
    if conf["qk_norm"]:
        attn["q_norm"] = {"scale": scale((L, hd))}
        attn["k_norm"] = {"scale": scale((L, hd))}
    return {
        # rows and columns past the published vocabulary are zero, so no
        # padded id is ever the greedy token
        "embed": normal((Vp, d), 1.0) * live[:, None].astype(dt),
        "final_norm": {"scale": scale((d,))},
        "unembed": normal((d, Vp), d ** -0.5) * live[None, :].astype(dt),
        "layers": {"attn": attn,
                   "mlp": {"norm": {"scale": scale((L, d))},
                           "w_gate": normal((L, d, ff), d ** -0.5),
                           "w_up": normal((L, d, ff), d ** -0.5),
                           "w_down": normal((L, ff, d), ff ** -0.5)}},
    }


def init_weights(conf: dict, seed: int):
    """The weights on the device, made in one jitted program."""
    from repro.models import transformer

    cfg = arch_config(conf)
    key = seed_key(seed)
    want = jax.eval_shape(lambda k: transformer.init_model(k, cfg), key)
    got = jax.eval_shape(lambda k: _weights(k, conf), key)
    sig = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype.name), t)
    if sig(want) != sig(got):
        raise ValueError("the benchmark's weights no longer match the "
                         "program's parameter layout")
    params = jax.jit(lambda k: _weights(k, conf))(key)
    return jax.block_until_ready(params)


def label_owner(conf: dict, full):
    """The label owner's own config and weights: the program's config cut
    to the layers above the cut (so its arena holds their KV only), and a
    device copy of those layers, the final norm and the unembedding."""
    cut = conf["cut_layer"]
    cfg = arch_config(conf).with_(n_layers=conf["num_hidden_layers"] - cut)
    top = jax.jit(lambda w: {
        "layers": jax.tree.map(lambda a: a[cut:], w["layers"]),
        "final_norm": w["final_norm"], "unembed": w["unembed"]})(full)
    return cfg, jax.block_until_ready(top)


def nbytes(tree) -> int:
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree.leaves(tree))
