"""Compile-only checks: every Pallas kernel the served path reaches on a
TPU lowers under Mosaic and compiles for a described v5e chip at the
published width of the benchmark model (d = 4096, k = 64, a bf16 arena),
and the served fused step updates its arena in place there.

Nothing runs: the chip is described (`jax.experimental.topologies`), not
attached, so these tests catch what interpret mode cannot — unaligned
blocks, primitives without a Mosaic lowering, VMEM overruns. The topology
is built inside a module fixture, never at import: only one process at a
time may load the TPU library, and building it while pytest collects
would give each xdist worker a different set of tests.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.payload import Payload, PayloadMeta
from repro.kernels.decode import kernel as dec
from repro.kernels.encode import kernel as enc
from repro.kernels.randtopk import kernel as sel
from repro.models import transformer
from repro.models.config import ArchConfig, Runtime
from repro.runtime import steps
from repro.runtime.server import jit_serving_steps

D, K = 4096, 64
ROWS = 8            # a full flush bucket; one client step encodes 1 row
CAPACITY = 8        # arena slots (+1 scratch row)

#: wire leaves (trailing shape, dtype) per payload kind
LEAVES = {
    "dense": [((D,), jnp.float32)],
    "sparse": [((K,), jnp.float32), ((K,), jnp.uint16)],
    "quant": [((D,), jnp.uint8), ((2,), jnp.float32)],
    "sparse_quant": [((K,), jnp.uint8), ((K,), jnp.uint16),
                     ((2,), jnp.float32)],
    "mask": [((K,), jnp.float32), ((D // 32,), jnp.uint32)],
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable can be written to the persistent cache
    # but not read back without the chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"


def test_topk_mask_compiles(one_chip):
    _compile(lambda x: sel.topk_mask_threshold(x, K, interpret=False),
             one_chip, ((ROWS, D), jnp.bfloat16))


def test_randtopk_mask_compiles(one_chip):
    _compile(lambda x, g, m: sel.randtopk_mask_kernel(x, g, m, K,
                                                      interpret=False),
             one_chip, ((ROWS, D), jnp.bfloat16), ((ROWS, D), jnp.float32),
             ((ROWS, 1), jnp.int32))


@pytest.mark.parametrize("kind,bits", [("sparse", 0), ("sparse_quant", 8),
                                       ("quant", 4), ("mask", 0)])
def test_encode_rows_compiles(one_chip, kind, bits):
    _compile(lambda x, m: enc.encode_rows_kernel(x, m, kind=kind, k=K,
                                                 bits=bits, interpret=False),
             one_chip, ((1, D), jnp.bfloat16), ((1, D), jnp.int32))


@pytest.mark.parametrize("width", [4, 12])
def test_pack_bits_compiles(one_chip, width):
    n = D if width == 4 else K          # quant codes / sparse indices
    _compile(lambda v: enc.pack_bits_kernel(v, width, interpret=False),
             one_chip, ((n,), jnp.int32))


@pytest.mark.parametrize("kind", sorted(LEAVES))
def test_decode_rows_compiles(one_chip, kind):
    _compile(lambda *a: dec.decode_rows_kernel(tuple(a), kind, D,
                                               interpret=False),
             one_chip, *[((ROWS,) + s, dt) for s, dt in LEAVES[kind]])


@pytest.mark.parametrize("kind", sorted(LEAVES))
def test_decode_to_slots_compiles(one_chip, kind):
    _compile(lambda xb, sl, *a: dec.decode_to_slots_kernel(
                 xb, tuple(a), sl, kind, interpret=False),
             one_chip, ((CAPACITY + 1, D), jnp.bfloat16),
             ((ROWS,), jnp.int32),
             *[((ROWS,) + s, dt) for s, dt in LEAVES[kind]])


#: (d_model, heads, kv heads, head dim, d_ff, arena slots): Qwen3-8B and
#: Phi-3-mini widths and arenas, four layers (the chat cell's label owner)
#: and a small vocabulary; a KV leaf (1.07 GB, 537 MB) is larger than the
#: chip's VMEM, so it stays in HBM as served
STEP_SHAPES = {"qwen3-8b": (4096, 32, 8, 128, 12288, 128),
               "phi3-mini": (3072, 32, 32, 96, 8192, 16)}


@pytest.mark.parametrize("shape", sorted(STEP_SHAPES))
def test_fused_arena_step_updates_in_place(one_chip, shape):
    """The served fused decode+step program at Qwen3-8B (GQA, head dim
    128) and Phi-3-mini (MHA, head dim 96) widths, cut to four layers,
    with a bf16 arena of 1024 positions, compiled for the chip: the
    donated KV leaves alias in place, no op copies or re-selects a whole
    leaf, and the program's temporaries stay under one K leaf (the step
    used to copy the arena several times over)."""
    d, heads, kv_heads, head_dim, d_ff, cap = STEP_SHAPES[shape]
    cfg = ArchConfig(name=shape, family="dense", n_layers=4, d_model=d,
                     n_heads=heads, n_kv_heads=kv_heads, head_dim=head_dim,
                     d_ff=d_ff, vocab=2048, qk_norm=True,
                     param_dtype="bfloat16", dtype="bfloat16")
    rt = Runtime(mesh=None, training=False)
    max_len, rows = 1024, 8

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(spec, jax.eval_shape(
        lambda k: transformer.init_model(k, cfg), jax.random.key(0)))
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((cap,) + a.shape, a.dtype,
                                       sharding=one_chip),
        jax.eval_shape(lambda: transformer.init_cache(None, cfg, rt, 1,
                                                      max_len)))
    payload = Payload(meta=PayloadMeta("sparse", d=cfg.d_model, k=K),
                      values=spec(jax.ShapeDtypeStruct((rows, 1, 1, K),
                                                       jnp.float32)),
                      indices=spec(jax.ShapeDtypeStruct((rows, 1, 1, K),
                                                        jnp.uint16)))
    _, fused = jit_serving_steps(steps.make_arena_top_step(cfg, rt, 0),
                                 dtype=cfg.adtype(), backend="xla")
    compiled = fused.lower(
        params, spec(jax.ShapeDtypeStruct((cap + 1, 1, 1, cfg.d_model),
                                          cfg.adtype())),
        payload, spec(jax.ShapeDtypeStruct((rows,), jnp.int32)), cache,
        spec(jax.ShapeDtypeStruct((cap,), jnp.bool_))).compile()
    leaf = cache["kv"]["k"]
    leaf_bytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
    hlo = compiled.as_text()
    aliased = re.findall(r"\{[\d,]*\}: \((\d+), \{", hlo.split("\n", 1)[0])
    kv_params = re.findall(
        r"parameter\((\d+)\).*op_name=\"cache\[\\?'kv\\?'\]", hlo)
    assert kv_params and set(kv_params) <= set(aliased)
    whole = [m.group(0) for m in re.finditer(
        r"= \w+\[([\d,]*)\]\S* (copy|transpose|select)\(", hlo)
        if int(np.prod([int(d) for d in m.group(1).split(",") if d])) ==
        int(np.prod(leaf.shape))]
    assert whole == []
    assert compiled.memory_analysis().temp_size_in_bytes < leaf_bytes
