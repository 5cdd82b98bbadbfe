"""Chip smoke test: the label owner's serving path, end to end on a TPU.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # four chips: the sharded arena only

One chip. `qwen3-8b` at its published widths (bf16, d_model 4096, 32/8
heads of 128, d_ff 12288, vocab 151936) with only the depth cut, to 8
layers split after layer 4; the weights are random, drawn from `--seed`.
The script checks every Pallas kernel of the served path against its XLA
or host-codec counterpart on the same inputs, serves identity, randtopk,
randtopk_mask and quant clients through `engine.run_streaming`, prints
measured against Table-2 analytic wire bytes per token, and checks that
the compiled fused decode+step holds Mosaic kernels (`tpu_custom_call`).

Four chips (`--four-chips`). The same model through the sharded arena on
a (pod 2, data 1, model 2) mesh, with fewer slots than clients so slots
are evicted and readmitted, against the one-device arena; and one direct
arena step against the one-device step.

Progress goes to stdout, one line per result. The last line is one JSON
object, `{"ok": true, "device": {...}}`. Without a TPU, or when any check
fails, the script exits nonzero and prints no such line. One process
holds the chip; nothing is started beside it. The compile cache is
`$JAX_COMPILATION_CACHE_DIR`, else `<checkout>/.jax_cache`.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

K = 64                      # support size of the sparse compressors
MIX = ["identity", f"randtopk:k={K}", f"randtopk_mask:k={K}", "quant:bits=4"]
N_CLIENTS, PROMPT_LEN, GEN = 8, 4, 4
LAYERS, CUT = 8, 4
HBM_BYTES = 16e9            # one v5e chip
#: KV tolerance of the sharded step, as a share of the leaf's largest
#: magnitude. K/V are bf16: a shard steps fewer rows, the compiler may sum
#: the f32 projection in another order, and a last-bit change survives the
#: bf16 rounding, the qk-norm and the rotary product as a few bf16 steps
#: (one step is 2^-8..2^-7 of a value); 2^-5 allows four.
KV_RTOL = 2.0 ** -5


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit shows as a near-zero compile)."""

    def __init__(self):
        from jax import monitoring

        self.seconds, self.programs, self.hits, self.misses = 0.0, 0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return self.seconds, self.programs, self.hits, self.misses

    def since(self, mark) -> str:
        s, p, h, m = (a - b for a, b in zip(self.mark(), mark))
        return (f"{s:.2f} s backend compile over {p} programs "
                f"(persistent cache: {h} hits, {m} misses)")


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def chip_share_config():
    import repro.configs as configs
    from repro.models.config import SplitConfig

    return configs.get("qwen3-8b").with_(
        n_layers=LAYERS, split=SplitConfig(cut_layer=CUT,
                                           compressor="randtopk", k=K))


def init_params(cfg, seed: int):
    """Random weights made on the device in one program (no host copy)."""
    import jax
    import numpy as np
    import repro.configs as configs
    from repro.models import transformer

    shapes = jax.eval_shape(lambda k: transformer.init_model(k, cfg),
                            jax.random.key(seed))
    nb = lambda t: sum(int(np.prod(a.shape)) * a.dtype.itemsize
                       for a in jax.tree.leaves(t))
    layers = nb(shapes["layers"])
    total = nb(shapes)
    print(f"config: {cfg.name} d_model {cfg.d_model}, {cfg.n_heads}/"
          f"{cfg.n_kv_heads} heads of {cfg.hd}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab}, {cfg.param_dtype}; depth cut to {cfg.n_layers} of "
          f"{configs.get(cfg.name).n_layers} layers, split after layer "
          f"{cfg.split.cut_layer}. Config arithmetic: {layers / 1e9:.2f} GB "
          f"of layers + "
          f"{(total - layers) / 1e9:.2f} GB embed/unembed/norm = "
          f"{total / 1e9:.2f} GB of {HBM_BYTES / 1e9:.0f} GB HBM",
          flush=True)
    t = time.perf_counter()
    params = jax.jit(lambda k: transformer.init_model(k, cfg))(
        jax.random.key(seed))
    jax.block_until_ready(params)
    print(f"init: params on device in {time.perf_counter() - t:.1f} s",
          flush=True)
    return params


def _assert_ulp(got, ref, what: str, mantissa_bits: int) -> None:
    """|got - ref| <= 1 ulp (of a float with `mantissa_bits` explicit
    mantissa bits) at the reference's largest magnitude — the documented
    FMA-contraction bound of the dequant."""
    import numpy as np

    ref = np.asarray(ref, np.float64)
    top = float(np.abs(ref).max()) or 1.0
    ulp = 2.0 ** (np.floor(np.log2(top)) - mantissa_bits)
    err = float(np.abs(np.asarray(got, np.float64) - ref).max())
    check(err <= ulp, f"{what}: max |diff| {err} > 1 ulp ({ulp})")


def check_kernels(cfg, seed: int) -> None:
    """Each served-path Pallas kernel against its XLA or host-codec
    counterpart, on the same inputs, at the config's width."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import compressors, selection, wire
    from repro.core.payload import Payload
    from repro.kernels.encode import ops as enc_ops
    from repro.split import protocol

    d, rows = cfg.d_model, 8
    # one row per client step, stacked as the server stacks a flush
    x = jax.random.normal(jax.random.key(seed + 7), (rows, 1, 1, d),
                          cfg.adtype())

    # selection: deterministic top-k (serving) and Eq. (7) (training)
    m_p = selection.topk_mask(x, K, backend="pallas")
    m_x = selection.topk_mask(x, K, backend="xla")
    check(bool(jnp.array_equal(m_p, m_x)), "topk_mask: pallas != xla")
    check(bool((m_p.sum(-1) == K).all()), "topk_mask: row count != k")
    key = jax.random.key(seed + 8)
    r_p = selection.randtopk_mask(x, K, 0.1, key, backend="pallas")
    r_x = selection.randtopk_mask(x, K, 0.1, key, backend="xla")
    check(bool(jnp.array_equal(r_p, r_x)), "randtopk_mask: pallas != xla")
    check(bool((r_p.sum(-1) == K).all()), "randtopk_mask: row count != k")
    print(f"kernel selection: top-k and Eq. (7) masks equal to XLA "
          f"({rows} rows x {d}, k {K})", flush=True)

    # encode: device frames (selection -> gather -> quantize -> pack
    # kernels) byte-equal to the host codec on the XLA encode
    payloads = {}
    for spec in (f"randtopk:k={K}", f"randtopk_mask:k={K}", "quant:bits=4",
                 f"randtopk_quant:k={K},bits=8", "identity"):
        comp = compressors.make_compressor(spec)
        ref = protocol.client_encode(
            dataclasses.replace(comp, backend="xla"), x)
        payloads[ref.meta.kind] = ref
        if ref.meta.kind == "dense":
            continue
        p, sections = protocol.client_encode_device(
            dataclasses.replace(comp, backend="pallas"), x)
        body = enc_ops.sections_to_bytes(p.meta, p.batch_shape, sections)
        host = wire.encode_payload(ref)
        check(body == host, f"encode {p.meta.kind}: device bytes differ "
              f"from the host codec")
        print(f"kernel encode {p.meta.kind}: {len(body)} B for {rows} rows, "
              f"identical to the host codec", flush=True)

    # decode: rows and the slot-arena write, against the XLA decode
    slots = np.asarray([5, 2, 7, 0], np.int32)
    xbuf0 = jax.random.normal(jax.random.key(seed + 9), (9, 1, 1, d),
                              cfg.adtype())
    untouched = np.setdiff1d(np.arange(9), slots)
    for kind, ref in payloads.items():
        dev = jax.tree.map(jnp.asarray, ref)
        got = compressors.payload_to_dense(dev, backend="pallas")
        want = compressors.payload_to_dense(dev, backend="xla")
        head = Payload(meta=ref.meta, **{n: a[:len(slots)] for n, a in
                                         dev.wire_leaves()})
        got_s, want_s = (np.asarray(protocol.decode_to_slots_in_jit(
            xbuf0, head, slots, dtype=cfg.dtype, backend=b)).astype(
                np.float32) for b in ("pallas", "xla"))
        if kind in ("quant", "sparse_quant"):
            _assert_ulp(got, want, f"decode {kind}", 23)        # f32
            _assert_ulp(got_s, want_s, f"decode-to-slots {kind}",
                        7 if cfg.dtype == "bfloat16" else 23)
            exact = "within 1 ulp"
        else:
            check(bool(jnp.array_equal(got, want)), f"decode {kind} inexact")
            check(np.array_equal(got_s, want_s),
                  f"decode-to-slots {kind} inexact")
            exact = "exact"
        check(np.array_equal(got_s[untouched],
                             np.asarray(xbuf0, np.float32)[untouched]),
              f"decode-to-slots {kind}: untouched slot rows moved")
        print(f"kernel decode {kind}: rows and slot write {exact} vs XLA",
              flush=True)


def serve(cfg, params, seed: int, log: CompileLog) -> None:
    """The served path through the user entry point: the four-compressor
    mix (lock-step clients: every flush mixes metas, so each flush runs a
    decode per meta and then the arena step), then randtopk alone (every
    flush one meta: the fused decode+step program)."""
    import numpy as np
    from repro.runtime import engine

    for label, mix in (("mixed", MIX), ("fused", [f"randtopk:k={K}"])):
        mark = log.mark()
        t = time.perf_counter()
        res = engine.run_streaming(
            cfg, n_clients=N_CLIENTS, prompt_len=PROMPT_LEN, gen=GEN,
            max_batch=N_CLIENTS, compressor_mix=mix, params=params,
            seed=seed)
        setup = time.perf_counter() - t - res["wall_s"]
        print(f"compile: {label} serving warm-up {log.since(mark)}; set-up "
              f"incl. warm {setup:.1f} s", flush=True)
        tok = res["tokens"]
        check(tok.shape == (N_CLIENTS, GEN), f"tokens shape {tok.shape}")
        check(bool(((tok >= 0) & (tok < cfg.vocab)).all()), "token id range")
        check(not any(res["fault_counters"].values()),
              f"faults on a clean wire: {res['fault_counters']}")
        print(f"serve {label}: {tok.size} tokens to {N_CLIENTS} sessions "
              f"({PROMPT_LEN} prompt + {GEN} generated each) in "
              f"{res['wall_s']:.2f} s wall over {res['flushes']} flushes "
              f"(mean fill {np.mean(res['batch_sizes']):.2f})", flush=True)
        per = {}
        for name, cs, comp in zip(res["compressors"], res["client_stats"],
                                  res["compressor_objs"]):
            check(cs["tokens_out"] == GEN,
                  f"{name}: {cs['tokens_out']} tokens")
            per.setdefault(name, (comp, []))[1].append(
                cs["payload_bytes_up"] / cs["frames_up"])
        for name, (comp, vals) in per.items():
            measured = float(np.mean(vals))
            analytic = comp.fwd_bits(cfg.d_model) / 8
            # each wire section rounds up to whole bytes; at most two
            check(0 <= measured - analytic < 2,
                  f"{name}: measured {measured} B vs analytic {analytic} B")
            print(f"wire {name}: {measured:.1f} B/token measured payload vs "
                  f"{analytic:.1f} B Table-2 analytic "
                  f"({cfg.d_model * 4} B uncompressed)", flush=True)


def check_fused_step(cfg, params, log: CompileLog) -> None:
    """The compiled fused decode+step of each payload meta holds Mosaic
    kernels: the chip runs the Pallas decode, not an XLA stand-in."""
    import jax
    import jax.numpy as jnp
    from repro.core import compressors
    from repro.core.payload import Payload
    from repro.models import transformer
    from repro.models.config import Runtime
    from repro.runtime import steps
    from repro.runtime.server import jit_serving_steps

    rt = Runtime(mesh=None, training=False)
    cap, bucket, d = N_CLIENTS, N_CLIENTS, cfg.d_model
    _, fused = jit_serving_steps(
        steps.make_arena_top_step(cfg, rt, cfg.split.cut_layer),
        dtype=cfg.adtype())
    one = jax.eval_shape(lambda: transformer.init_cache(
        None, cfg, rt, 1, PROMPT_LEN + GEN))
    arena = jax.tree.map(lambda a: jax.ShapeDtypeStruct((cap,) + a.shape,
                                                        a.dtype), one)
    xbuf = jax.ShapeDtypeStruct((cap + 1, 1, 1, d), cfg.adtype())
    x = jax.ShapeDtypeStruct((1, 1, d), cfg.adtype())
    mark = log.mark()
    for spec in MIX:
        comp = compressors.make_compressor(spec)
        p = jax.eval_shape(lambda v: comp.encode(v), x)
        stacked = Payload(meta=p.meta, **{
            n: jax.ShapeDtypeStruct((bucket,) + a.shape, a.dtype)
            for n, a in p.wire_leaves()})
        text = fused.lower(
            params, xbuf, stacked, jax.ShapeDtypeStruct((bucket,), jnp.int32),
            arena, jax.ShapeDtypeStruct((cap,), bool)).compile().as_text()
        n = text.count("tpu_custom_call")
        check(n > 0, f"fused step {p.meta.kind}: no tpu_custom_call")
        print(f"fused step {p.meta.kind}: compiled program holds {n} "
              f"tpu_custom_call", flush=True)
    print(f"compile: fused-step checks {log.since(mark)}", flush=True)


def four_chips(cfg, params, seed: int, log: CompileLog) -> None:
    """The sharded arena on a (pod 2, data 1, model 2) mesh against the
    one-device arena: a direct step, then a contended serving run."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_serving_mesh
    from repro.models import transformer
    from repro.models.config import Runtime
    from repro.runtime import engine, steps

    mesh = make_serving_mesh(4, model=2, pod=2)
    print(f"mesh: {dict(mesh.shape)}", flush=True)
    rt = Runtime(mesh=None, training=False)
    cut, cap = cfg.split.cut_layer, 8
    rep = jax.device_put(params, NamedSharding(mesh, P()))

    # one direct arena step, sharded vs one device
    cache0 = jax.tree.map(lambda a: jnp.stack([a] * cap),
                          transformer.init_cache(params, cfg, rt, 1, 8))
    xbuf = jax.random.normal(jax.random.key(seed + 3),
                             (cap + 1, 1, 1, cfg.d_model), cfg.adtype())
    active = jnp.asarray([True, False] * (cap // 2))
    mark = log.mark()
    ref_tok, ref_cache = jax.jit(steps.make_arena_top_step(cfg, rt, cut))(
        params, xbuf, cache0, active)
    # slot s's activation and token live at its ingestion-pod row
    # (SlotArena.wire_row): the direct drive presents the same layout
    block = cap // 2
    perm = np.asarray([((s // block - 1) % 2) * block + s % block
                       for s in range(cap)])
    xw = np.asarray(xbuf).copy()
    xw[perm] = np.asarray(xbuf)[:cap]
    tok, new = jax.jit(steps.make_arena_top_step(cfg, rt, cut, mesh=mesh))(
        rep, jnp.asarray(xw), cache0, active)
    print(f"compile: direct steps {log.since(mark)}", flush=True)
    live = np.asarray(active)
    agree = np.asarray(ref_tok)[live] == np.asarray(tok)[perm][live]
    print(f"direct step: tokens agree on {int(agree.sum())}/{agree.size} "
          f"active rows", flush=True)
    for (path, r), n, o in zip(jax.tree_util.tree_leaves_with_path(ref_cache),
                               jax.tree.leaves(new), jax.tree.leaves(cache0)):
        name = jax.tree_util.keystr(path)
        r, n, o = (np.asarray(a, np.float32) for a in (r, n, o))
        check(np.array_equal(n[~live], o[~live]),
              f"{name}: inactive rows moved")
        err = float(np.abs(n - r).max())
        bound = KV_RTOL * float(np.abs(r).max() or 1.0)
        check(err <= bound, f"{name}: max |diff| {err} > {bound}")
        print(f"direct step {name}: max |diff| {err:.3g} (bound {bound:.3g},"
              f" {int((n != r).sum())}/{r.size} elements differ); "
              f"inactive rows bit-identical", flush=True)

    # contended serving: fewer slots than sessions -> LRU evict/readmit
    kw = dict(n_clients=N_CLIENTS, prompt_len=PROMPT_LEN, gen=GEN,
              max_batch=4, compressor_mix=[f"randtopk:k={K}"], seed=seed)
    mark = log.mark()
    ref = engine.run_streaming(cfg, params=params, **kw)
    got = engine.run_streaming(cfg, params=params, mesh=mesh, capacity=4,
                               **kw)
    print(f"compile: serving warm-ups {log.since(mark)}", flush=True)
    snap = got["metrics"]
    ev = snap["slot_evictions_total"]["series"][0]["value"]
    re_ = snap["slot_readmissions_total"]["series"][0]["value"]
    check(ev >= 1, f"no evictions with {N_CLIENTS} sessions over 4 slots")
    check(got["tokens"].shape == ref["tokens"].shape, "token shape")
    same = got["tokens"] == ref["tokens"]
    print(f"sharded serve: {got['tokens'].size} tokens, {int(ev)} evictions,"
          f" {int(re_)} readmissions; tokens agree with the one-device arena"
          f" on {int(same.sum())}/{same.size} ({int(same.all(-1).sum())}/"
          f"{len(same)} sessions whole)", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-arena path on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from repro.launch import compile_cache

    cache_dir = compile_cache.enable()
    log = CompileLog()
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}", flush=True)
    cfg = chip_share_config()
    params = init_params(cfg, args.seed)
    if args.four_chips:
        four_chips(cfg, params, args.seed, log)
    else:
        check_kernels(cfg, args.seed)
        serve(cfg, params, args.seed, log)
        check_fused_step(cfg, params, log)
    stats = dev.memory_stats() or {}
    print(f"memory: peak {stats.get('peak_bytes_in_use', 0) / 1e9:.2f} GB "
          f"of {stats.get('bytes_limit', 0) / 1e9:.2f} GB on device 0; "
          f"compile in all: {log.since((0.0, 0, 0, 0))}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
