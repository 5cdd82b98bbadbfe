"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
        --steps 200 --batch 8 --seq 128 --split randtopk --k 16

Runs a real training loop (synthetic pipeline, AdamW, checkpointing every
--ckpt-every steps) on whatever devices exist; with --mesh d,m it builds a
(data, model) mesh over the host devices.
"""
from __future__ import annotations

import argparse

import jax

import repro.configs as configs
from repro.checkpoint import latest_step, restore, save
from repro.data.pipeline import TokenPipeline
from repro.launch import compile_cache
from repro.launch.steps import make_eval_step, make_train_step
from repro.models import transformer
from repro.models.common import count_params
from repro.models.config import Runtime, SplitConfig
from repro.optim import adamw_init
from repro.testing.clock import Clock, SYSTEM_CLOCK


def main(argv=None, *, clock: Clock = SYSTEM_CLOCK):
    """CLI entry; `clock` is the injectable time source every elapsed-time
    print reads (`testing.clock`) — wall time by default, a `VirtualClock`
    in tests so logged timings are deterministic instead of machine noise."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--split", default=None)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--alpha", type=float, default=0.1)
    ap.add_argument("--cut", type=int, default=0)
    ap.add_argument("--selection-backend", default=None,
                    choices=["auto", "xla", "pallas"],
                    help="top-k selection backend (default: pallas on TPU, "
                         "xla elsewhere)")
    ap.add_argument("--mesh", default=None, help="e.g. 2,4 for (data,model)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = configs.get(args.arch, smoke=args.smoke)
    if args.split:
        cut = args.cut or max(1, cfg.n_layers // 2)
        if cfg.family == "vlm":
            g = cfg.cross_attn_every
            cut = max(g, cut // g * g)
        cfg = cfg.with_(split=SplitConfig(cut_layer=cut,
                                          compressor=args.split, k=args.k,
                                          alpha=args.alpha,
                                          backend=args.selection_backend))
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_mesh

        shape = tuple(int(x) for x in args.mesh.split(","))
        mesh = make_mesh(shape, ("data", "model")[: len(shape)])
    rt = Runtime(mesh=mesh, training=True)

    params = transformer.init_model(jax.random.key(0), cfg)
    opt = adamw_init(params)
    print(f"arch={cfg.name} params={count_params(params):,} "
          f"devices={jax.device_count()} split={cfg.split}")
    if cfg.split:
        from repro.split import protocol

        analytic = protocol.wire_bytes_per_step(cfg, args.batch, args.seq,
                                                training=True)
        measured = protocol.measured_payload_bytes(cfg, args.batch, args.seq,
                                                   training=False,
                                                   key=jax.random.key(3))
        print(f"cut-layer wire/step: {analytic:.0f} B analytic (fwd+bwd), "
              f"{measured} B measured fwd payload "
              f"(dense fwd would be {args.batch*args.seq*cfg.d_model*4} B)")

    start = 0
    if args.ckpt_dir:
        last = latest_step(args.ckpt_dir)
        if last >= 0:
            params = restore(args.ckpt_dir, last, params)
            opt = restore(args.ckpt_dir + "/opt", last, opt)
            start = last
            print(f"restored step {last}")

    pipe = TokenPipeline(cfg, args.batch, args.seq, rt=rt)
    step_fn = jax.jit(make_train_step(cfg, rt, lr=args.lr),
                      donate_argnums=(0, 1))
    t0 = clock.monotonic()
    for step in range(start, args.steps):
        batch = pipe.next_batch(step)
        key = jax.random.fold_in(jax.random.key(1), step)
        params, opt, metrics = step_fn(params, opt, batch, key)
        if step % args.log_every == 0 or step == args.steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            print(f"step {step:5d} loss={m['loss']:.4f} ce={m['ce']:.4f} "
                  f"gnorm={m['grad_norm']:.2f} "
                  f"({(clock.monotonic()-t0):.1f}s)")
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            save(args.ckpt_dir, step + 1, params)
            save(args.ckpt_dir + "/opt", step + 1, opt)
    return params


if __name__ == "__main__":
    main()
