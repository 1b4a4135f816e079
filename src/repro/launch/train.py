"""End-to-end LM training driver.

Runs any registered architecture (``--arch``) at any scale preset
(``--preset tiny|small|full``) on synthetic token streams, with the full
production substrate engaged: sharded data pipeline, AdamW + chunked
xent + remat + optional microbatching, async fault-tolerant
checkpointing (restore-on-start), straggler monitoring, and optional
host-device meshes for CPU bring-up.

    PYTHONPATH=src python -m repro.launch.train \
        --arch smollm-135m --preset tiny --steps 200

On real TPU pods the same driver runs with the production mesh
(``--mesh production`` / ``--multi-pod``); nothing in the loop is
host-count-specific (the data pipeline feeds per-host shards).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--preset", default="tiny",
                    choices=["tiny", "small", "full"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default="none",
                    help="'none' | 'RxC' host mesh | 'production'")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N host devices (set BEFORE jax import)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}")

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.checkpoint import Checkpointer
    from repro.compile_cache import enable_compile_cache
    from repro.configs import get_config
    from repro.data import ShardedBatcher, make_lm_tokens
    from repro.launch.mesh import make_host_mesh, make_production_mesh
    from repro.launch import specs as sp
    from repro.models import build_model
    from repro.runtime import StepTimeMonitor
    from repro.sharding import ShardingCtx, param_specs
    from repro.training import (AdamWConfig, init_state, make_train_step)

    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.preset == "tiny":
        cfg = dataclasses.replace(
            cfg, n_layers=cfg.layer_period * 2, d_model=128, n_heads=4,
            n_kv_heads=2 if cfg.n_kv_heads < cfg.n_heads else 4, head_dim=32,
            d_ff=256 if cfg.d_ff else 0, vocab=2048,
            **({"n_experts": 4, "top_k": 2, "moe_d_ff": 64}
               if cfg.n_experts else {}),
            **({"n_enc_layers": 2, "enc_seq": 64} if cfg.enc_dec else {}),
            **({"mrope_sections": (4, 6, 6)} if cfg.mrope else {}),
            **({"kv_lora_rank": 64, "q_lora_rank": 96, "qk_rope_dim": 16,
                "qk_nope_dim": 32, "v_head_dim": 32} if cfg.mla else {}))
    elif args.preset == "small":
        cfg = dataclasses.replace(cfg, n_layers=cfg.layer_period * 2)

    # --- mesh / ctx
    mesh = None
    if args.mesh == "production":
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    elif args.mesh != "none":
        r, c = (int(x) for x in args.mesh.split("x"))
        mesh = make_host_mesh((r, c))
    ctx = (sp.make_ctx(mesh) if mesh is not None else ShardingCtx())

    model = build_model(cfg, ctx, q_chunk=min(1024, args.seq),
                        kv_chunk=min(1024, args.seq))
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(10, args.steps // 20),
                          total_steps=args.steps)
    step_fn = make_train_step(model, opt_cfg, loss_chunk=min(512, args.seq),
                              microbatches=args.microbatches)

    # --- init (sharded when on-mesh)
    key = jax.random.PRNGKey(args.seed)
    if mesh is not None:
        pspecs = param_specs(ctx, jax.eval_shape(model.init, key))
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
        params = jax.jit(model.init, out_shardings=shardings)(key)
        state = {"params": params, "opt": init_state(params)}
        step_fn = jax.jit(step_fn, donate_argnums=(0,))
    else:
        params = model.init(key)
        state = {"params": params, "opt": init_state(params)}
        step_fn = jax.jit(step_fn, donate_argnums=(0,))

    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    print(f"arch={args.arch} preset={args.preset} params={n_params:,} "
          f"devices={len(jax.devices())}")

    # --- checkpointing / restore
    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    if ckpt is not None and ckpt.latest_step() is not None:
        state = ckpt.restore(state)
        start_step = ckpt.latest_step()
        print(f"restored checkpoint at step {start_step}")

    # --- data
    stream = make_lm_tokens(
        max(args.steps, 200) * args.batch * args.seq + args.seq + 1,
        cfg.vocab, seed=args.seed)
    batcher = ShardedBatcher(stream, args.batch, args.seq, mesh=mesh,
                             batch_axes=ctx.dp_axes if mesh else ("data",))
    batcher.seek(start_step)
    monitor = StepTimeMonitor()

    it = iter(batcher)
    losses = []
    for step in range(start_step, args.steps):
        tokens, labels = next(it)
        batch = {"tokens": tokens, "labels": labels}
        if cfg.enc_dec:
            frames = jnp.zeros((args.batch, cfg.enc_seq, cfg.d_model),
                               jnp.float32)
            batch["frames"] = frames
        t0 = time.time()
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        dt = time.time() - t0
        if monitor.observe(step, dt):
            print(f"  [straggler] step {step} took {dt:.2f}s "
                  f"(ema {monitor.ema:.2f}s)")
        losses.append(loss)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} {dt:.2f}s")
        if ckpt is not None and (step + 1) % args.ckpt_every == 0:
            ckpt.save(step + 1, state)
    if ckpt is not None:
        ckpt.save(args.steps, state, blocking=True)
    print(json.dumps({"first_loss": losses[0], "last_loss": losses[-1],
                      "monitor": monitor.summary()}))


if __name__ == "__main__":
    main()
