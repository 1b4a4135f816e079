"""Serving drivers.

LM mode (default) — prefill a batch of prompts, decode greedily:

    PYTHONPATH=src python -m repro.launch.serve \
        --arch smollm-135m --preset tiny --batch 4 --prompt-len 32 --steps 16

SVM mode — fit demo tenants, export ServableModels, page them through
a shared score cell, and drive the threaded continuous-batching loop:

    PYTHONPATH=src python -m repro.launch.serve --mode svm \
        --tenants 6 --requests 200 --family nystrom
"""
from __future__ import annotations

import argparse
import dataclasses
import time


def main_svm(args) -> None:
    import numpy as np

    from repro.core import PEMSVM, SVMConfig
    from repro.core.nystrom import NystromSVM
    from repro.serving import ServeLoop, WeightPager

    rng = np.random.default_rng(args.seed)
    n, d = 4_000, 32
    X = rng.normal(size=(n, d)).astype(np.float32)

    pager = WeightPager(max_resident=args.resident)
    oracles = {}
    for t in range(args.tenants):
        w = rng.normal(size=d)
        y = np.where(X @ w > 0, 1.0, -1.0).astype(np.float32)
        if args.family == "nystrom":
            model = NystromSVM(
                SVMConfig(formulation="KRN", sigma=3.0, lam=0.1,
                          max_iters=15, min_iters=5), n_landmarks=48)
        else:
            model = PEMSVM(SVMConfig(max_iters=15, min_iters=5))
        model.fit(X, y)
        name = f"tenant{t}"
        pager.register(model.export_servable(name=name))
        oracles[name] = model.decision_function(X[:256])

    loop = ServeLoop(pager).start()
    t0 = time.time()
    futs = []
    for i in range(args.requests):
        nr = int(rng.integers(1, 97))
        j = int(rng.integers(0, n - nr + 1))
        futs.append((f"tenant{i % args.tenants}",
                     loop.submit(f"tenant{i % args.tenants}", X[j:j + nr])))
    rows = sum(f.result(timeout=60).shape[0] for _, f in futs)
    dt = time.time() - t0
    loop.stop()

    q = loop.latency_quantiles()
    ok = all(
        np.array_equal(pager.scorer(name).score(X[:256])[:, 0], oracle)
        for name, oracle in oracles.items())
    print(f"served {loop.n_requests} requests / {rows} rows in {dt:.2f}s "
          f"({rows / dt:.0f} rows/s) over {loop.n_batches} batches")
    print(f"latency p50={q['p50_ms']:.2f}ms p99={q['p99_ms']:.2f}ms  "
          f"pager hits={pager.hits} misses={pager.misses} "
          f"evictions={pager.evictions} "
          f"resident={pager.resident_bytes}B")
    print(f"bitwise parity vs decision_function across all tenants: {ok}")
    if not ok:
        raise SystemExit(1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="lm", choices=["lm", "svm"])
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--preset", default="tiny", choices=["tiny", "full"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--temp", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--resident", type=int, default=4)
    ap.add_argument("--requests", type=int, default=100)
    ap.add_argument("--family", default="linear",
                    choices=["linear", "nystrom"])
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.mode == "svm":
        main_svm(args)
        return

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import generate

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

    model = build_model(cfg, q_chunk=min(512, args.prompt_len),
                        kv_chunk=min(512, args.prompt_len))
    params = model.init(jax.random.PRNGKey(args.seed))

    key = jax.random.PRNGKey(args.seed + 1)
    batch = {"tokens": jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab)}
    if cfg.enc_dec:
        batch["frames"] = jnp.zeros((args.batch, cfg.enc_seq, cfg.d_model))

    t0 = time.time()
    out = generate(model, params, batch,
                   steps=args.steps,
                   cache_len=args.prompt_len + args.steps,
                   temp=args.temp, seed=args.seed)
    dt = time.time() - t0
    print(f"generated {out.shape} tokens in {dt:.2f}s "
          f"({args.batch * args.steps / dt:.1f} tok/s)")
    print("first sequences:", out[:2].tolist())


if __name__ == "__main__":
    main()
