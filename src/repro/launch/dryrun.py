import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production mesh and extract the roofline terms.

A CPU emulation by design. The lines above run before ANY other import
— jax locks the device count at first init, and the dry-run (and only
the dry-run) needs 512 placeholder host devices to build the 16x16 /
2x16x16 meshes. It is pinned to the CPU backend so that, on a machine
with a TPU, it (and the sweep/hill-climb children that run it) never
takes the chip or blocks on the TPU runtime's one-process lock.

Usage:
  python -m repro.launch.dryrun --arch yi-34b --shape train_4k \
      [--multi-pod] [--out runs/dryrun] [--opt k=v ...]

Emits one JSON per cell with cost/memory analysis + per-collective bytes
parsed from the optimized HLO. benchmarks/roofline.py turns these into
the EXPERIMENTS.md tables.
"""
import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402
from repro.configs import SHAPES, applicable, get_config  # noqa: E402
from repro.launch import hlo_cost  # noqa: E402
from repro.launch import specs as sp  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serving import make_decode_step, make_prefill_step  # noqa: E402
from repro.training import AdamWConfig, make_train_step  # noqa: E402

# TPU v5e-class hardware constants (per chip) for §Roofline
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link

_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
_LAST_CACHE_INFO = None
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in the (per-partition)
    optimized HLO. Returns {op_kind: bytes, 'total': bytes}."""
    out = {k: 0 for k in _COLL_OPS}
    n_ops = 0
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"%?[\w.\-]+ = .*? (" + "|".join(_COLL_OPS) +
                     r")(?:-start|-done)?\(", ls)
        if not m:
            continue
        kind = m.group(1)
        if "-done(" in ls:       # async pair: count the -start only
            continue
        n_ops += 1
        # operand types appear inside the call parens
        args = ls.split("(", 1)[1]
        b = sum(_shape_bytes(dt, dims)
                for dt, dims in _SHAPE_RE.findall(args.split("),")[0] + ")")
                if dt in _DTYPE_BYTES)
        out[kind] += b
    out["total"] = sum(out[k] for k in _COLL_OPS)
    out["n_ops"] = n_ops
    return out


def build_cell(arch: str, shape_name: str, multi_pod: bool, opts: dict):
    """Returns (mesh, fn, example_args, in_shardings, out_shardings,
    donate)."""
    if arch.startswith("pemsvm"):
        from repro.launch.svm_cell import build_svm_cell
        mesh = make_production_mesh(multi_pod=multi_pod)
        jitted, args, in_sh = build_svm_cell(arch, shape_name, mesh, opts)
        return mesh, jitted, args, in_sh, None, ()
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = sp.make_ctx(mesh, shape)
    model = build_model(
        cfg, ctx,
        q_chunk=int(opts.get("q_chunk", 1024)),
        kv_chunk=int(opts.get("kv_chunk", 1024)),
        ssm_chunk=int(opts.get("ssm_chunk", 256)),
        skip_masked_blocks=bool(int(opts.get("skip_masked_blocks", 0))),
        remat_policy=opts.get("remat_policy", "nothing"),
        seq_parallel_attn=bool(int(opts.get("seq_attn", 0))))

    if shape.kind == "train":
        pstructs, pspecs = sp.param_struct_specs(cfg, ctx)
        ostructs, ospecs = sp.opt_state_specs(pstructs, pspecs)
        bstructs, bspecs = sp.batch_specs(cfg, shape, ctx, with_labels=True)
        state_structs = {"params": pstructs, "opt": ostructs}
        state_specs = {"params": pspecs, "opt": ospecs}
        step = make_train_step(
            model, AdamWConfig(),
            remat=bool(int(opts.get("remat", 1))),
            loss_chunk=int(opts.get("loss_chunk", 512)),
            microbatches=int(opts.get("microbatches", 1)))
        return (mesh, step, (state_structs, bstructs),
                (state_specs, bspecs), (state_specs, P()), ())

    # Serving param layout levers (§Perf): FSDP is a training pattern —
    # without optimizer state, weights can replicate over 'data'
    # (serve_fsdp=0) and even over 'model' (serve_tp=0, small models).
    import dataclasses as _dc
    pctx = ctx
    if not int(opts.get("serve_fsdp", 1)):
        pctx = _dc.replace(pctx, fsdp_axis=None)
    if not int(opts.get("serve_tp", 1)):
        pctx = _dc.replace(pctx, tp_axis=None)
    pstructs, pspecs = sp.param_struct_specs(cfg, pctx, dtype=cfg.dtype)
    if shape.kind == "prefill":
        bstructs, bspecs = sp.batch_specs(cfg, shape, ctx, with_labels=False)
        cstructs, cspecs = sp.cache_specs(cfg, shape, ctx)
        del cstructs
        step = make_prefill_step(model, cache_len=shape.seq_len)
        tok_spec = ctx.spec((shape.global_batch,), ctx.dp_axes)
        return (mesh, step, (pstructs, bstructs), (pspecs, bspecs),
                (tok_spec, cspecs), ())

    # decode: one new token against a seq_len cache
    B = shape.global_batch
    cstructs, cspecs = sp.cache_specs(cfg, shape, ctx)
    global _LAST_CACHE_INFO
    _LAST_CACHE_INFO = (cstructs, cspecs, ctx)
    tok_struct = sp.sds((B, 1), jnp.int32)
    pos_struct = sp.sds((), jnp.int32)
    tok_spec = ctx.spec((B, 1), ctx.dp_axes, None)
    step = make_decode_step(model)
    lg_spec = ctx.spec((B, cfg.vocab), ctx.dp_axes,
                       ctx.tp_axis if cfg.vocab % ctx.axis_size(
                           ctx.tp_axis) == 0 else None)
    return (mesh, step, (pstructs, tok_struct, pos_struct, cstructs),
            (pspecs, tok_spec, P(), cspecs),
            (ctx.spec((B,), ctx.dp_axes), lg_spec, cspecs),
            (3,))  # donate the cache


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             opts: dict | None = None, *, keep_hlo: bool = False) -> dict:
    opts = opts or {}
    is_svm = arch.startswith("pemsvm")
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = 512 if multi_pod else 256
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "chips": chips, "opts": opts, "ok": False}

    if not is_svm:
        cfg = get_config(arch)
        shape = SHAPES[shape_name]
        runs, reason = applicable(cfg, shape)
        if not runs:
            rec.update(skipped=True, reason=reason, ok=True)
            return rec

    t0 = time.time()
    try:
        mesh, fn, args, in_sh, out_sh, donate = build_cell(
            arch, shape_name, multi_pod, opts)
        with jax.set_mesh(mesh):
            if is_svm:     # svm cells arrive pre-wrapped by shard_map
                jitted = fn
            else:
                jitted = jax.jit(fn, in_shardings=in_sh,
                                 out_shardings=out_sh,
                                 donate_argnums=donate)
            lowered = jitted.lower(*args)
            rec["lower_s"] = round(time.time() - t0, 1)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 1)

        hlo = compiled.as_text()
        cost = hlo_cost.analyze(hlo)
        rec["flops_per_device"] = cost["flops"]
        rec["bytes_per_device"] = cost["hbm_bytes"]
        # XLA's own (loop-bodies-once) numbers, for reference
        ca = compiled.cost_analysis() or {}
        rec["xla_flops_once"] = float(ca.get("flops", 0.0))
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "generated_code_bytes": int(
                getattr(ma, "generated_code_size_in_bytes", 0)),
        }
        per_dev_total = (rec["memory"]["argument_bytes"]
                         + rec["memory"]["output_bytes"]
                         + rec["memory"]["temp_bytes"])
        rec["memory"]["total_bytes"] = per_dev_total
        # Buffer donation is NOT implemented on the CPU backend, so the
        # donated KV/state cache of decode cells is double-counted here
        # (once as a non-aliased output, once as the DUS copy in temp).
        # On the TPU target the cache updates in place; subtract both
        # phantom copies for the fits-HBM verdict and record the
        # adjustment explicitly.
        if _LAST_CACHE_INFO is not None and donate:
            cstructs_, cspecs_, ctx_ = _LAST_CACHE_INFO
            cache_bytes = 0
            for leaf, spec_ in zip(jax.tree.leaves(cstructs_),
                                   jax.tree.leaves(
                                       cspecs_, is_leaf=lambda x: hasattr(
                                           x, 'spec') or x is None)):
                n_shards = 1
                spec_obj = getattr(spec_, 'spec', spec_)
                if spec_obj is not None:
                    for entry in spec_obj:
                        if entry is None:
                            continue
                        axes_ = entry if isinstance(entry, tuple) else (entry,)
                        for a in axes_:
                            n_shards *= mesh.shape[a]
                cache_bytes += (leaf.size * leaf.dtype.itemsize) // n_shards
            rec["memory"]["donated_cache_bytes_per_device"] = cache_bytes
            # Three phantom copies on CPU: (a) non-aliased output buffer,
            # (b) the scan's loop-state double buffer, (c) the DUS copy —
            # all alias in place on TPU for donated buffers threaded
            # through the layer scan. One live cache stays (in args).
            adj = per_dev_total - 3 * cache_bytes
            rec["memory"]["total_bytes_tpu_donated"] = adj
            rec["memory"]["fits_16gb_hbm"] = bool(adj < 16e9)
        else:
            rec["memory"]["fits_16gb_hbm"] = bool(per_dev_total < 16e9)

        rec["collectives_per_device"] = {
            "total": cost["collective_bytes"],
            "n_ops": cost["collective_ops"],
            **cost["collectives_by_kind"]}
        if keep_hlo:
            rec["hlo_path"] = f"/tmp/hlo_{arch}_{shape_name}_{mesh_name}.txt"
            with open(rec["hlo_path"], "w") as f:
                f.write(hlo)

        # roofline terms (global FLOPs = per-device x chips)
        coll = rec["collectives_per_device"]["total"]
        rec["terms"] = {
            "compute_s": rec["flops_per_device"] / PEAK_FLOPS,
            "memory_s": rec["bytes_per_device"] / HBM_BW,
            "collective_s": coll / ICI_BW,
        }
        rec["terms"]["dominant"] = max(rec["terms"],
                                       key=lambda k: rec["terms"][k])
        # model flops: 6ND for LM cells; N*K^2 + 3NK (+K^3/3 solve) per
        # SVM iteration (paper Sec 4.3: the Sigma^p statistic dominates)
        if is_svm:
            from repro.launch.svm_cell import SVM_SHAPES
            sp_ = SVM_SHAPES[shape_name]
            m_cls = sp_.get("M", 1) if sp_["task"] == "MLT" else 1
            nd = m_cls * (2 * sp_["N"] * sp_["K"] ** 2
                          + 6 * sp_["N"] * sp_["K"] + sp_["K"] ** 3 / 3)
        else:
            tokens = shape.global_batch * (
                shape.seq_len if shape.kind != "decode" else 1)
            nd = 6 * cfg.active_params() * tokens
            if shape.kind in ("prefill", "decode"):
                nd = nd / 3  # 2ND for inference
        rec["model_flops"] = float(nd)
        global_flops = rec["flops_per_device"] * chips
        rec["useful_flops_ratio"] = (rec["model_flops"] / global_flops
                                     if global_flops else 0.0)
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    from repro.launch.svm_cell import SVM_SHAPES
    ap.add_argument("--shape", required=True,
                    choices=sorted(SHAPES) + sorted(SVM_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--opt", action="append", default=[],
                    help="k=v model/step options (q_chunk, remat, ...)")
    args = ap.parse_args()
    opts = dict(kv.split("=", 1) for kv in args.opt)

    rec = run_cell(args.arch, args.shape, args.multi_pod, opts,
                   keep_hlo=args.keep_hlo)
    os.makedirs(args.out, exist_ok=True)
    tag = "multi" if args.multi_pod else "single"
    suffix = ("_" + "_".join(f"{k}-{v}" for k, v in sorted(opts.items()))
              if opts else "")
    path = os.path.join(args.out,
                        f"{args.arch}_{args.shape}_{tag}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
    print(json.dumps({k: v for k, v in rec.items()
                      if k not in ("traceback",)}, indent=2))
    if not rec["ok"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
