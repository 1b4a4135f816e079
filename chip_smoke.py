#!/usr/bin/env python3
"""Bring-up smoke of the PEMSVM fit and serve path on TPU.

    python chip_smoke.py             # one chip: dna, year, mnist8m, Nystrom
    python chip_smoke.py --chips 4   # four chips: the dna fit on a (4,) data
                                     # mesh and a (2, 2) k-shard mesh, each
                                     # against the same fit on one chip

Every phase runs through the normal entry points (``PEMSVM.fit``,
``NystromSVM.fit``, ``export_servable``, ``ServeLoop``) on data made
from a seed by ``repro.data.synthetic`` at the paper's feature widths;
only the row count N is cut to what one chip holds. Each phase prints
one JSON line: compile seconds and steady seconds per iteration of the
jitted step (timed around ``block_until_ready``; a smoke reading, not a
benchmark), objective first/last, held-out score, its oracle
differences and ``peak_bytes_in_use``. Oracle checks and sanity gates
raise, so a phase that fails fails the run. The last line is the
device tag

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

Without a TPU the script exits non-zero before running anything. It
keeps JAX's persistent compile cache where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# One bf16 rounding of a product (2^-8 relative): the coarsest precision
# the TPU's default f32 matmul path uses. Every statistic term in the
# oracle check is bounded by it, relative to the sum of |terms|.
STAT_TOL = 2.0 ** -8
KERNEL_MARK = "tpu_custom_call"  # a Pallas kernel in compiled TPU HLO
ITERS = 8                     # fixed iterations per fit (tol = 0)
ORACLE_ROWS = 65_536          # held-out rows: score and the f64 oracle
# Training rows per phase: the paper's widths, N cut to one chip (dna in
# f32 fills about 4M rows of a v5e's 16 GB; the fit also holds a padded
# copy of X and the oracle fit a second one).
ROWS = {"dna": 1 << 20, "year": 1 << 18, "mnist8m": 1 << 19,
        "nystrom": 1 << 18}


def log(rec: dict, out: str | None) -> None:
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        rec["peak_bytes_in_use"] = stats["peak_bytes_in_use"]
    line = json.dumps(rec, default=float)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def holdout(X, y):
    n = ORACLE_ROWS
    return X[:-n], y[:-n], X[-n:], y[-n:]


def with_bias(X):
    return np.concatenate([X, np.ones((len(X), 1), np.float32)], 1)


def fixed_iters(cfg, **kw):
    return dataclasses.replace(cfg, min_iters=ITERS, max_iters=ITERS,
                               tol=0.0, **kw)


def dna_problem():
    """dna (Table 5) at N rows: config, train and held-out split. lam is
    scaled with N so the prior weighs the data as it does at the
    paper's 25.6M rows (the cut benchmarks/fig2_cores.py makes)."""
    from repro.configs.svm_paper import dna_lin_em_cls
    from repro.data import make_dna_like

    N = ROWS["dna"]
    base = dna_lin_em_cls()
    cfg = fixed_iters(base, lam=base.lam * N / 25_600_000)
    return (cfg, *holdout(*make_dna_like(N + ORACLE_ROWS, 800)))


def time_step(svm, X, y) -> dict:
    """Compile the fit's one-iteration step for (X, y) and time it.

    The step is the solver's own (``_build_step_fn``) on the solver's
    own device placement (``_prepare``); its compiled HLO must hold the
    Pallas kernel (``tpu_custom_call``), i.e. the fused statistic ran on
    the chip and not its jnp fallback."""
    from repro.core import solver

    cfg = svm.config
    data, prior, state = svm._prepare(np.asarray(X, np.float32), y)
    step = solver._build_step_fn(cfg, None, (), prior is not None)
    args = (data, prior) if prior is not None else (data,)
    t0 = time.perf_counter()
    compiled = jax.jit(step).lower(*args, state,
                                   jax.random.PRNGKey(0)).compile()
    compile_s = time.perf_counter() - t0
    if KERNEL_MARK not in compiled.as_text():
        raise AssertionError("compiled step has no Pallas kernel "
                             "(tpu_custom_call): the fused statistic "
                             "did not run on the chip")
    keys = jax.random.split(jax.random.PRNGKey(1), ITERS + 1)
    state, _ = compiled(*args, state, keys[0])          # warm-up
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for k in keys[1:]:
        state, aux = compiled(*args, state, k)
    jax.block_until_ready((state, aux))
    return {"compile_s": compile_s,
            "s_per_iter": (time.perf_counter() - t0) / ITERS}


def stat_oracle(X, rho, beta, w, epilogue, eps_ins=0.0, seed=None):
    """The fused statistic on the chip against float64 host arithmetic.

    Margins are checked against X @ w in f64; b and Sigma against f64
    sums built from the kernel's own augmentation outputs (so a row at
    the gamma clamp, whose weight is 1/eps, does not turn a margin
    rounding into an O(1) statistic difference). Each error is relative
    to the sum of the |terms| it accumulates."""
    from repro.kernels import ops

    out = ops.fused_stats(jnp.asarray(X), jnp.asarray(rho),
                          jnp.asarray(beta), jnp.asarray(w), None, None,
                          epilogue=epilogue, eps_ins=eps_ins, seed=seed)
    margin, *aug, b, S = (np.asarray(a, np.float64) for a in out)
    X64, w64 = X.astype(np.float64), np.asarray(w, np.float64)
    r64 = np.asarray(rho, np.float64)
    if epilogue.endswith("svr"):
        gamma, omega = aug
        weight = 1.0 / gamma + 1.0 / omega
        coef = (r64 - eps_ins) / gamma + (r64 + eps_ins) / omega
    else:
        (gamma,) = aug
        weight = 1.0 / gamma
        coef = r64 / gamma + np.asarray(beta, np.float64)
    A = np.abs(X64)
    err = {
        "margin": np.max(np.abs(margin - X64 @ w64) / (A @ np.abs(w64)
                                                      + 1e-30)),
        "b": np.max(np.abs(b - X64.T @ coef)
                    / (A.T @ np.abs(coef) + 1e-30)),
        "S": np.max(np.abs(S - X64.T @ (X64 * weight[:, None]))
                    / (A.T @ (A * np.abs(weight)[:, None]) + 1e-30)),
    }
    for k, v in err.items():
        if not v <= STAT_TOL:
            raise AssertionError(f"{epilogue} statistic {k}: relative "
                                 f"error {v:.3g} > {STAT_TOL:.3g}")
    return err


def fit_phase(name, svm, X, y, Xte, yte, min_score) -> tuple[dict, object]:
    """Fit through the entry point; gate finite weights and the held-out
    score (``svm.score``: accuracy, or -RMSE for SVR)."""
    t0 = time.perf_counter()
    res = svm.fit(X, y)
    fit_s = time.perf_counter() - t0
    if not np.all(np.isfinite(res.weights)):
        raise AssertionError(f"{name}: non-finite weights")
    rec = {"phase": name, "N": len(X), "K": X.shape[1],
           "n_iters": res.n_iters, "fit_s_cold": fit_s,
           "obj_first": res.objective[0], "obj_last": res.objective[-1],
           "score": svm.score(Xte, yte)}
    if not rec["score"] >= min_score:
        raise AssertionError(f"{name}: held-out score {rec['score']:.3f}"
                             f" < {min_score}")
    return rec, res


def rel_diff(res, ref) -> dict:
    w, w0 = np.asarray(res.weights), np.asarray(ref.weights)
    d = np.abs(w[:w0.size] - w0)
    return {"w_max_abs": d.max(), "w_rel": d.max() / np.abs(w0).max(),
            "obj_last_rel": abs(res.objective[-1] - ref.objective[-1])
            / abs(ref.objective[-1])}


def phase_dna(out):
    from repro.core import PEMSVM

    cfg, X, y, Xte, yte = dna_problem()
    svm = PEMSVM(cfg)
    rec, res = fit_phase("dna", svm, X, y, Xte, yte, 0.75)
    rec.update(time_step(svm, X, y))
    rec["stat_err"] = stat_oracle(with_bias(Xte), yte, yte, res.weights,
                                  "em_hinge")
    with jax.default_matmul_precision("highest"):
        ref = PEMSVM(dataclasses.replace(cfg, backend="ref")).fit(X, y)
    rec["vs_ref_highest"] = rel_diff(res, ref)
    log(rec, out)


def phase_year(out):
    from repro.configs.svm_paper import year_lin_em_svr
    from repro.core import PEMSVM
    from repro.data import make_year_like

    X, y, Xte, yte = holdout(*make_year_like(ROWS["year"] + ORACLE_ROWS,
                                             90))
    cfg = fixed_iters(year_lin_em_svr())
    svm = PEMSVM(cfg)
    # RMSE <= 0.9 on unit-variance targets (predicting 0 gives 1.0)
    rec, res = fit_phase("year", svm, X, y, Xte, yte, -0.9)
    rec.update(time_step(svm, X, y))
    rec["stat_err"] = stat_oracle(with_bias(Xte), yte, np.zeros_like(yte),
                                  res.weights, "em_svr",
                                  eps_ins=cfg.eps_ins)
    log(rec, out)


def phase_mnist8m(out):
    from repro.configs.svm_paper import mnist8m_lin_mc_mlt
    from repro.core import PEMSVM
    from repro.data import make_mnist8m_like
    from repro.kernels import rng

    X, y, Xte, yte = holdout(*make_mnist8m_like(
        ROWS["mnist8m"] + ORACLE_ROWS, 784))
    # burnin cut from 10 so 4 of the 8 sweeps enter the posterior mean
    cfg = fixed_iters(mnist8m_lin_mc_mlt(), rng="fused", burnin=4)
    svm = PEMSVM(cfg)
    rec, fused = fit_phase("mnist8m[fused]", svm, X, y, Xte, yte, 0.5)
    rec.update(time_step(svm, X, y))
    ybin = np.where(yte == 0, 1.0, -1.0).astype(np.float32)
    rec["stat_err"] = stat_oracle(with_bias(Xte), ybin, ybin,
                                  fused.weights[0], "mc_hinge",
                                  seed=rng.pack_seed(jax.random.PRNGKey(3)))
    log(rec, out)
    svm = PEMSVM(dataclasses.replace(cfg, rng="fused_predraw"))
    rec, pre = fit_phase("mnist8m[fused_predraw]", svm, X, y, Xte, yte, 0.5)
    rec["fused_vs_predraw"] = dict(
        rel_diff(fused, pre),
        bitwise=bool(np.array_equal(fused.weights, pre.weights)))
    log(rec, out)


def phase_nystrom(out):
    from repro.configs.svm_paper import news20_krn_em_cls
    from repro.core import NystromSVM
    from repro.data import make_alpha_like
    from repro.serving import ServeLoop, WeightPager

    X, y, Xte, yte = holdout(*make_alpha_like(
        ROWS["nystrom"] + ORACLE_ROWS, 500))
    # Unit-norm rows, as news20's tf-idf rows are, so the config's
    # sigma = 1 RBF sees the distances it was chosen for.
    X /= np.linalg.norm(X, axis=1, keepdims=True)
    Xte /= np.linalg.norm(Xte, axis=1, keepdims=True)
    cfg = fixed_iters(news20_krn_em_cls())
    nys = NystromSVM(cfg, n_landmarks=512)
    rec, res = fit_phase("nystrom", nys, X, y, Xte, yte, 0.65)
    rec.update(time_step(nys.svm, X, y))

    pager = WeightPager(max_resident=1)
    pager.register(nys.export_servable(name="alpha-krn"))
    loop = ServeLoop(pager, max_batch=1024).start()
    rng = np.random.default_rng(0)
    reqs = [(int(s), int(n)) for s, n in zip(
        rng.integers(0, len(Xte) - 1024, size=32),
        rng.integers(1, 1025, size=32))]
    try:
        futs = [loop.submit("alpha-krn", Xte[s:s + n]) for s, n in reqs]
        got = [f.result(timeout=300) for f in futs]
    finally:
        loop.stop()
    diff = max(np.max(np.abs(g[:, 0] - nys.decision_function(Xte[s:s + n])))
               for g, (s, n) in zip(got, reqs))

    # float64 host evaluation of the same model (its own landmarks,
    # K_mm^{-1/2} and weights): reported, not gated (PERF.md §7).
    Xh = Xte[:4096].astype(np.float64)
    lm = nys._landmarks.astype(np.float64)
    d2 = ((Xh * Xh).sum(1)[:, None] + (lm * lm).sum(1)[None, :]
          - 2.0 * Xh @ lm.T)
    phi = np.exp(-np.maximum(d2, 0.0) / (2.0 * cfg.sigma ** 2)) \
        @ nys._proj.astype(np.float64)
    w64 = np.asarray(res.weights, np.float64)
    host = phi @ w64[:-1] + w64[-1]
    rec["serve"] = {
        "requests": len(got), "rows": sum(n for _, n in reqs),
        "served_vs_decision_max_abs": diff,
        "latency": loop.latency_quantiles(),
        "decision_vs_f64_host_max_abs":
            np.max(np.abs(nys.decision_function(Xte[:4096]) - host)),
        "decision_max_abs": np.max(np.abs(host))}
    if not diff <= 1e-5 * max(1.0, np.max(np.abs(host))):
        raise AssertionError(f"served scores differ from "
                             f"decision_function by {diff:.3g}")
    log(rec, out)


def phase_mesh(out):
    """dna on four chips: a (4,) data mesh and a (2, 2) mesh whose model
    axis shards Sigma's columns, each against the same fit on one."""
    from jax.sharding import AxisType

    from repro.core import PEMSVM

    cfg, X, y, Xte, yte = dna_problem()
    rec0, one = fit_phase("dna[1 chip]", PEMSVM(cfg), X, y, Xte, yte, 0.75)
    log(rec0, out)
    meshes = {
        "dna[(4,) data]": (jax.make_mesh((4,), ("data",),
                                         axis_types=(AxisType.Auto,)),
                           cfg),
        # K = 801 with the bias; the model axis needs K % 2 == 0.
        "dna[(2,2) k-shard]": (
            jax.make_mesh((2, 2), ("data", "model"),
                          axis_types=(AxisType.Auto,) * 2),
            dataclasses.replace(cfg, k_shard_axis="model", pad_features=2)),
    }
    for name, (mesh, mcfg) in meshes.items():
        rec, res = fit_phase(name, PEMSVM(mcfg, mesh=mesh), X, y, Xte, yte,
                             0.75)
        cmp = rec["vs_one_chip"] = dict(
            rel_diff(res, one), score_diff=abs(rec["score"] - rec0["score"]))
        # The band is on the objective and the held-out accuracy, not
        # the weights: rows at the gamma clamp (weight 1/eps) let the EM
        # path carry a psum's f32 reassociation far along directions the
        # objective barely sees (w_rel ~1e-2 at obj_rel ~3e-5 on four
        # CPU devices).
        if not (cmp["obj_last_rel"] <= 1e-3 and cmp["score_diff"] <= 0.01):
            raise AssertionError(f"{name} disagrees with one chip: {cmp}")
        log(rec, out)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--out", default=None,
                    help="also append each phase's JSON line here")
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    log({"phase": "setup", "compile_cache": cache,
         "cache_files_at_start": sum(len(f) for *_, f in os.walk(cache))},
        args.out)
    t0 = time.perf_counter()
    phases = ((phase_mesh,) if args.chips == 4 else
              (phase_dna, phase_year, phase_mnist8m, phase_nystrom))
    for phase in phases:
        phase(args.out)
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t0:.1f} s", file=sys.stderr)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
