#!/usr/bin/env python
"""CPU 2-D (data x model) k_shard smoke for CI (mirrors the stream/krn/
mc smoke pattern): the column-windowed single-stream statistic on a
real multi-device mesh, gated on parity with the replicated path.

Forces 2 emulated CPU devices (the env var must be set before jax
initializes, hence at module top) and builds a (1, 2) (data, model)
mesh, so the windowed kernels run under real shard_map axis indices.

Gates:

  * EM-CLS k_shard whole-fit parity vs the single-device fit
    (<= 1e-3 rel — deterministic; the data axis has ONE shard, so the
    only fp channel is the windowed-matmul split);
  * MC-CLS chain identity: iteration one EXACT (the rowwise-keyed
    draws are layout-invariant), short-chain trace within the
    documented fp32 band;
  * k_shard x phi_spec (Nystrom) EM whole-fit parity <= 1e-4 — the
    composition this PR unlocks (was NotImplementedError);
  * SVMConfig.pad_features route: an indivisible width fits and
    predictions match the unpadded fit.
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=2"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> int:
    import jax
    import numpy as np

    from repro.core import PEMSVM, SVMConfig
    from repro.core.nystrom import NystromSVM

    mesh = jax.make_mesh((1, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rng = np.random.default_rng(0)
    N, K = 1024, 23                    # +bias -> 24, model axis 2 | 24
    w_true = rng.normal(size=K)
    X = rng.normal(size=(N, K)).astype(np.float32)
    y = np.where(X @ w_true + 0.3 * rng.normal(size=N) > 0, 1.0, -1.0)
    ok = True

    # --- gate 1: EM-CLS k_shard whole-fit parity ----------------------
    em = dict(max_iters=20, min_iters=20, eps=1e-2)
    r1 = PEMSVM(SVMConfig(**em)).fit(X, y)
    rk = PEMSVM(SVMConfig(k_shard_axis="model", **em), mesh=mesh,
                data_axes=("data",)).fit(X, y)
    rel = np.abs(rk.weights - r1.weights).max() / np.abs(r1.weights).max()
    print(f"EM-CLS k_shard rel err: {rel:.2e} (gate 1e-3)")
    ok &= rel < 1e-3

    # --- gate 2: MC-CLS chain identity --------------------------------
    mc = dict(algorithm="MC", max_iters=12, min_iters=12, eps=1e-2,
              burnin=6)
    m1 = PEMSVM(SVMConfig(**mc)).fit(X, y)
    mk = PEMSVM(SVMConfig(k_shard_axis="model", **mc), mesh=mesh,
                data_axes=("data",)).fit(X, y)
    tr = np.abs(np.array(mk.objective) - np.array(m1.objective)) / (
        np.abs(np.array(m1.objective)))
    print(f"MC-CLS k_shard trace rel: iter1={tr[0]:.2e} max={tr.max():.2e}"
          " (gates 1e-6 / 2e-3)")
    ok &= tr[0] < 1e-6 and tr.max() < 2e-3

    # --- gate 3: k_shard x phi_spec (Nystrom) EM parity ---------------
    def kcfg(**kw):
        return SVMConfig(formulation="KRN", sigma=5.0, lam=0.1,
                         eps=1e-2, max_iters=15, min_iters=15, **kw)

    n1 = NystromSVM(kcfg(), n_landmarks=31)       # phi width 32 -> | 2
    rn1 = n1.fit(X, y)
    nk = NystromSVM(kcfg(k_shard_axis="model"), n_landmarks=31,
                    mesh=mesh, data_axes=("data",))
    rnk = nk.fit(X, y)
    rel = np.abs(rnk.weights - rn1.weights).max() / np.abs(
        rn1.weights).max()
    print(f"KRN(Nystrom) k_shard rel err: {rel:.2e} (gate 1e-4), "
          f"scores {n1.score(X, y):.3f}/{nk.score(X, y):.3f}")
    ok &= rel < 1e-4

    # --- gate 4: pad_features route ------------------------------------
    base = PEMSVM(SVMConfig(add_bias=False, **em)).fit(X, y)
    pk = PEMSVM(SVMConfig(add_bias=False, k_shard_axis="model",
                          pad_features=2, **em),
                mesh=mesh, data_axes=("data",))
    rp = pk.fit(X, y)
    rel = np.abs(rp.weights[:K] - base.weights).max() / np.abs(
        base.weights).max()
    print(f"pad_features k_shard rel err: {rel:.2e} (gate 1e-3), "
          f"padded width {rp.weights.shape[0]}")
    ok &= rel < 1e-3 and rp.weights.shape == (24,)

    if not ok:
        print("KSHARD SMOKE FAIL")
        return 1
    print("KSHARD SMOKE OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
