"""Paper Fig. 2: training speed vs number of workers (dna dataset).

This container has ONE physical core, so wall-clock over forced host
devices cannot show parallel speedup (all 'devices' share the core).
Instead each P runs in a subprocess and reports the *per-device* compiled
cost of one EM iteration (exact loop-aware HLO analysis): FLOPs/device
must fall as 1/P (the paper's linear-scaling regime) while the reduction
payload stays constant — the same accounting the §Roofline cells use.
Wall-clock is reported as a secondary sanity column with this caveat.

A CPU emulation by design: the children run with ``JAX_PLATFORMS=cpu``.
On a TPU machine the parent (``benchmarks/run.py``) has already touched
JAX and holds the chip, and a child that reached for it would fail or
block on the TPU runtime's lock."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from .common import emit

_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_dev}"
import json, time
import numpy as np, jax
from repro.core import PEMSVM, SVMConfig, lam_from_C
from repro.data import make_dna_like
from repro.launch.hlo_cost import analyze

n_dev = {n_dev}
X, y = make_dna_like({n}, {k})
lam = lam_from_C(1e-5) * {n} / 2_500_000
mesh = None
if n_dev > 1:
    mesh = jax.make_mesh((n_dev,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
svm = PEMSVM(SVMConfig(lam=lam, max_iters=6, min_iters=6, tol=0.0),
             mesh=mesh)
data, prior, state = svm._prepare(
    np.concatenate([X, np.ones((len(X), 1), np.float32)], 1), y)
step = svm._build_step(False)
key = jax.random.PRNGKey(0)
import jax.numpy as jnp
lowered = step.lower(data, state, key) if hasattr(step, "lower") else \
    jax.jit(step).lower(data, state, key)
cost = analyze(lowered.compile().as_text())
t0 = time.time()
res = svm.fit(X, y)
wall = (time.time() - t0) / res.n_iters
print(json.dumps({{"n_dev": n_dev, "flops_per_dev": cost["flops"],
                   "coll_bytes": cost["collective_bytes"],
                   "wall_s_per_iter": wall, "acc": svm.score(X, y)}}))
"""


def run(n: int = 40_000, k: int = 400, devices=(1, 2, 4, 8, 16),
        full=False):
    rows = []
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    for n_dev in devices:
        env = dict(os.environ)
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        code = textwrap.dedent(_SCRIPT.format(n_dev=n_dev, n=n, k=k))
        p = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=900)
        assert p.returncode == 0, p.stderr[-2000:]
        r = json.loads(p.stdout.strip().splitlines()[-1])
        rows.append({"name": f"P={n_dev}",
                     "seconds": r["flops_per_dev"] / 197e12,
                     "flops_per_dev": f"{r['flops_per_dev']:.4g}",
                     "coll_bytes": f"{r['coll_bytes']:.4g}",
                     "wall_1core_caveat": round(r["wall_s_per_iter"], 3),
                     "acc": round(r["acc"], 4)})
    base = float(rows[0]["flops_per_dev"])
    for r, n_dev in zip(rows, devices):
        r["flop_speedup"] = round(base / float(r["flops_per_dev"]), 2)
        r["parallel_efficiency"] = round(
            base / float(r["flops_per_dev"]) / n_dev, 3)
    emit(rows, "fig2_cores")
    return rows
