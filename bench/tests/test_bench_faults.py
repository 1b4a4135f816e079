"""``correct`` comes out false for the control and for every fault a
cell can have, at a size a test run holds on the CPU.

The control is the reference computed in bfloat16 and put in the
program's place. The faults are planted in the program under a run of
the harness (``run.run_cell``, with only the look for a chip skipped):
a step that returns its state unchanged, half the rows left out with
the statistic doubled, the psum between chips left out (on four virtual
CPU devices, in a child process), and each M-step's answer altered.
"""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.reference import common  # noqa: E402

# Sizes a test holds, with the cells' own limits.
SMALL = {"mnist8m-fit": dict(rows_per_chip=32768, iters=5),
         "dna-fit-dp4": dict(rows_per_chip=4096, iters=4)}


def small_job(cell, seed, chips=None):
    job = harness.resolve(cell, seed, **SMALL[cell])
    if chips is not None:
        job = dataclasses.replace(job, chips=chips, traffic=dict(
            job.traffic, mesh={"data": chips}))
    return job


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_control_is_not_correct(cell):
    job = small_job(cell, 2**34 + 11, chips=1)
    X3, t3 = harness.reference_data(job, None)
    ref_w, ref_trace = harness.reference_fit(job, X3, t3)
    w, trace = harness.reference_fit(job, X3, t3, prec=common.CONTROL)
    nums = harness.compare(job, w, trace, ref_w, ref_trace, (X3, t3))
    checks = {k: {"value": v, "limit": job.limits[k]}
              for k, v in nums.items()}
    assert not harness.passed(checks), checks


# --------------------------------------------------------------- faults

def plant(fault):
    """Break the program's timed path underneath the solver."""
    from repro.core import linear, multiclass, solver, stats

    saved = {}

    def patch(mod, name, fn):
        saved[(mod, name)] = getattr(mod, name)
        setattr(mod, name, fn)

    if fault == "unchanged":
        cls, mlt = linear.cls_step, multiclass.mlt_step
        patch(linear, "cls_step",
              lambda data, w, key, **kw: (w, cls(data, w, key, **kw)[1]))
        patch(multiclass, "mlt_step",
              lambda data, W, key, **kw: (W, mlt(data, W, key, **kw)[1]))
    elif fault == "half_batch":
        acc = linear.accumulate_stats

        def half(X, rho, beta, w, **kw):
            n = X.shape[0] // 2
            m, g, _, _ = acc(X, rho, beta, w, **kw)
            _, _, S, b = acc(X[:n], rho[:n], beta[:n], w, **kw)
            return m, g, 2.0 * S, 2.0 * b

        patch(linear, "accumulate_stats", half)
        patch(multiclass, "accumulate_stats", half)
    elif fault == "no_exchange":
        patch(stats, "reduce_stats", lambda S, b, axes, **kw: (S, b))
    elif fault == "altered_answer":
        post = stats.posterior_params

        def altered(*a, **kw):
            L, mu = post(*a, **kw)
            i = jnp.argmax(jnp.abs(mu))
            return L, mu.at[i].set(-mu[i])

        patch(stats, "posterior_params", altered)
    def retrace():
        # the jitted steps keep their traces; drop them all
        solver._build_step_fn.cache_clear()
        solver._chunk_runner.cache_clear()
        jax.clear_caches()

    retrace()

    def undo():
        for (mod, name), fn in saved.items():
            setattr(mod, name, fn)
        retrace()

    return undo


def run_with_fault(cell, fault, seed):
    """The result of one harness run with ``fault`` planted."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_run",
                                                  ROOT / "bench" / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    undo = plant(fault)
    try:
        return run.run_cell(small_job(cell, seed), 0.01, trace=False)
    finally:
        undo()


ONE_CHIP = [("mnist8m-fit", f) for f in (None, "unchanged", "half_batch",
                                         "altered_answer")]


@pytest.mark.parametrize("cell,fault", ONE_CHIP)
def test_fault_makes_correct_false(cell, fault):
    result = run_with_fault(cell, fault, 2**36 + 5)
    assert result["correct"] is (fault is None), result["checks"]


FOUR_CHIPS = [None, "unchanged", "half_batch", "no_exchange",
              "altered_answer"]


@pytest.fixture(scope="module")
def four_chip_results():
    """Every four-chip fault in one child process with four CPU
    devices (this process keeps its single device)."""
    code = (
        "import json, sys; sys.path[:0] = [%r, %r]\n"
        "from bench.tests import test_bench_faults as t\n"
        "print(json.dumps({str(f): t.run_with_fault('dna-fit-dp4', f, "
        "2**37 + 9)['checks'] for f in t.FOUR_CHIPS}))\n"
        % (str(ROOT), str(ROOT / "src")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", FOUR_CHIPS)
def test_four_chip_fault_makes_correct_false(four_chip_results, fault):
    checks = four_chip_results[str(fault)]
    assert harness.passed(checks) is (fault is None), checks
