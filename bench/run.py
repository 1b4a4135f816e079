#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the cell's data on the device from ``--seed``, copies it
to the host once (the estimator's ``fit`` takes host arrays), builds the
estimator the configuration names and runs one whole warm-up fit, which
compiles every program the window uses. The window then runs whole fits
back to back until ``--seconds`` have passed and the fit in progress
has returned. ``--trace 1`` records the window with
the JAX profiler and reports the per-layer metrics instead of the
end-to-end ones. After the window the reference fits the same data
(made again from the seed) and every number compared is printed with
its limit, last on standard error and last in the result.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``busy_s`` and
``window_s`` in a traced run), ``breakdown`` (traced runs) and
``checks``. Without a TPU, or with fewer chips than the cell asks for,
it exits with 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, peaks, tracefile  # noqa: E402


def log(msg: str) -> None:
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips_present(chips: int) -> str | None:
    """Why this machine cannot run a cell on ``chips`` TPUs, or None."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"needs a TPU; JAX found platform {devs[0].platform!r}"
    if len(devs) < chips:
        return f"the cell needs {chips} chips; JAX sees {len(devs)}"
    return None


def device_info(job) -> dict:
    import jax

    devs = jax.devices()[:job.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": int(peak)}


def run_cell(job, seconds: float, trace: bool, t_start: float = T_START,
             peak=None) -> dict:
    """Set-up, window, metrics and checks of one run; the result object."""
    import jax
    import numpy as np
    from jax.profiler import ProfileOptions, TraceAnnotation

    mesh = harness.make_mesh(job)
    X, t = harness.make_data(job, mesh)
    X_host, t_host = np.asarray(X), np.asarray(t)
    del X, t
    svm = harness.estimator(job, mesh)

    def fit():
        return svm.fit(X_host, t_host)

    warm = fit()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s")

    span = None
    if trace:
        shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(harness.TRACE_DIR),
                                 profiler_options=opts)
        span = TraceAnnotation
    try:
        win = harness.run_window(fit, seconds, span)
    finally:
        if trace:
            jax.profiler.stop_trace()
    log(f"window {win.seconds:.3f} s, {len(win.fit_s)} fits: {win.fit_s}")
    win.digests.insert(0, harness.digest(np.asarray(warm.weights)))
    device = device_info(job)

    result = {"attempted": len(win.fit_s), "failed": win.nonfinite}
    if trace:
        tr = tracefile.load(str(harness.TRACE_DIR))
        shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
        ctx = harness.Context(tr, job, peak or peaks.peak(device["kind"]))
        metrics = {}
        for m in job.per_layer:
            v = ctx.value(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        lo, hi = tr.window
        busy = [tracefile.busy_seconds(e, lo, hi) for e in tr.ops.values()]
        device["busy_s"] = sum(busy) / max(len(busy), 1)
        device["window_s"] = hi - lo
        result["breakdown"] = {"device_ops": tracefile.op_breakdown(tr),
                               "idle_gaps": tracefile.gap_breakdown(tr)}
    else:
        values = {"setup_s": setup_s, "fit_s": win.seconds / len(win.fit_s)}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in job.end_to_end}

    del svm, X_host, t_host, warm
    gc.collect()
    t0 = time.perf_counter()
    checks = harness.check(job, win, mesh)
    log(f"reference and checks {time.perf_counter() - t0:.3f} s")
    result.update(correct=harness.passed(checks), metrics=metrics,
                  device=device, checks=checks)
    return {k: result[k] for k in ("correct", "attempted", "failed",
                                   "metrics", "device", "breakdown",
                                   "checks") if k in result}


def main(argv=None) -> int:
    args = parse(argv)
    job = harness.resolve(args.workload, args.seed)
    why = chips_present(job.chips)
    if why:
        print(f"bench/run.py: {args.workload}: {why}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    emit(run_cell(job, args.seconds, bool(args.trace)))
    return 0


def emit(result: dict) -> None:
    """Each number compared with its limit as the last lines of standard
    error, then the result as the last line of standard output."""
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.exit(main())
