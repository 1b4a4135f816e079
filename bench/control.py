#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, on the chip.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 [--control] [--look]

For each seed: the cell's data made on the device, one fit through the
estimator the configuration names at the cell's own size, then the
reference from scratch. Prints one JSON line per seed with the numbers that
``correct`` compares (``harness.compare``), the objective gap at every
iteration and the relative gap of each class's weights. With
``--control`` the same numbers are read for the control (the reference
computed in bfloat16, put in the program's place) and for each fault
the reference module plants in its own fit (``FAULTS``: state left
unchanged, half the rows with the statistic doubled, the exchange
between chips left out, an M-step's answer altered). With ``--look``
they are read for the reference itself at two other roundings: with
bfloat16 operands and float32 sums (``bf16_products``, the precision of
a float32 matmul at the TPU's default), and at full precision with the
rows summed in another order (``reordered``: two row shards, added at
the end). The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def class_gaps(w, ref_w) -> list:
    """Relative L2 gap of each class's weights (one for a binary fit)."""
    import numpy as np

    from bench.reference import common

    w, ref_w = np.atleast_2d(w), np.atleast_2d(ref_w)
    return [common.rel_l2(a, b) for a, b in zip(w, ref_w)]


def program_fit(job, mesh):
    """(weights, objective trace) of one fit through the estimator's
    ``fit``."""
    import numpy as np

    X, t = harness.make_data(job, mesh)
    X_host, t_host = np.asarray(X), np.asarray(t)
    del X, t
    res = harness.estimator(job, mesh).fit(X_host, t_host)
    return np.asarray(res.weights), list(res.objective)


def readings(job, mesh, control: bool, look: bool = False) -> dict:
    from bench.reference import common

    t0 = time.perf_counter()
    w, trace = program_fit(job, mesh)
    gc.collect()
    X3, t3 = harness.reference_data(job, mesh)
    ref_w, ref_trace = harness.reference_fit(job, X3, t3)

    def numbers(w, trace):
        return dict(harness.compare(job, w, trace, ref_w, ref_trace,
                                    (X3, t3)),
                    gaps=harness.gaps(trace, ref_trace),
                    class_w_rel=class_gaps(w, ref_w))

    out = {"seed": job.seed, "program": numbers(w, trace)}
    runs = {}
    if control:
        runs["control"] = {"prec": common.CONTROL}
        runs.update({f: {"fault": f} for f in harness.reference(job).FAULTS
                     if f != "no_exchange" or job.chips > 1})
    if look:
        runs["bf16_products"] = {"prec": common.BF16_PRODUCTS}
    for name, kw in runs.items():
        out[name] = numbers(*harness.reference_fit(job, X3, t3, **kw))
    if look:
        S, n, K = X3.shape
        halves = (X3.reshape(2 * S, n // 2, K), t3.reshape(2 * S, n // 2))
        out["reordered"] = numbers(*harness.reference_fit(job, *halves))
    out["seconds"] = time.perf_counter() - t0
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--look", action="store_true")
    ap.add_argument("--out", default=None, help="also append lines here")
    args = ap.parse_args(argv)
    harness.enable_compile_cache()
    for seed in args.seeds:
        job = harness.resolve(args.workload, seed)
        line = json.dumps(readings(job, harness.make_mesh(job),
                                   args.control, args.look))
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
