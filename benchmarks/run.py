"""Benchmark harness: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV. ``--full`` uses paper-size
datasets (hours on CPU); default sizes finish in minutes."""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma-separated benchmark names")
    args, _ = ap.parse_known_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (elastic_overhead, fig2_cores, fig34_scaling,
                   fig56_convergence, fleet_recovery, kshard_fused,
                   mc_fused, nystrom_fused, rng_fused, roofline,
                   serve_latency, stream_vs_resident, table5_dna,
                   table6_svr, table7_krn, table8_mlt, table9_gram)
    benches = {
        "table5_dna": table5_dna.run,
        "table6_svr": table6_svr.run,
        "table7_krn": table7_krn.run,
        "table8_mlt": table8_mlt.run,
        "table9_gram": table9_gram.run,
        "fig2_cores": fig2_cores.run,
        "fig34_scaling": fig34_scaling.run,
        "fig56_convergence": fig56_convergence.run,
        "roofline": roofline.run,
        "stream_vs_resident": stream_vs_resident.run,
        "nystrom_fused": nystrom_fused.run,
        "mc_fused": mc_fused.run,
        "rng_fused": rng_fused.run,
        "kshard_fused": kshard_fused.run,
        "elastic_overhead": elastic_overhead.run,
        "fleet_recovery": fleet_recovery.run,
        "serve_latency": serve_latency.run,
    }
    only = [x for x in args.only.split(",") if x]
    failed = []
    for name, fn in benches.items():
        if only and name not in only:
            continue
        print(f"# --- {name} ---", flush=True)
        t0 = time.time()
        try:
            fn(full=args.full)
        except Exception:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    if failed:
        print(f"# FAILED: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
