"""The program's own host spans in a traced window.

``PEMSVM.fit`` writes ``pemsvm.*`` spans (``jax.profiler.TraceAnnotation``)
on the thread that fits, the thread that holds the benchmark's
``bench.fit`` spans, so ``Trace.host`` has them on the devices' clock.
"""
from __future__ import annotations


def per_fit_ms(trace, name: str) -> float | None:
    """Milliseconds a fit spends in spans called ``name``: for each traced
    fit, the spans that lie inside its ``bench.fit`` interval, summed; the
    mean over the traced fits, a fit without one counting 0. None when no
    fit holds such a span (a program that writes none)."""
    spans = [e for e in trace.host if e.name == name]
    per_fit = [[e.dur for e in spans
                if fit.start <= e.start and e.end <= fit.end]
               for fit in trace.fits]
    if not any(per_fit):
        return None
    return sum(map(sum, per_fit)) / len(per_fit) * 1e3
