"""The readers of the program's own spans, on a hand-built trace: two
fits with nested ``pemsvm.*`` spans, ``pemsvm.pad_rows`` missing from
the second, and a ``pemsvm.bias`` span outside any fit."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, peaks, spans  # noqa: E402
from bench.tracefile import Event, Trace  # noqa: E402

READERS = ("bias_ms", "pad_rows_ms", "upload_ms", "finalize_ms")


def fit_spans(t0, bias, upload, finalize, pad_rows=None):
    """One fit's host spans from t0, in the order ``PEMSVM.fit`` writes
    them: bias, labels, pad_rows, two uploads (rows, state), one chunk
    holding its dispatch and sync, finalize."""
    out, t = [], t0 + 0.1
    out.append(Event("pemsvm.bias", t, bias))
    t += bias
    out.append(Event("pemsvm.labels", t, 0.05))
    t += 0.05
    if pad_rows is not None:
        out.append(Event("pemsvm.pad_rows", t, pad_rows))
        t += pad_rows
    for dur in upload:
        out.append(Event("pemsvm.upload", t, dur))
        t += dur
    out += [Event("pemsvm.chunk", t, 1.0),
            Event("pemsvm.dispatch", t, 0.01),
            Event("pemsvm.sync", t + 0.02, 0.9)]
    t += 1.0
    out.append(Event("pemsvm.finalize", t, finalize))
    t += finalize
    return [Event("bench.fit", t0, 4.5),
            Event("pemsvm.fit", t0 + 0.05, t - t0 - 0.05)] + out


def make_trace(host):
    return Trace({0: [Event("%fusion.1 = f32[8] fusion(f32[8] %a)",
                            1.5, 0.5)]}, {0: []}, host)


def context(host):
    return harness.Context(make_trace(host),
                           harness.resolve("mnist8m-fit", 1),
                           peaks.peak("TPU v5 lite"))


def two_fits():
    return (fit_spans(0.0, 0.3, (1.0, 0.05), 0.1, pad_rows=0.3)
            + fit_spans(10.0, 0.5, (2.0, 0.05), 0.3)
            + [Event("pemsvm.bias", 6.0, 1.0),        # between the fits
               Event("np.asarray(jax.Array)", 6.0, 1.0)])


def test_spans_nest_in_their_fit():
    tr = make_trace(two_fits())
    assert len(tr.fits) == 2
    for fit in tr.fits:
        prog = [e for e in tr.host if e.name.startswith("pemsvm.")
                and fit.start <= e.start < fit.end]
        outer = next(e for e in prog if e.name == "pemsvm.fit")
        assert all(outer.start <= e.start and e.end <= outer.end
                   for e in prog)


@pytest.mark.parametrize("name,want", [
    ("bias_ms", (300 + 500) / 2),             # the span between fits left out
    ("pad_rows_ms", (300 + 0) / 2),           # the second fit has none
    ("upload_ms", (1050 + 2050) / 2),         # rows and state summed per fit
    ("finalize_ms", (100 + 300) / 2),
])
def test_reader_sums_spans_per_fit(name, want):
    assert context(two_fits()).value(name) == pytest.approx(want)


def test_per_fit_ms_of_a_span_with_no_reader():
    tr = make_trace(two_fits())
    assert spans.per_fit_ms(tr, "pemsvm.labels") == pytest.approx(50.0)
    assert spans.per_fit_ms(tr, "pemsvm.sync") == pytest.approx(900.0)


@pytest.mark.parametrize("name", READERS)
def test_reader_with_no_program_span_returns_none(name):
    # a program that writes no span: only the benchmark's own
    bare = [Event("bench.fit", 0.0, 4.0), Event("bench.fit", 10.0, 4.0),
            Event("DevicePut", 0.5, 0.4)]
    assert context(bare).value(name) is None
    # a program span, but outside every fit
    outside = bare + [Event(f"pemsvm.{name[:-3]}", 5.0, 1.0)]
    assert context(outside).value(name) is None
