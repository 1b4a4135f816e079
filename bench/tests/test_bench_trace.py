"""The trace reductions and per-layer metric readers, on a hand-built
trace: two fits of two iterations each on two devices."""
import dataclasses
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, peaks, tracefile, work  # noqa: E402
from bench.tracefile import Event, Trace  # noqa: E402

K = "%fused_stats.7 = (f32[8,1]) custom-call(f32[8,896] %pad.1), custom_call_target=\"tpu_custom_call\""
PAD = "%pad.1 = f32[8,896] pad(f32[8,785] %x)"
AR = "%all-reduce.3 = f32[321] all-reduce(f32[321] %p)"
LOOP = "%while.2 = (s32[]) while((s32[]) %t), condition=%c, body=%b"


def device_events(t0):
    """One fit's device side from t0: the chunk program 1.0-3.0 s after
    t0, holding a pad, two kernel calls and an all-reduce."""
    mods = [Event("jit_runner(123)", t0 + 1.0, 2.0),
            Event("jit_convert_element_type(9)", t0 + 0.2, 0.1)]
    ops = [Event(LOOP, t0 + 1.0, 2.0),                 # container
           Event("%convert.1 = f32[8] convert(s32[8] %a)", t0 + 0.2, 0.1),
           Event(PAD, t0 + 1.2, 0.3),
           Event(K, t0 + 1.5, 0.4),
           Event(K, t0 + 2.0, 0.4),
           Event(AR, t0 + 2.5, 0.1)]
    return mods, ops


def make_trace():
    host = [Event("bench.fit", 0.0, 4.0), Event("bench.fit", 10.0, 4.0),
            Event("np.asarray(jax.Array)", 6.0, 1.0),
            Event("DevicePut", 10.5, 0.4)]
    ops, mods = {0: [], 1: []}, {0: [], 1: []}
    for t0 in (0.0, 10.0):
        for d in (0, 1):
            m, o = device_events(t0)
            mods[d] += m
            ops[d] += o
    # device 1's kernel runs longer in the second fit
    ops[1] = [dataclasses.replace(e, dur=0.6) if e.name == K and e.start > 10
              else e for e in ops[1]]
    return Trace(ops, mods, host)


@pytest.fixture
def ctx():
    job = harness.resolve("dna-fit-dp4", 1)
    job = dataclasses.replace(job, traffic=dict(job.traffic, iters=2,
                                                rows_per_chip=1024))
    return harness.Context(make_trace(), job, peaks.peak("TPU v5 lite"))


def test_window_and_fits():
    tr = make_trace()
    assert len(tr.fits) == 2
    assert tr.window == (0.0, 14.0)


def test_union_of_busy_intervals_and_idle_share(ctx):
    tr = ctx.trace
    # device 0, first fit: convert 0.1 + pad 0.3 + kernels 0.8 + AR 0.1;
    # the while container is not work
    assert tracefile.busy_seconds(tr.ops[0], 0.0, 4.0) == pytest.approx(1.3)
    overlapping = [Event("a", 0.0, 2.0), Event("b", 1.0, 2.0),
                   Event("c", 5.0, 1.0)]
    assert tracefile.busy_seconds(overlapping, 0.0, 10.0) == pytest.approx(4.0)
    assert tracefile.busy_seconds(overlapping, 1.5, 5.5) == pytest.approx(2.0)
    assert tracefile.idle_gaps(overlapping, 0.0, 10.0) == [
        (3.0, 5.0), (6.0, 10.0)]
    # device 1's longer second-fit kernels overlap its all-reduce
    busy0, busy1 = 2 * 1.3, 1.3 + 1.5
    want = 100.0 * (1 - (busy0 + busy1) / 2 / 14.0)
    assert ctx.value("device_idle.fit") == pytest.approx(want)


def test_kernel_sums_per_iteration(ctx):
    # slowest device: 0.8 s (first fit) + 1.2 s (second) over 4 iterations
    assert ctx.value("fused_stats_ms") == pytest.approx(2.0 / 4 * 1e3)
    per = ctx.metric("fused_stats_ms").per_device(ctx)
    assert per[0] == (pytest.approx(1.6), 4)
    assert ctx.value("allreduce_ms") == pytest.approx(0.2 / 4 * 1e3)
    # the chunk program's busy time, slowest device: 1.2 + 1.4
    assert ctx.value("step_ms") == pytest.approx(2.6 / 4 * 1e3)


def test_roofline_and_mfu_from_counts(ctx):
    fs = ctx.metric("fused_stats_roofline")
    least, bound = fs.least_seconds(ctx.job, ctx.peak)
    assert bound == "memory"
    assert ctx.value("fused_stats_roofline") == pytest.approx(
        100 * least / 0.5)
    flops = work.iteration_flops(ctx.job)
    assert ctx.value("step_mfu") == pytest.approx(
        100 * flops / (0.65 * 4 * 197e12))


def test_prep_ms_is_span_start_to_first_program_op(ctx):
    # the chunk program's first op (the pad) starts 1.2 s into each fit
    assert ctx.value("prep_ms") == pytest.approx(1200.0)


def test_gap_attribution():
    tr = make_trace()
    gaps = dict(tracefile.gap_breakdown(tr))
    # device 0 idles 2.6-10.2 (midpoint inside np.asarray), 10.3-11.2
    # (inside DevicePut) and 2.9 s in all inside the fits' own spans
    assert gaps == pytest.approx({"np.asarray(jax.Array)": 7.6,
                                  "DevicePut": 0.9, "bench.fit": 2.9})
    top = dict(tracefile.op_breakdown(tr))
    assert "while.2" not in top
    assert top["fused_stats.7"] == pytest.approx((1.6 + 2.0) / 2)


def test_metric_with_nothing_to_read_returns_none(ctx):
    one_chip = dataclasses.replace(
        ctx, trace=Trace({0: [Event(K, 0.5, 0.1)]}, {0: []},
                         [Event("bench.fit", 0.0, 1.0)]))
    assert one_chip.value("allreduce_ms") is None
    assert one_chip.value("step_ms") is None
    assert one_chip.value("prep_ms") is None


def scan_breakdown(trace, device=0, top=10):
    """The plain definition ``gap_breakdown`` sweeps: for each idle gap,
    a scan of every host span for the innermost covering its midpoint."""
    import collections

    def activity(t):
        inner = None
        for e in trace.host:
            if e.start <= t < e.end and (inner is None or e.dur < inner.dur):
                inner = e
        return inner.name if inner is not None else "outside any span"

    lo, hi = trace.window
    by = collections.Counter()
    for a, b in tracefile.idle_gaps(trace.ops.get(device, []), lo, hi):
        by[activity(0.5 * (a + b))] += b - a
    return [[k, v] for k, v in by.most_common(top)]


def random_trace(seed):
    """Fits on a grid of quarter seconds, so gap midpoints fall on span
    edges; each fit holds nested spans whose durations repeat, and some
    spans start together with one duration. Time between fits holds no
    span, or a lone one."""
    import random

    rng = random.Random(seed)
    q = lambda k: 0.25 * k  # noqa: E731
    host, ops, t = [], [], 0
    for f in range(rng.randint(3, 12)):
        n = rng.randint(8, 40)
        host.append(Event("bench.fit", q(t), q(n)))
        for s in range(rng.randint(1, 25)):
            a = t + rng.randint(0, n - 1)
            d = rng.choice((1, 2, 2, 3, 4, 8))
            host.append(Event(f"span{rng.randint(0, 6)}", q(a), q(d)))
            if rng.random() < 0.3:
                host.append(Event(f"twin{s}", q(a), q(d)))
        for _ in range(rng.randint(0, 12)):
            a = t + rng.randint(0, n - 1)
            ops.append(Event("%fusion.1 = f32[8] fusion(f32[8] %a)", q(a),
                             q(rng.randint(1, 3))))
        t += n + rng.randint(0, 6)
        if rng.random() < 0.5:
            host.append(Event("between", q(t - 1), q(2)))
    rng.shuffle(host)
    return Trace({0: ops}, {0: []}, host)


@pytest.mark.parametrize("seed", range(40))
def test_gap_breakdown_sweep_equals_scan(seed):
    tr = random_trace(seed)
    want = scan_breakdown(tr, top=50)
    assert tracefile.gap_breakdown(tr, top=50) == want
    assert tracefile.gap_breakdown(tr) == want[:10]


@pytest.mark.parametrize("seed", range(20))
def test_busy_in_equals_busy_seconds_per_interval(seed):
    """``busy_in`` over program intervals gives, float for float, what
    ``busy_seconds`` gives interval by interval: on quarter-second grids
    where ops touch, nest, straddle an interval's edges and lie outside
    every interval, containers among them."""
    import random

    rng = random.Random(seed)
    q = lambda k: 0.25 * k + 0.1 * rng.random() * (k % 3 == 0)  # noqa: E731
    ops = [Event(rng.choice((PAD, K, AR, LOOP)), q(rng.randint(0, 400)),
                 q(rng.randint(0, 12))) for _ in range(rng.randint(0, 300))]
    iv = sorted((q(a), q(a) + q(rng.randint(0, 30)))
                for a in rng.sample(range(400), rng.randint(1, 30)))
    want = [tracefile.busy_seconds(ops, a, b) for a, b in iv]
    assert tracefile.busy_in(ops, iv) == want
    assert tracefile.busy_in(ops, []) == []
