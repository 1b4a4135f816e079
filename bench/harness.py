"""The benchmark's machinery, driven by ``BENCHMARK.json`` and the files
it names: a cell's configuration (``bench/configs/<config>.json``), its
traffic (``bench/traffic/<traffic>.json``), its correctness limits
(``bench/checks/<workload>.json``), the estimator, data generator and
reference the configuration names (``estimator``, default ``PEMSVM``;
``bench/data/<dataset>.py``, ``bench/reference/<reference>.py``) and one
reader per per-layer metric (``bench/metrics/<metric>.py``). Adding a
cell, a mix or a metric adds files and entries; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib
import importlib.util
import json
import math
import time
from collections.abc import Callable
from pathlib import Path

from bench import work

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
DEFAULT_ESTIMATOR = "PEMSVM"


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Job:
    """One cell, resolved: what a run fits and how it is checked."""
    workload: str
    chips: int
    config: dict           # bench/configs/<config>.json, lam resolved
    traffic: dict          # bench/traffic/<traffic>.json
    checks: dict           # bench/checks/<workload>.json
    end_to_end: tuple      # metric entries of BENCHMARK.json
    per_layer: tuple
    seed: int

    @property
    def limits(self) -> dict:
        """Each number compared and its limit; ``obj_sweep`` in the
        checks file gives one limit per iteration (null: not compared),
        compared as ``obj_s01``, ``obj_s02``, ... for the iterations a fit
        runs."""
        per = self.checks.get("obj_sweep", [])[:self.iters]
        return dict({f"obj_s{t:02d}": v for t, v in enumerate(per, 1)
                     if v is not None}, **self.checks["limits"])

    @property
    def rows(self) -> int:
        return self.traffic["rows_per_chip"] * self.chips

    @property
    def rows_per_chip(self) -> int:
        return self.traffic["rows_per_chip"]

    @property
    def iters(self) -> int:
        return self.traffic["iters"]

    @property
    def estimator(self) -> str:
        """The program's estimator class that fits the configuration."""
        return self.config.get("estimator", DEFAULT_ESTIMATOR)

    @property
    def spec(self) -> "Estimator":
        """What the harness knows of that estimator."""
        return ESTIMATORS[self.estimator]

    @property
    def width(self) -> int:
        """Width K of the statistic the estimator accumulates: the
        configuration's ``spec.width_key`` plus the bias column."""
        return self.config[self.spec.width_key] + int(self.config["add_bias"])

    @property
    def classes(self) -> int:
        """Class passes per iteration (M for MLT, 1 otherwise)."""
        mlt = self.config["options"].endswith("MLT")
        return self.config["num_classes"] if mlt else 1

    @property
    def fit_seed(self) -> int:
        """The seed the fit's own sampler is given (31 bits)."""
        return self.seed & 0x7FFFFFFF


def resolve(name: str, seed: int, root: Path = ROOT, **traffic_kw) -> Job:
    """The cell ``name`` of ``root``'s BENCHMARK.json. ``traffic_kw``
    overrides keys of its traffic (rows, iterations), for tests at a
    small size."""
    spec = read_json(root / "BENCHMARK.json")
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in spec['workloads']]}")
    centry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = read_json(root / centry["file"])
    traffic = read_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    traffic = dict(traffic, **traffic_kw)
    if traffic["mesh"]["data"] != cell["chips"]:
        raise ValueError(f"{name}: traffic mesh {traffic['mesh']} does not "
                         f"span the cell's {cell['chips']} chip(s)")
    n = traffic["rows_per_chip"] * cell["chips"]
    est = config.get("estimator", DEFAULT_ESTIMATOR)
    if est not in ESTIMATORS:
        raise ValueError(f"{centry['file']}: estimator {est!r} is not one "
                         f"of {sorted(ESTIMATORS)}")
    ESTIMATORS[est].check(config, centry["file"], n)
    # lam = 2 / C (paper Eq. 1), scaled with the rows so the prior
    # weighs the data as it does at the source's size.
    config = dict(config, lam=2.0 / config["C"] * n / config["source_rows"])
    checks = read_json(root / "bench" / "checks" / f"{name}.json")

    def applies(m):
        return name in m.get("workloads", [name])

    return Job(name, cell["chips"], config, traffic, checks,
               tuple(m for m in spec["end_to_end"] if applies(m)),
               tuple(m for m in spec["per_layer"] if applies(m)), seed)


@dataclasses.dataclass(frozen=True)
class Estimator:
    """What the harness knows of one of the program's estimators. Every
    choice that differs by estimator reads its entry in ``ESTIMATORS``;
    another estimator adds an entry, and its counts to ``work.py``."""
    width_key: str          # config key of the statistic's width, bias aside
    x_bias: bool            # X itself carries the bias column (add_bias)
    kernel: str             # HLO name of its fused statistic kernel
    pass_flops: Callable    # (n, job) -> FLOPs of one class pass
    call_counts: Callable   # (n, job) -> (FLOPs, bytes) of one kernel call
    check: Callable         # (config, file, rows) -> None, or raises
    build: Callable         # (SVMConfig, job, mesh) -> the estimator


def _no_check(config: dict, file: str, rows: int) -> None:
    pass


def _check_nystrom(config: dict, file: str, rows: int) -> None:
    """Refuse a NystromSVM configuration the program cannot fit, naming
    the key at fault."""
    m = config.get("n_landmarks")
    if type(m) is not int or not 1 <= m <= rows:
        raise ValueError(f"{file}: n_landmarks {m!r}: NystromSVM needs a "
                         f"whole number of landmarks from 1 to the {rows} "
                         f"rows")
    if not config["options"].startswith("KRN-"):
        raise ValueError(f"{file}: options {config['options']!r}: "
                         f"NystromSVM fits a KRN configuration")
    if not config["add_bias"]:
        raise ValueError(f"{file}: add_bias false: NystromSVM always "
                         f"carries its bias in phi-space")


def _build_pemsvm(cfg, job: Job, mesh):
    from repro.core import PEMSVM

    return PEMSVM(cfg, mesh=mesh)


def _build_nystrom(cfg, job: Job, mesh):
    """NystromSVM draws its landmarks from the run's seed (``fit_seed``),
    so a reference given that seed can draw the same ones."""
    from repro.core import NystromSVM

    return NystromSVM(cfg, n_landmarks=job.config["n_landmarks"],
                      mesh=mesh, seed=job.fit_seed)


# The program's estimators a configuration may name, by class name.
# NystromSVM's statistic is phi-space (width n_landmarks + bias, the bias
# in phi), so its X and its reference's rows carry no bias column.
ESTIMATORS = {
    "PEMSVM": Estimator("n_features", True, "fused_stats",
                        work.lin_pass_flops, work.lin_call, _no_check,
                        _build_pemsvm),
    "NystromSVM": Estimator("n_landmarks", False, "nystrom_fused_stats",
                            work.nystrom_pass_flops, work.nystrom_call,
                            _check_nystrom, _build_nystrom),
}


def load_metric(name: str, root: Path = ROOT):
    """The reader module ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def dataset(job: Job):
    return importlib.import_module(f"bench.data.{job.config['dataset']}")


def reference(job: Job):
    return importlib.import_module(
        f"bench.reference.{job.config['reference']}")


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, for
    every program, so that only a checkout's first run compiles."""
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def data_key(seed: int):
    """A JAX key from any non-negative seed, all 64 bits of it."""
    import jax

    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} is not in [0, 2^64)")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def make_mesh(job: Job):
    import jax
    from jax.sharding import AxisType

    if job.chips == 1:
        return None
    return jax.make_mesh((job.chips,), ("data",),
                         axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:job.chips])


@dataclasses.dataclass(frozen=True)
class RowSharding:
    x: object
    rows: object


def make_data(job: Job, mesh):
    """(X, target) on the device(s), made from the run's seed."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = None if mesh is None else RowSharding(
        NamedSharding(mesh, P("data", None)), NamedSharding(mesh, P("data")))
    return dataset(job).make(data_key(job.seed), job.rows,
                             job.config["n_features"],
                             job.config["num_classes"], sh)


def svm_config(job: Job):
    """The program's SVMConfig from its own paper factory. Each key of the
    configuration file that is a field of SVMConfig is applied from the
    file where its ``reduced`` lists the key, and otherwise checked
    against what the factory gives, as are ``options`` and, unless
    ``reduced`` lists it, ``C`` (lam = 2 / C); a mismatch raises."""
    from repro.configs import svm_paper
    from repro.core import SVMConfig

    c = job.config
    reduced = c["reduced"]
    base = getattr(svm_paper, c["factory"])()
    a = c["options"].split("-")[1]
    if a != base.algorithm and "algorithm" in reduced:
        base = dataclasses.replace(base, algorithm=a)
    fields = {f.name for f in dataclasses.fields(SVMConfig)}
    stated = {k: v for k, v in c.items() if k in fields}
    applied = {k: v for k, v in stated.items() if k in reduced}
    checked = {k: (getattr(base, k), v) for k, v in stated.items()
               if k not in reduced}
    checked["options"] = (base.options, c["options"])
    if "C" not in reduced:
        checked["lam (2 / C)"] = (base.lam, 2.0 / c["C"])
    for k, (got, want) in checked.items():
        ok = (math.isclose(got, want, rel_tol=1e-12)
              if isinstance(want, float) and isinstance(got, (int, float))
              else got == want)
        if not ok:
            raise ValueError(f"{c['factory']}: {k} is {got!r}, the "
                             f"configuration file states {want!r}")
    return dataclasses.replace(base, **dict(
        applied, min_iters=job.iters, max_iters=job.iters,
        seed=job.fit_seed))


def estimator(job: Job, mesh):
    """The estimator the configuration names, built from ``svm_config``
    as a user builds it."""
    return job.spec.build(svm_config(job), job, mesh)


def digest(weights) -> str:
    import numpy as np

    return hashlib.sha256(np.ascontiguousarray(weights).tobytes()).hexdigest()


@dataclasses.dataclass
class Window:
    seconds: float = 0.0          # wall time of the window
    fit_s: list = dataclasses.field(default_factory=list)
    digests: list = dataclasses.field(default_factory=list)
    nonfinite: int = 0
    last: object = None           # the last fit's FitResult


def run_window(fit, seconds: float, span=None) -> Window:
    """Whole fits back to back until ``seconds`` have passed and the fit
    in progress has returned. ``span(name)`` wraps each fit (a profiler
    annotation in a traced run)."""
    import contextlib

    import numpy as np

    span = span or (lambda name: contextlib.nullcontext())
    win = Window()
    t_start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        with span("bench.fit"):
            res = fit()
        win.fit_s.append(time.perf_counter() - t0)
        w = np.asarray(res.weights)
        win.digests.append(digest(w))
        win.nonfinite += int(not np.all(np.isfinite(w)))
        win.last = res
        if time.perf_counter() - t_start >= seconds:
            break
    win.seconds = time.perf_counter() - t_start
    return win


def layout(job: Job, X, t, mesh):
    """The reference's (S, n, K) rows on the cell's devices, with the
    bias column where the estimator's X carries one (``spec.x_bias`` and
    ``add_bias``); NystromSVM's reference gets the raw rows and
    featurises them itself. Takes over X's buffer."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    S = job.chips
    N, D = X.shape

    bias = job.spec.x_bias and job.config["add_bias"]

    def f(X, t):
        if bias:
            X = jnp.concatenate([X, jnp.ones((N, 1), X.dtype)], axis=1)
        return X.reshape(S, N // S, X.shape[1]), t.reshape(S, N // S)

    out = None if mesh is None else (
        NamedSharding(mesh, P("data", None, None)),
        NamedSharding(mesh, P("data", None)))
    return jax.jit(f, out_shardings=out, donate_argnums=(0,))(X, t)


def gaps(trace, ref_trace) -> list:
    """Relative gap of the objective at each iteration."""
    return [abs(p - r) / abs(r) for p, r in zip(trace, ref_trace)]


def compare(job: Job, weights, trace, ref_w, ref_trace, data=None) -> dict:
    """The numbers that decide ``correct``, as the cell's limits name
    them, for a fit's weights and objective trace against the
    reference's: ``obj_first``, the relative gap of the first
    iteration's objective; ``obj_trace``, the largest relative gap of
    the objective over all iterations; ``obj_sNN``, the gap at iteration
    NN; ``w_rel``, the relative L2 gap of the final weights; ``w_obj``,
    the relative gap between the objectives the reference computes, over
    ``data`` = (X3, t3), at the fit's final weights and at its own."""
    from bench.reference import common

    g = gaps(trace, ref_trace)
    ok = len(g) == job.iters and all(math.isfinite(x) for x in g)
    out = {"obj_first": g[0] if g else math.nan,
           "obj_trace": max(g) if ok else math.nan}
    for t in range(1, job.iters + 1):
        out[f"obj_s{t:02d}"] = g[t - 1] if t <= len(g) else math.nan
    if "w_rel" in job.limits:
        out["w_rel"] = common.rel_l2(weights, ref_w)
    if "w_obj" in job.limits:
        mine, ref = (reference(job).objective(*data, w, job.config)
                     for w in (weights, ref_w))
        out["w_obj"] = abs(mine - ref) / abs(ref)
    return {k: v for k, v in out.items() if k in job.limits}


def reference_data(job: Job, mesh):
    """The cell's rows made again from the seed, in the reference's
    (S, n, K) layout."""
    X, t = make_data(job, mesh)
    return layout(job, X, t, mesh)


def reference_fit(job: Job, X3, t3, **kw):
    import jax

    with jax.default_matmul_precision("highest"):
        return reference(job).fit(X3, t3, job.config, job.iters,
                                  job.fit_seed, **kw)


def check(job: Job, win: Window, mesh) -> dict:
    """Every number compared, each with its limit. Run once the
    program's state is freed: the data is made again from the seed and
    the reference fits it from scratch."""
    X3, t3 = reference_data(job, mesh)
    ref_w, ref_trace = reference_fit(job, X3, t3)
    nums = compare(job, win.last.weights, win.last.objective, ref_w,
                   ref_trace, (X3, t3))
    nums["fits_differ"] = sum(d != win.digests[0] for d in win.digests)
    nums["nonfinite_fits"] = win.nonfinite
    # A number that is not finite fails, and is printed as a string so
    # the result stays JSON.
    return {k: {"value": v if math.isfinite(v) else repr(v),
                "limit": job.limits[k]} for k, v in nums.items()}


def passed(checks: dict) -> bool:
    return all(isinstance(c["value"], (int, float)) and c["value"] <= c["limit"]
               for c in checks.values())


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets: the reduced trace of the
    traced window, the job and the chip's peaks."""
    trace: object
    job: Job
    peak: object
    root: Path = ROOT
    _mods: dict = dataclasses.field(default_factory=dict)

    @property
    def n_fits(self) -> int:
        return len(self.trace.fits)

    @property
    def iterations(self) -> int:
        """Iterations the traced fits executed, together."""
        return self.n_fits * self.job.iters

    def metric(self, name: str):
        if name not in self._mods:
            self._mods[name] = load_metric(name, self.root)
        return self._mods[name]

    def value(self, name: str):
        return self.metric(name).read(self)
