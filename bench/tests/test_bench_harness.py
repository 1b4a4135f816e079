"""The harness by name: BENCHMARK.json's shape, every cell resolving to
its files, the refusal without a TPU, and the window and result line at
a tiny size on the CPU."""
import json
import math
import os
import re
import subprocess
import sys
import time
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load_run():
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_run",
                                                  ROOT / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_benchmark_json_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    names = [c["name"] for c in SPEC["configs"]]
    assert len(set(names)) == len(names)
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 2)
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in names
        assert len(w["why"]) <= 200
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    job = harness.resolve(cell, 2**33 + 1)
    spec = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert (ROOT / "bench" / "traffic" / f"{spec['traffic']}.json").is_file()
    assert (ROOT / "bench" / "checks" / f"{cell}.json").is_file()
    assert job.chips == spec["chips"] == job.traffic["mesh"]["data"]
    for m in job.per_layer:
        assert callable(harness.load_metric(m["name"]).read)
    assert callable(harness.dataset(job).make)
    assert callable(harness.reference(job).fit)
    names = {m["name"] for m in job.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and job.per_layer
    cfg = harness.svm_config(job)
    assert cfg.options == job.config["options"]
    assert cfg.max_iters == cfg.min_iters == job.iters and cfg.tol == 0.0
    assert math.isclose(cfg.lam, 2 / job.config["C"] * job.rows
                        / job.config["source_rows"])
    assert all(isinstance(v, (int, float)) for v in job.limits.values())


def test_run_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_window_runs_whole_fits_past_its_length():
    calls = []

    def fit():
        calls.append(time.perf_counter())
        time.sleep(0.05)
        w = np.ones(3) if len(calls) < 3 else np.full(3, np.nan)
        return types.SimpleNamespace(weights=w)

    win = harness.run_window(fit, 0.12)
    assert len(win.fit_s) == len(calls) == 3
    assert win.seconds >= 0.12 and win.seconds >= sum(win.fit_s)
    assert win.nonfinite == 1
    assert win.digests[0] == win.digests[1] != win.digests[2]


def test_result_line_and_checks_last(capsys):
    run = load_run()
    job = harness.resolve(CELLS[0], 2**35 + 3, rows_per_chip=2048, iters=3)
    result = run.run_cell(job, 0.01, trace=False)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["correct"] == harness.passed(result["checks"])
    assert set(result["metrics"]) == {m["name"] for m in job.end_to_end}
    assert set(result["checks"]) == set(job.limits)
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    run.emit(result)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == result
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert all(line.startswith("check ") and " limit " in line
               for line in tail)
