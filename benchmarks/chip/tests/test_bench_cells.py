"""Every cell runs end to end at a tiny size on the CPU (the Pallas kernel
interpreted), and the measurement command refuses to run off a TPU."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import harness

CELLS = [w["name"] for w in json.loads(
    (harness.HERE.parents[1] / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_tiny_on_cpu(tiny, monkeypatch, cell, trace):
    if trace:
        # the CPU has no device plane: read what the host spans give
        listed = harness.Spec.metrics_for
        monkeypatch.setattr(harness.Spec, "metrics_for", lambda *a: [
            m for m in listed(*a) if m["source"] != "device_trace"])
    spec = harness.Spec.load(tiny, tiny / "bench")
    result = harness.run_cell(spec, cell, 2**32 + 9, 0.2, bool(trace),
                              time.perf_counter())
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    want = {m["name"] for m in spec.metrics_for(cell, bool(trace))}
    assert set(result["metrics"]) == want
    if trace:
        assert "breakdown" in result and "window_s" in result["device"]
    else:
        assert all(v["value"] > 0 for v in result["metrics"].values())


def test_listed_metric_reading_nothing_fails_the_run(tiny):
    """A traced run on the CPU finds no device op: the device metrics the
    cell lists read nothing, and the run fails instead of leaving them
    out."""
    spec = harness.Spec.load(tiny, tiny / "bench")
    with pytest.raises(harness.SetupError, match="nothing to read"):
        harness.run_cell(spec, "zoo128.mega", 5, 0.2, True,
                         time.perf_counter())


def test_span_target_missing_fails_the_run(tiny):
    """A span around a function the program no longer has fails the run."""
    metric = tiny / "bench" / "metrics" / "pareto_share.py"
    metric.write_text(metric.read_text().replace(
        "pareto_front_indices_3d", "pareto_front_indices_4d"))
    spec = harness.Spec.load(tiny, tiny / "bench")
    with pytest.raises(harness.SetupError, match="cannot wrap"):
        harness.run_cell(spec, "zoo128.mega", 5, 0.2, True,
                         time.perf_counter())


def _bench(cwd, *args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/bench.py", *args], cwd=cwd,
        env=env, capture_output=True, text=True, timeout=300)


def test_off_tpu_exits_nonzero_and_names_the_platform():
    root = harness.HERE.parents[1]
    p = _bench(root, "--workload", "zoo128.mega", "--seed", str(2**33 + 1),
               "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert p.stdout.strip() == ""


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's own
    files has no system to measure."""
    root = harness.HERE.parents[1]
    shutil.copy(root / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(tmp_path, "--workload", "zoo128.mega", "--seed", "3",
               "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
