"""Adding a configuration, a traffic mix and a metric is adding files and
``BENCHMARK.json`` entries: no file the benchmark already has changes."""

import hashlib
import json
import time

import harness


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def test_new_cell_from_new_files_only(tiny):
    before = _digests(tiny)
    bench = tiny / "bench"
    (bench / "configs" / "gen64.json").write_text(json.dumps(dict(
        json.loads((bench / "configs" / "gen1000.json").read_text()),
        name="gen64", suite=[{"gen": 64, "mode": "rng"}])))
    (bench / "traffic" / "sweep.small.json").write_text(json.dumps(
        {"entry": "run_sweep", "variants": 256, "backend": "pallas",
         "clamp": True}))
    (bench / "limits" / "gen64.sweep.small.json").write_text(
        (bench / "limits" / "gen1000.sweep.json").read_text())
    (bench / "metrics" / "ops_in_window.py").write_text(
        "def read(ctx):\n    return float(ctx.window.ops)\n")
    spec = json.loads((tiny / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "gen64", "source": "https://example.org",
                            "file": "benchmarks/chip/configs/gen64.json",
                            "reduced": [], "why": "a test configuration"})
    spec["workloads"].append({"name": "gen64.sweep.small", "config": "gen64",
                              "traffic": "sweep.small", "chips": 1,
                              "why": "a test cell"})
    spec["end_to_end"].append({"name": "ops_in_window", "unit": "ops",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["gen64.sweep.small"]})
    for m in spec["end_to_end"]:
        if m["name"] == "cells_per_s":
            m["workloads"].append("gen64.sweep.small")
    (tiny / "BENCHMARK.json").write_text(json.dumps(spec))

    after = _digests(tiny)
    assert all(after[k] == v for k, v in before.items())
    result = harness.run_cell(harness.Spec.load(tiny, bench),
                              "gen64.sweep.small", 11, 0.2, False,
                              time.perf_counter())
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"cells_per_s", "setup_s",
                                      "ops_in_window"}
    assert result["metrics"]["ops_in_window"]["value"] == result["attempted"]
