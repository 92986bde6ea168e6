"""Helpers for the benchmark's own tests, run by path on the CPU:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

# Four host devices, so that a cell on a four-chip mesh runs here too.
os.environ["XLA_FLAGS"] = " ".join(filter(None, [
    os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=4"]))

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: Sizes a CPU test run can hold, in place of each traffic's own.
TINY = {"variants": 1024, "steps": 12}


def tiny_copy(tmp_path: Path) -> Path:
    """A copy of the benchmark under ``tmp_path`` (as ``<root>/bench``) with
    every traffic cut to ``TINY`` sizes."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    for path in (bench / "traffic").glob("*.json"):
        t = json.loads(path.read_text())
        for k, v in TINY.items():
            if k in t:
                t[k] = v
        path.write_text(json.dumps(t))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    return tmp_path


@pytest.fixture
def tiny(tmp_path):
    return tiny_copy(tmp_path)


@pytest.fixture(autouse=True)
def cpu_devices(monkeypatch):
    """The harness measures on a TPU alone; these tests drive the rest of a
    run on the CPU's devices."""
    import jax

    import harness

    monkeypatch.setattr(harness, "check_device",
                        lambda chips: jax.devices()[:chips])
