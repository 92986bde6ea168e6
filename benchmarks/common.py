"""Shared helpers for the benchmark harness.

Benchmarks consume the dry-run artifacts (benchmarks/artifacts/*.json,
produced by ``python -m repro.launch.dryrun``).  If artifacts are missing the
benchmarks fall back to a small set of synthetic profiles so the harness
always runs (clearly labelled ``synthetic``).
"""

from __future__ import annotations

import glob
import os
import time
from typing import Callable, Dict, List, Tuple

from repro.core import WorkloadProfile

ART_DIR = os.path.join(os.path.dirname(__file__), "artifacts")
OUT_DIR = os.path.join(os.path.dirname(__file__), "out")

# Smoke mode (run.py --smoke): tiny synthetic profiles, single repeat, small
# sweep populations -- CI exercises every benchmark function in seconds.
SMOKE = False

# Two-suite split for Table I / Fig. 3 analogues (DESIGN.md §2):
# dense transformers (Koios-like homogeneous compute) vs structured archs.
DENSE_SUITE = ("chatglm3-6b", "qwen3-32b", "qwen1.5-4b", "deepseek-67b")
STRUCTURED_SUITE = ("whisper-medium", "jamba2-mini", "grok-1-314b",
                    "qwen2-moe-a2.7b", "paligemma-3b", "falcon-mamba-7b")


def load_profiles(mesh: str = "pod16x16") -> List[WorkloadProfile]:
    """mesh="" loads every mesh's artifacts."""
    profiles = []
    for path in sorted(glob.glob(os.path.join(ART_DIR, "*.json"))):
        p = WorkloadProfile.load(path)
        if mesh and p.mesh != mesh:
            continue
        profiles.append(p)
    return profiles


def synthetic_profiles() -> List[WorkloadProfile]:
    out = []
    mixes = [
        ("synthetic-compute", 2e14, 5e10, 5e9),
        ("synthetic-memory", 5e12, 8e11, 5e9),
        ("synthetic-collective", 5e12, 5e10, 8e10),
    ]
    for name, flops, hbm, coll in mixes:
        out.append(WorkloadProfile(
            name=name, arch=name, shape="train_4k", mesh="pod16x16",
            flops=flops, bytes_accessed=hbm, hbm_bytes=hbm,
            collective_bytes={"all-reduce": coll}, num_devices=256,
            model_flops=flops * 0.7 * 256, tokens=1 << 20))
    return out


def scaling_profiles(n: int) -> List[WorkloadProfile]:
    """``n`` deterministic synthetic apps spanning the bottleneck spectrum
    (used by the sweep_scaling benchmark and smoke runs)."""
    out = []
    for i in range(n):
        # Rotate dominance between compute / memory / interconnect while
        # varying magnitudes so no two apps score identically.
        f = 1e12 * (10.0 ** (i % 3)) * (1.0 + 0.13 * i)
        h = 1e9 * (10.0 ** ((i + 1) % 3)) * (1.0 + 0.07 * i)
        c = 1e9 * (10.0 ** ((i + 2) % 3)) * (1.0 + 0.11 * i)
        out.append(WorkloadProfile(
            name=f"scale-{i:03d}", arch=f"scale-{i:03d}", shape="train_4k",
            mesh="pod16x16", flops=f, bytes_accessed=h, hbm_bytes=h,
            collective_bytes={"all-reduce": c},
            pod_collective_bytes=0.25 * c if i % 4 == 0 else 0.0,
            num_devices=256, model_flops=f * 0.7 * 256, tokens=1 << 20))
    return out


def profiles_or_synthetic(mesh: str = "pod16x16"):
    if SMOKE:
        return synthetic_profiles(), True
    profs = load_profiles(mesh)
    if profs:
        return profs, False
    return synthetic_profiles(), True


def suites_of(profiles) -> Dict[str, List[str]]:
    names = {p.name for p in profiles}
    dense = [p.name for p in profiles if p.arch in DENSE_SUITE]
    structured = [p.name for p in profiles if p.arch in STRUCTURED_SUITE]
    if not dense or not structured:
        return {"all": sorted(names)}
    return {"dense-transformers": sorted(dense),
            "structured-archs": sorted(structured)}


def timeit(fn: Callable, *args, repeat: int = 5, **kw) -> Tuple[float, object]:
    if SMOKE:
        repeat = 1
    fn(*args, **kw)  # warm
    t0 = time.perf_counter()
    for _ in range(repeat):
        result = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / repeat
    return dt * 1e6, result  # us


def emit(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.2f},{derived}")


def write_out(fname: str, text: str) -> None:
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, fname), "w") as f:
        f.write(text)
