#!/usr/bin/env python3
"""The control of a cell: the reference, one precision below the one the
cell's program computes in, answering in the program's place.

    python3 benchmarks/chip/control.py --workload zoo128.mega --seeds 1 2 3

For each seed the cell's inputs are built as a run builds them, the
reference computed in the entry's ``CONTROL_DTYPE`` (bfloat16 for the
float32 sweeps, float32 for the float64 co-design) gives the answers, and
they are judged as a run's answers are.  Every number is printed beside
its limit; a sound limit makes every seed come out not correct.  The
control never runs the program, and the benchmark's runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def dtype_of(name: str):
    import ml_dtypes
    import numpy as np

    return ml_dtypes.bfloat16 if name == "bfloat16" else np.dtype(name).type


def control_judged(spec, name: str, seed: int):
    """The control of cell ``name`` on ``seed``, judged as a run's answers
    are: the worst reading of each number, and the cell's limits."""
    import harness

    w = spec.cell(name)
    traffic = spec.json_file("traffic", w["traffic"])
    entry = spec.module("entries", traffic["entry"])
    run = harness.Run(w, spec.json_file("configs", w["config"]), traffic,
                      seed, [])
    cell = entry.Cell(run)
    cell.answers = cell.control(dtype_of(entry.CONTROL_DTYPE))
    limits = spec.json_file("limits", name)
    worst, _, _ = harness.judge(cell, limits)
    return worst, limits


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import harness

    spec = harness.Spec.load(ROOT)
    for seed in args.seeds:
        t0 = time.perf_counter()
        worst, limits = control_judged(spec, args.workload, seed)
        failed = [k for k, v in worst.items() if not v <= limits[k]]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": not failed,
                          "failed": failed,
                          "checks": {k: {"value": v, "limit": limits[k]}
                                     for k, v in worst.items()},
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
