#!/usr/bin/env python3
"""Measure one cell of the on-chip benchmark once.

    python3 benchmarks/chip/bench.py --workload zoo128.mega --seed 7 \\
        --seconds 10 --trace 0

Run from the root of a checkout on a machine that holds the chips the cell
asks for.  Set-up (jax and TPU start, inputs from the seed, one warm-up
operation, programs from the compile cache in ``<checkout>/.jax_cache``)
is timed as ``setup_s``; then the cell's operation runs back to back for
``--seconds``.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` records a profiler trace of the window and reports the
per-layer metrics instead.  After the window every answer is compared
with the float64 reference in ``reference/``; each number compared is
printed beside its limit as the last lines of standard error, and the
last line of standard output is the result as one JSON object.

Exit status 0 means a result was printed (``correct`` may still be
false); 2 means the run could not measure (no TPU, too few chips, a
missing file) and printed no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    try:
        spec = harness.Spec.load(ROOT)
        spec.cell(args.workload)
        if not (ROOT / "src" / "repro").is_dir():
            raise harness.SetupError(
                f"the system under test is not at {ROOT / 'src'}")
        sys.path.insert(0, str(ROOT / "src"))
        # The compile cache lives at a fixed path inside the checkout.
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
        result = harness.run_cell(spec, args.workload, args.seed,
                                  args.seconds, bool(args.trace), T_START)
    except harness.SetupError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
