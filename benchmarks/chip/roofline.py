"""Operations and bytes of the congruence pass, and the chip's peaks.

The work is counted from what the cell's correctness check demands, not
from how the program computes it: the inputs read once, the outputs the
check compares written once, and the arithmetic of Eq. 1 once per
(app, variant) cell.
"""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent

#: Arithmetic per (app, variant) cell of one Eq. 1 pass, counting each
#: add, subtract, multiply, divide, square root, compare, select, min and
#: max as one operation: the three raw terms (compute 1, memory 1,
#: interconnect 4: two divides, a select, an add), three scalings, two adds
#: for gamma, the shared ``gamma - beta`` with its zero test and guard (3),
#: per subsystem the idealized term, two adds, ``alpha - beta``, a divide,
#: ``1 - x``, the zero select and the clip's min and max (9, three times),
#: and the L2 aggregate (three squares, two adds, a square root).
FLOPS_PER_CELL = 1 + 1 + 4 + 3 + 2 + 3 + 3 * 9 + 6

F32 = 4
PROFILE_ROWS = 7    # six profile fields and the target beta
MACHINE_ROWS = 8
OUTPUT_ROWS = 8     # gamma, three alphas, three scores, the aggregate


def full_pass(apps: int, variants: int) -> tuple:
    """``(flops, bytes)`` of a pass whose check compares all 8 outputs."""
    cells = apps * variants
    return (FLOPS_PER_CELL * cells,
            F32 * (PROFILE_ROWS * apps + MACHINE_ROWS * variants
                   + OUTPUT_ROWS * cells))


def stats_pass(apps: int, variants: int) -> tuple:
    """``(flops, bytes)`` of a pass whose check compares only the
    per-variant suite means and the per-app minima and their indices."""
    return (FLOPS_PER_CELL * apps * variants,
            F32 * (PROFILE_ROWS * apps + MACHINE_ROWS * variants
                   + variants + 2 * apps))


def peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r} in peaks.json")
    return table[device_kind]


def least_seconds(flops: float, bytes_: float, device_kind: str) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    p = peaks(device_kind)
    compute = flops / p["flops_per_s"]
    memory = bytes_ / p["hbm_bytes_per_s"]
    return (memory, "hbm") if memory >= compute else (compute, "compute")
