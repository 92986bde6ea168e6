"""One run of one cell: set up, measure a window, check, report.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in ``configs/<config>.json``, its
traffic in ``traffic/<traffic>.json``, the entry module that traffic names
in ``entries/<entry>.py``, the limits of its checks in
``limits/<cell>.json`` and each metric it reports in
``metrics/<metric>.py``.  Adding a cell, a configuration or a metric adds
files and entries; no file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent


class SetupError(RuntimeError):
    """The run cannot measure: no chip, too few chips, or a broken cell."""


@dataclasses.dataclass
class Spec:
    """The benchmark as ``BENCHMARK.json`` describes it, plus where its
    files live (``bench_dir``, normally this directory)."""

    data: dict
    bench_dir: Path

    @classmethod
    def load(cls, root: Path, bench_dir: Path = HERE) -> "Spec":
        path = root / "BENCHMARK.json"
        if not path.is_file():
            raise SetupError(f"no BENCHMARK.json at {root}")
        return cls(json.loads(path.read_text()), bench_dir)

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise SetupError(f"no workload {name!r} in BENCHMARK.json; have "
                         f"{[w['name'] for w in self.data['workloads']]}")

    def json_file(self, kind: str, name: str) -> dict:
        path = self.bench_dir / kind / f"{name}.json"
        if not path.is_file():
            raise SetupError(f"missing {path}")
        return json.loads(path.read_text())

    def metrics_for(self, cell: str, trace: bool) -> List[dict]:
        group = self.data["per_layer" if trace else "end_to_end"]
        return [m for m in group
                if "workloads" not in m or cell in m["workloads"]]

    def module(self, kind: str, name: str):
        path = self.bench_dir / kind / f"{name}.py"
        if not path.is_file():
            raise SetupError(f"missing {path}")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


@dataclasses.dataclass
class Run:
    """What an entry module is handed: the cell's files and the run's
    arguments."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    devices: list


@dataclasses.dataclass
class Window:
    """The measured window: whole operations, back to back, from its start
    to the end of the operation that was running when ``seconds`` ran out."""

    seconds: float
    ops: int
    counts: Dict[str, float]


def check_device(chips: int) -> list:
    """The cell's chips; a run off a TPU, on too few chips or with the
    pallas kernel interpreted cannot measure."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SetupError(f"no TPU: jax found platform {platform!r} with "
                         f"{len(devices)} device(s)")
    if len(devices) < chips:
        raise SetupError(f"the cell needs {chips} chips; jax found "
                         f"{len(devices)}")
    from repro.core import get_backend

    if get_backend("pallas").interpret:
        raise SetupError("the pallas backend chose interpret mode on a TPU")
    return devices[:chips]


def measure(cell, seconds: float) -> Window:
    """Run ``cell.op()`` back to back until ``seconds`` have passed, always
    ending with a whole operation."""
    start = time.perf_counter()
    ops = 0
    while True:
        cell.op()
        ops += 1
        if time.perf_counter() - start >= seconds:
            break
    return Window(time.perf_counter() - start, ops, cell.counts(ops))


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def judge(cell, limits: dict):
    """Compare every answer of the window with the reference; returns the
    worst reading of each number, the answers attempted and those failed."""
    readings = cell.judge()
    if not readings:
        raise SetupError("the window produced no answer to check")
    names = sorted(set().union(*readings))
    missing = set(names) - set(limits)
    if missing:
        raise SetupError(f"no limit for {sorted(missing)}")
    # a reading that is not finite prints as the largest float
    worst = {k: min(max(r[k] for r in readings if k in r),
                    sys.float_info.max) for k in names}
    failed = sum(any(not r[k] <= limits[k] for k in r) for r in readings)
    return worst, len(readings), failed


def report_metrics(metrics: List[dict], modules: list, ctx) -> dict:
    """Every metric the cell lists, read.  A reader that finds nothing in
    a cell that lists its metric fails the run: the program no longer
    has what the metric reads (a kernel renamed, a span's function moved),
    and the metric's ``workloads`` in ``BENCHMARK.json`` have to say so."""
    out = {}
    for m, mod in zip(metrics, modules):
        value = mod.read(ctx)
        if value is None:
            raise SetupError(f"metric {m['name']}: nothing to read in "
                             f"{ctx.run.cell['name']}")
        if not math.isfinite(value):
            raise SetupError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


@dataclasses.dataclass
class Context:
    """What a metric reader gets: the run, the window, the set-up time and,
    in a traced run, the reduced trace."""

    run: Run
    cell: object
    window: Window
    setup_s: float
    trace: Optional[object] = None


def run_cell(spec: Spec, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, log=sys.stderr):
    """One run; returns the result object the last line prints."""
    w = spec.cell(name)
    config = spec.json_file("configs", w["config"])
    traffic = spec.json_file("traffic", w["traffic"])
    limits = spec.json_file("limits", name)
    entry = spec.module("entries", traffic["entry"])
    metrics = spec.metrics_for(name, trace)
    metric_mods = [spec.module("metrics", m["name"]) for m in metrics]

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = check_device(int(w["chips"]))
    t_devices = time.perf_counter()
    run = Run(w, config, traffic, seed, devices)
    cell = entry.Cell(run)
    t_inputs = time.perf_counter()
    cell.warm_up()
    t_warm = time.perf_counter()
    setup_s = t_warm - t_start
    print(f"set-up {setup_s:.3f} s: start and devices "
          f"{t_devices - t_start:.3f} s, inputs {t_inputs - t_devices:.3f} s, "
          f"warm-up {t_warm - t_inputs:.3f} s", file=log, flush=True)

    reduced = None
    if trace:
        import tracing

        spans = {}
        for mod in metric_mods:
            spans.update(getattr(mod, "SPANS", {}))
        with tracing.spans_installed(spans), \
                tracing.Recording() as rec, \
                jax.profiler.TraceAnnotation(tracing.WINDOW):
            window = measure(cell, seconds)
        reduced = rec.trace
        print(f"trace {rec.trace_bytes} bytes", file=log, flush=True)
    else:
        window = measure(cell, seconds)
    print(f"window {window.seconds:.3f} s, {window.ops} operations",
          file=log, flush=True)
    peak = memory_peak(devices)

    t_check = time.perf_counter()
    worst, attempted, failed = judge(cell, limits)
    print(f"checked {attempted} answers in "
          f"{time.perf_counter() - t_check:.1f} s", file=log, flush=True)

    ctx = Context(run, cell, window, setup_s, reduced)
    values = report_metrics(metrics, metric_mods, ctx)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": peak}
    result = {"correct": failed == 0, "attempted": attempted,
              "failed": failed, "metrics": values, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["checks"] = {k: {"value": v, "limit": limits[k]}
                        for k, v in worst.items()}
    for k, v in worst.items():
        print(f"check {k} {v!r} limit {limits[k]!r}", file=log, flush=True)
    return result


