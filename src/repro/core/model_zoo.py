"""Model-zoo profile suites: the registry's configs as measured workloads.

The sweep/frontier/serving layers score *profiles*; until now the only
profiles available without a manual dry-run were the synthetic trio in
``benchmarks/common.py``.  This module closes the measurement loop: every
config in ``repro.configs`` x scenario in {train, serve-prefill,
serve-decode} x a batch/seq grid (``configs/shapes.zoo_shapes``) is lowered
and compiled through the dry-run extraction path
(``launch/extract.run_cell``) and emitted as a ``WorkloadProfile`` suite
that plugs directly into ``run_sweep`` / ``shard_sweep`` /
``frontier_codesign`` / ``CodesignService``.

Extraction is expensive (a full XLA compile per cell), so profiles are
cached as canonical JSON artifacts keyed by a fingerprint of (config,
shape, extraction version):

  * smoke suite (tiny configs, single host device, compiles anywhere) --
    checked in under ``src/repro/core/zoo_cache/`` and doubling as the
    golden files for ``tests/test_model_zoo.py``;
  * full suite (published configs, 16x16 pod mesh, needs the dry-run's
    fake host devices) -- read first from the cells checked in under
    ``src/repro/core/zoo_cache/full/`` (jamba2-mini's twelve), then from
    ``benchmarks/artifacts/zoo/``, regenerated via
    ``python -m repro.core.model_zoo``.

The calibration layer (``calibration_report``) cross-checks the two
step-time code paths on every cell: the batched Eq.1 kernel path
(``sweep.batched_step_time`` -> ``kernels_xp``) against the scalar
roofline path (``roofline.analyze`` -> ``timing``), reporting the
per-cell ratio, dominant-term agreement and worst offenders -- so
congruence scores are anchored to the measured HLO costs, not assumed.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import (
    ShapeSpec,
    ZOO_SCENARIOS,
    scenario_kind,
    zoo_shapes,
)
from repro.core import kernels_xp as K
from repro.core import roofline as R
from repro.core.costs import WorkloadProfile
from repro.core.machine import TPU_V5E, MachineModel
from repro.core.sweep import MachineBatch, ProfileBatch, batched_step_time

#: Bump whenever the extraction math changes shape -- stale caches are
#: detected by fingerprint mismatch and re-extracted (or rejected).
ZOO_EXTRACTION_VERSION = 1

#: The same, for math that only the full suite's pod meshes see; a term of
#: full-suite fingerprints alone, so the smoke goldens keep theirs.  2: scan
#: FLOPs counted per device (they were one global count added to per-device
#: FLOPs), routed-expert FLOPs counted analytically.
ZOO_FULL_EXTRACTION_VERSION = 2

#: Smoke suite: one arch per major family branch (dense attention, SSM),
#: small enough that the fast CI tier recompiles them from scratch.
SMOKE_ARCHS: Tuple[str, ...] = ("chatglm3-6b", "falcon-mamba-7b")

#: Checked-in smoke cache (module-relative: importable from any cwd).
SMOKE_CACHE_DIR = os.path.join(os.path.dirname(__file__), "zoo_cache")

#: Full-suite cells checked in with the program, read before the artifact
#: tree below.
FULL_CHECKED_IN_DIR = os.path.join(SMOKE_CACHE_DIR, "full")

#: Default full-suite cache under the repo's benchmark-artifact tree
#: (anchored to the source tree, like ``benchmarks.common.ART_DIR``, so
#: suite resolution does not depend on the caller's cwd).
FULL_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))),
    "benchmarks", "artifacts", "zoo")

#: Volatile WorkloadProfile fields zeroed/dropped by canonicalization --
#: wall-clock measurements that differ run to run but carry no cost info.
_VOLATILE_META = ("probe_seconds",)


@dataclasses.dataclass(frozen=True)
class ZooCell:
    """One (config, scenario, shape) extraction unit."""

    arch: str
    scenario: str
    shape: ShapeSpec
    smoke: bool

    @property
    def name(self) -> str:
        return f"{self.arch}/{self.shape.name}"

    @property
    def cache_key(self) -> str:
        return f"{self.arch}__{self.shape.name}"

    @property
    def config(self):
        return get_config(self.arch, smoke=self.smoke)


def zoo_cells(
    archs: Optional[Sequence[str]] = None,
    scenarios: Optional[Sequence[str]] = None,
    *,
    smoke: bool = False,
) -> List[ZooCell]:
    """The zoo grid: every (arch x scenario x shape) cell, in stable order."""
    if archs is None:
        archs = SMOKE_ARCHS if smoke else ARCH_IDS
    scenarios = tuple(scenarios) if scenarios is not None else ZOO_SCENARIOS
    for s in scenarios:
        scenario_kind(s)  # validates the name
    return [
        ZooCell(arch=a, scenario=s, shape=shape, smoke=smoke)
        for a in archs
        for s in scenarios
        for shape in zoo_shapes(s, smoke=smoke)
    ]


# --------------------------------------------------------------------------- #
# Fingerprints + canonical JSON (the golden-file contract)
# --------------------------------------------------------------------------- #


def cell_fingerprint(cell: ZooCell) -> str:
    """Digest of everything that determines a cell's extracted costs.

    Covers the full config (``repr`` of the frozen dataclass is
    deterministic), the shape, the scenario and the extraction version --
    so a cached profile is provably stale the moment any input changes.
    """
    inputs = {
        "version": ZOO_EXTRACTION_VERSION,
        "arch": cell.arch,
        "scenario": cell.scenario,
        "smoke": cell.smoke,
        "config": repr(cell.config),
        "shape": dataclasses.asdict(cell.shape),
    }
    if not cell.smoke:
        inputs["full_version"] = ZOO_FULL_EXTRACTION_VERSION
    payload = json.dumps(inputs, sort_keys=True)
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def canonical_profile_dict(profile: WorkloadProfile) -> dict:
    """JSON form with volatile wall-clock fields zeroed.

    ``compile_seconds``/``probe_seconds`` differ between byte-identical
    extractions; everything else is a deterministic function of (config,
    shape, jax version), which is what the golden tests pin.
    """
    d = profile.to_json()
    d["compile_seconds"] = 0.0
    d["meta"] = {k: v for k, v in d.get("meta", {}).items()
                 if k not in _VOLATILE_META}
    return d


def canonical_profile_bytes(profile: WorkloadProfile) -> bytes:
    return (json.dumps(canonical_profile_dict(profile), indent=1,
                       sort_keys=True) + "\n").encode()


def cache_path(cell: ZooCell, cache_dir: str) -> str:
    return os.path.join(cache_dir, cell.cache_key + ".json")


def default_cache_dirs(smoke: bool) -> Tuple[str, ...]:
    """Where a suite's cells are looked up, in order; extraction writes to
    the last."""
    return (SMOKE_CACHE_DIR,) if smoke else (FULL_CHECKED_IN_DIR,
                                              FULL_CACHE_DIR)


# --------------------------------------------------------------------------- #
# Extraction (lazy imports: compiling pulls in jax; loading does not)
# --------------------------------------------------------------------------- #


def extract_profile(cell: ZooCell, *, calibrate: Optional[bool] = None,
                    verbose: bool = False) -> WorkloadProfile:
    """Compile one zoo cell and extract its WorkloadProfile.

    Smoke cells compile on a single-host-device (1, 1) mesh, so they run
    in any process; full cells need the 16x16 pod mesh and therefore the
    dry-run's 256+ fake host devices (``launch.xla_flags``).  Depth-probe
    calibration defaults off for smoke (unrolled tiny stacks need none)
    and on for full configs (scan-over-layers under-counting).
    """
    import jax

    from repro.launch import extract as EX
    from repro.launch import mesh as MESH
    from repro.launch import xla_flags

    cfg = cell.config
    if cell.smoke:
        mesh = MESH.make_mesh((1, 1), ("data", "model"))
        mesh_label = "host1x1"
    else:
        xla_flags.ensure_host_device_count(256)
        mesh = MESH.make_production_mesh(multi_pod=False)
        mesh_label = "pod16x16"
    if calibrate is None:
        calibrate = not cell.smoke
    profile = EX.run_cell(
        cfg, cell.shape, mesh, mesh_label, EX.default_variant(cfg), None,
        multi_pod=False, verbose=verbose, calibrate=calibrate)
    profile.meta.update(
        scenario=cell.scenario,
        suite="zoo-smoke" if cell.smoke else "zoo",
        fingerprint=cell_fingerprint(cell),
        extraction_version=ZOO_EXTRACTION_VERSION,
        jax_version=jax.__version__,
    )
    return profile


def profiles_from_configs(
    archs: Optional[Sequence[str]] = None,
    scenarios: Optional[Sequence[str]] = None,
    *,
    smoke: bool = False,
    cache_dir: Optional[str] = None,
    refresh: bool = False,
    extract_missing: bool = True,
    calibrate: Optional[bool] = None,
    max_cells: Optional[int] = None,
    verbose: bool = False,
) -> List[WorkloadProfile]:
    """The zoo bridge: registry configs -> measured WorkloadProfile suite.

    For every cell of ``zoo_cells(archs, scenarios, smoke=...)``: load the
    cached profile if its fingerprint matches the cell's current inputs,
    otherwise re-extract (compile) and re-cache.  ``extract_missing=False``
    makes missing/stale cells a hard error instead -- the cache-only mode
    CI and the CLIs use so a sweep never triggers a surprise zoo compile.
    """
    dirs = (cache_dir,) if cache_dir else default_cache_dirs(smoke)
    cells = zoo_cells(archs, scenarios, smoke=smoke)
    if max_cells is not None:
        cells = cells[:max_cells]
    out: List[WorkloadProfile] = []
    for cell in cells:
        path = cache_path(cell, dirs[-1])
        found = stale = None
        for d in () if refresh else dirs:
            p = cache_path(cell, d)
            if os.path.exists(p):
                profile = WorkloadProfile.load(p)
                if profile.meta.get("fingerprint") == cell_fingerprint(cell):
                    found = profile
                    break
                stale = stale or p
        if found is not None:
            out.append(found)
            continue
        if stale and not extract_missing:
            raise RuntimeError(
                f"zoo cache entry {stale} is stale (config/shape/"
                f"extraction-version changed since it was written); "
                f"regenerate with: PYTHONPATH=src python -m "
                f"repro.core.model_zoo {'--smoke ' if smoke else ''}"
                f"--refresh")
        if not refresh and not extract_missing:
            raise RuntimeError(
                f"zoo cache entry {path} is missing; extract the suite "
                f"first: PYTHONPATH=src python -m repro.core.model_zoo"
                f"{' --smoke' if smoke else ''}")
        if not extract_missing:
            raise RuntimeError(
                f"zoo cache entry {path} needs re-extraction but "
                f"extract_missing=False")
        if verbose:
            print(f"== zoo extract {cell.name} [{cell.scenario}] ==",
                  flush=True)
        profile = extract_profile(cell, calibrate=calibrate, verbose=verbose)
        os.makedirs(dirs[-1], exist_ok=True)
        with open(path, "wb") as f:
            f.write(canonical_profile_bytes(profile))
        out.append(WorkloadProfile.from_json(canonical_profile_dict(profile)))
    return out


# --------------------------------------------------------------------------- #
# Suite names (the ONE grammar shared by CLIs, CodesignSpec and the service)
# --------------------------------------------------------------------------- #

SUITE_BASES = ("zoo", "zoo-smoke")


class ZooSuite(NamedTuple):
    smoke: bool
    scenario: Optional[str]
    arch: Optional[str]      # one registry architecture, or all of them


def parse_suite(suite: str) -> ZooSuite:
    """``zoo[@arch][:scenario]`` | ``zoo-smoke[@arch][:scenario]``."""
    if not isinstance(suite, str):
        raise ValueError(f"suite must be a string, got {type(suite).__name__}")
    head, sep, scenario = suite.partition(":")
    base, at, arch = head.partition("@")
    if base not in SUITE_BASES:
        raise ValueError(
            f"unknown suite {suite!r}; expected "
            f"{' | '.join(SUITE_BASES)} with an optional @arch of the "
            f"registry and an optional :scenario of {ZOO_SCENARIOS}, or a "
            f"generated suite gen:<count>[:seed=<int>][:mode=halton|rng]")
    if at and arch not in ARCH_IDS:
        raise ValueError(f"unknown architecture {arch!r} in suite "
                         f"{suite!r}; have {ARCH_IDS}")
    if sep and scenario not in ZOO_SCENARIOS:
        raise ValueError(
            f"unknown zoo scenario {scenario!r} in suite {suite!r}; "
            f"have {ZOO_SCENARIOS}")
    return ZooSuite(base == "zoo-smoke", scenario if sep else None,
                    arch if at else None)


def validate_suite_name(suite: Optional[str]) -> None:
    """Shared validation hook (``CodesignSpec.validate`` and CLIs).

    Dispatches between the zoo grammar and the generated-suite grammar
    (``repro.core.genload``) so every caller of the ONE validation path
    accepts ``gen:<count>`` suites for free.
    """
    if suite is None:
        return
    from repro.core.genload import is_gen_suite, parse_gen_suite
    if is_gen_suite(suite):
        parse_gen_suite(suite)
    else:
        parse_suite(suite)


def resolve_suite(
    suite: str,
    *,
    cache_dir: Optional[str] = None,
    extract_missing: Optional[bool] = None,
) -> List[WorkloadProfile]:
    """Suite name -> profile list, cache-first.

    Smoke suites extract on a cache miss (tiny configs, seconds each);
    full suites are cache-only by default -- a missing artifact raises
    with the regeneration command rather than starting a multi-minute
    pod-mesh compile inside a sweep.  Generated suites (``gen:<count>``,
    see ``repro.core.genload``) regenerate deterministically from the
    suite string alone and never touch the cache.
    """
    from repro.core.genload import is_gen_suite, resolve_gen_suite
    if is_gen_suite(suite):
        return resolve_gen_suite(suite)
    smoke, scenario, arch = parse_suite(suite)
    if extract_missing is None:
        extract_missing = smoke
    return profiles_from_configs(
        archs=(arch,) if arch else None,
        scenarios=(scenario,) if scenario else None,
        smoke=smoke, cache_dir=cache_dir, extract_missing=extract_missing)


# --------------------------------------------------------------------------- #
# Calibration: Eq.1 batched kernels vs the scalar roofline path
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class CalibrationCell:
    name: str
    scenario: str
    eq1_s: float          # batched kernel path (sweep.batched_step_time)
    roofline_s: float     # scalar path (roofline.analyze)
    ratio: float          # eq1_s / roofline_s
    dominant_eq1: str
    dominant_roofline: str

    @property
    def agree(self) -> bool:
        return self.dominant_eq1 == self.dominant_roofline


@dataclasses.dataclass(frozen=True)
class CalibrationReport:
    """Per-cell agreement between the two step-time code paths.

    Both paths consume the same measured HLO costs; the batched path is
    the kernel layer every sweep/service request runs through, the scalar
    path is the roofline module the dry-run reports with.  Ratio ~= 1 and
    matching dominant terms on every cell is the pinned invariant
    (tests/test_model_zoo.py).
    """

    machine: str
    backend: str
    timing_model: str
    cells: Tuple[CalibrationCell, ...]

    @property
    def dominant_agreement(self) -> float:
        if not self.cells:
            return math.nan
        return sum(c.agree for c in self.cells) / len(self.cells)

    def worst_offenders(self, top_k: int = 5) -> List[CalibrationCell]:
        """Cells ranked by |log ratio| (worst Eq.1-vs-roofline mismatch)."""
        def badness(c: CalibrationCell) -> float:
            if not (math.isfinite(c.ratio) and c.ratio > 0):
                return math.inf
            return abs(math.log(c.ratio))
        return sorted(self.cells, key=badness, reverse=True)[:top_k]

    def to_json(self, top_k: Optional[int] = None) -> dict:
        return {
            "machine": self.machine,
            "backend": self.backend,
            "timing_model": self.timing_model,
            "num_cells": len(self.cells),
            "dominant_agreement": self.dominant_agreement,
            "worst_offenders": [c.name for c in self.worst_offenders()],
            "cells": [dataclasses.asdict(c)
                      for c in self.cells[:top_k or len(self.cells)]],
        }

    def markdown(self, top_k: Optional[int] = None) -> str:
        lines = [
            f"### Zoo calibration -- Eq.1 kernels vs roofline "
            f"({self.machine}, {self.backend} backend, "
            f"{self.timing_model} timing)",
            "",
            f"{len(self.cells)} cells, dominant-term agreement "
            f"{100.0 * self.dominant_agreement:.1f}%",
            "",
            "| cell | scenario | Eq.1 (s) | roofline (s) | ratio "
            "| dominant (Eq.1 / roofline) |",
            "|---|---|---|---|---|---|",
        ]
        shown = self.cells[:top_k or len(self.cells)]
        for c in shown:
            mark = "" if c.agree else " **!=**"
            lines.append(
                f"| {c.name} | {c.scenario} | {c.eq1_s:.3e} "
                f"| {c.roofline_s:.3e} | {c.ratio:.4f} "
                f"| {c.dominant_eq1} / {c.dominant_roofline}{mark} |")
        if len(shown) < len(self.cells):
            lines.append(f"| ... {len(self.cells) - len(shown)} more |  "
                         f"|  |  |  |  |")
        worst = self.worst_offenders()
        if worst:
            lines += ["", "Worst offenders (by |log ratio|): "
                      + ", ".join(f"{c.name} ({c.ratio:.4f})"
                                  for c in worst)]
        return "\n".join(lines)


def calibration_report(
    profiles: Sequence[WorkloadProfile],
    machine: MachineModel = TPU_V5E,
    *,
    backend: Optional[str] = None,
    timing_model: str = "serial",
) -> CalibrationReport:
    """Cross-check Eq.1 batched step times against scalar roofline times.

    Step times on the batched side come from the selected kernel backend
    (the exact code every sweep runs); dominant terms on both sides come
    from the reference numpy kernels / ``timing`` module respectively.
    """
    profiles = list(profiles)
    pb = ProfileBatch.from_profiles(profiles)
    mb = MachineBatch.from_models([machine])
    eq1 = batched_step_time(pb, mb, timing_model=timing_model,
                            backend=backend)[:, 0]
    tc, tm, ti = K.scaled_times(np, pb.arrays(), mb.arrays())
    terms = np.stack([tc[:, 0], tm[:, 0], ti[:, 0]])
    term_names = ("compute", "memory", "interconnect")
    cells = []
    for i, p in enumerate(profiles):
        rep = R.analyze(p, machine)
        roofline_s = (rep.step_time_serial_s if timing_model == "serial"
                      else rep.step_time_overlap_s)
        ratio = (float(eq1[i]) / roofline_s if roofline_s > 0 else math.nan)
        cells.append(CalibrationCell(
            name=p.name,
            scenario=str(p.meta.get("scenario", p.step_kind)),
            eq1_s=float(eq1[i]),
            roofline_s=roofline_s,
            ratio=ratio,
            dominant_eq1=term_names[int(np.argmax(terms[:, i]))],
            dominant_roofline=rep.dominant,
        ))
    be = K.get_backend(backend)
    return CalibrationReport(
        machine=machine.name,
        backend=be.name,
        timing_model=timing_model,
        cells=tuple(cells),
    )


# --------------------------------------------------------------------------- #
# CLI: extract/refresh the caches and print the calibration table
# --------------------------------------------------------------------------- #


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Extract the model-zoo profile suite and report "
                    "Eq.1-vs-roofline calibration.")
    ap.add_argument("--smoke", action="store_true",
                    help="smoke suite (tiny configs, single device, checked-"
                         "in cache) instead of the full registry")
    ap.add_argument("--arch", action="append", help="arch id(s); default all")
    ap.add_argument("--scenario", action="append", choices=ZOO_SCENARIOS,
                    help="scenario(s); default all")
    ap.add_argument("--cache-dir", default=None,
                    help="profile cache directory (default: the suite's "
                         "canonical cache)")
    ap.add_argument("--refresh", action="store_true",
                    help="re-extract even when the cached fingerprint "
                         "matches")
    ap.add_argument("--max-cells", type=int, default=None, metavar="N",
                    help="extract at most N cells (bounded CI runs)")
    ap.add_argument("--no-calibrate", action="store_true",
                    help="skip depth-probe cost calibration (full suite "
                         "defaults to calibrated)")
    ap.add_argument("--out", default=None,
                    help="write the calibration report to <out>.md/.json "
                         "(default: stdout)")
    args = ap.parse_args(argv)

    if not args.smoke:
        # Must land before jax initializes; the extraction itself verifies
        # the count via ensure_host_device_count and fails loudly if not.
        from repro.launch import xla_flags
        xla_flags.request_host_devices(512)

    profiles = profiles_from_configs(
        archs=tuple(args.arch) if args.arch else None,
        scenarios=tuple(args.scenario) if args.scenario else None,
        smoke=args.smoke,
        cache_dir=args.cache_dir,
        refresh=args.refresh,
        calibrate=False if args.no_calibrate else None,
        max_cells=args.max_cells,
        verbose=True,
    )
    report = calibration_report(profiles)
    md = report.markdown()
    if args.out:
        with open(args.out + ".md", "w") as f:
            f.write(md + "\n")
        with open(args.out + ".json", "w") as f:
            json.dump(report.to_json(), f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.out}.{{md,json}}")
    else:
        print(md)
    print(f"{len(profiles)} profiles; dominant-term agreement "
          f"{100.0 * report.dominant_agreement:.1f}%")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
