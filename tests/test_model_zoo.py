"""Model-zoo measurement path: golden profiles, calibration, invariants.

Three layers of protection for the zoo bridge (core/model_zoo.py):

  1. golden regression -- the smoke suite is re-extracted from scratch and
     compared against the checked-in JSON goldens in
     ``src/repro/core/zoo_cache/``; any change to the extraction math makes
     this fail byte-for-byte (the comparison is gated on the jax version
     recorded in the golden, with a structural fallback across versions);
  2. calibration -- every cached zoo cell agrees between the batched Eq.1
     kernel path and the scalar roofline path (ratio ~ 1, dominant term
     matches);
  3. property tests -- roofline invariants over randomized profiles and
     machines (dominant == argmax, step time monotone in every rate,
     useful_ratio <= 1 whenever HLO FLOPs cover the model FLOPs, JSON
     round-trips).  Uses hypothesis when installed, otherwise a seeded
     numpy sampling loop with the same predicates (the container image
     ships no hypothesis; CI installs it via the dev extras).
"""

import dataclasses
import json
import math
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import model_zoo as MZ
from repro.core.costs import WorkloadProfile
from repro.core.machine import ALL_SUBSYSTEMS, TPU_V5E, VARIANTS
from repro.core.roofline import RooflineReport, analyze
from repro.core.spec import CodesignSpec
from repro.core.sweep import run_sweep
from repro.core.timing import step_time, subsystem_times
from repro.launch import xla_flags

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")

from conftest import floats_property


# --------------------------------------------------------------------------- #
# Grid + suite-name grammar (pure, no compiles)
# --------------------------------------------------------------------------- #


def test_zoo_cell_counts():
    full = MZ.zoo_cells()
    smoke = MZ.zoo_cells(smoke=True)
    assert len(full) >= 100, len(full)          # acceptance: 100+ real cells
    assert len(smoke) >= 6, len(smoke)
    # every (arch, scenario) pair of the registry is covered
    assert {(c.arch, c.scenario) for c in full} == {
        (a, s) for a in MZ.ARCH_IDS for s in MZ.ZOO_SCENARIOS}
    # cache keys are unique (one artifact per cell)
    assert len({c.cache_key for c in full}) == len(full)


def test_zoo_full_shapes_fit_production_mesh():
    # The full suite compiles on the 16x16 pod mesh: every global batch
    # must split across the 16-way data axis.
    for cell in MZ.zoo_cells():
        assert cell.shape.global_batch % 16 == 0, cell.name
        assert cell.shape.seq_len % 16 == 0, cell.name


def test_suite_name_grammar():
    assert MZ.parse_suite("zoo") == (False, None, None)
    assert MZ.parse_suite("zoo-smoke") == (True, None, None)
    assert MZ.parse_suite("zoo:train") == (False, "train", None)
    assert MZ.parse_suite("zoo-smoke:serve-decode") == (
        True, "serve-decode", None)
    assert MZ.parse_suite("zoo@jamba2-mini") == (False, None, "jamba2-mini")
    assert MZ.parse_suite("zoo-smoke@chatglm3-6b:train") == (
        True, "train", "chatglm3-6b")
    for bad in ("zoop", "zoo:", "zoo:bogus", "smoke", "zoo-smoke:train:x",
                "zoo@", "zoo@bogus", "zoop@jamba2-mini",
                "zoo@jamba2-mini:bogus"):
        with pytest.raises(ValueError):
            MZ.parse_suite(bad)
    # the ONE validation path: CodesignSpec.validate delegates here
    CodesignSpec(suite="zoo:serve-prefill").validate()
    with pytest.raises(ValueError):
        CodesignSpec(suite="zoo:bogus").validate()


def test_cell_fingerprint_tracks_inputs():
    a, b = MZ.zoo_cells(smoke=True)[:2]
    assert MZ.cell_fingerprint(a) != MZ.cell_fingerprint(b)
    # same cell -> same fingerprint (deterministic)
    assert MZ.cell_fingerprint(a) == MZ.cell_fingerprint(a)


def test_full_suite_is_cache_only(tmp_path):
    with pytest.raises(RuntimeError, match="model_zoo"):
        MZ.resolve_suite("zoo", cache_dir=str(tmp_path))


def test_full_suite_entry_of_older_math_is_stale(tmp_path):
    """A full-suite entry fingerprinted before ``ZOO_FULL_EXTRACTION_VERSION``
    (falcon-mamba-7b's config is unchanged since, only its pod-scale scan
    FLOPs moved) is refused as stale, while the smoke fingerprints keep no
    such term."""
    import hashlib

    cell = MZ.zoo_cells(archs=("falcon-mamba-7b",))[0]
    old_inputs = {
        "version": MZ.ZOO_EXTRACTION_VERSION, "arch": cell.arch,
        "scenario": cell.scenario, "smoke": False,
        "config": repr(cell.config),
        "shape": dataclasses.asdict(cell.shape)}
    old = hashlib.sha256(json.dumps(old_inputs, sort_keys=True)
                         .encode()).hexdigest()[:16]
    assert old != MZ.cell_fingerprint(cell)
    smoke = MZ.zoo_cells(archs=("falcon-mamba-7b",), smoke=True)[0]
    golden = json.load(open(MZ.cache_path(smoke, MZ.SMOKE_CACHE_DIR)))
    assert golden["meta"]["fingerprint"] == MZ.cell_fingerprint(smoke)
    golden["meta"]["fingerprint"] = old
    with open(MZ.cache_path(cell, str(tmp_path)), "w") as f:
        json.dump(golden, f)
    with pytest.raises(RuntimeError, match="stale"):
        MZ.profiles_from_configs(
            archs=(cell.arch,), scenarios=(cell.scenario,),
            cache_dir=str(tmp_path), extract_missing=False, max_cells=1)


def test_single_arch_suite_reads_checked_in_cells():
    """``zoo@jamba2-mini`` resolves, cache-only, to the twelve published-
    width cells checked in under ``zoo_cache/full/``, each fingerprint
    matching the current config, shape and extraction version."""
    profiles = MZ.resolve_suite("zoo@jamba2-mini", extract_missing=False)
    cells = MZ.zoo_cells(archs=("jamba2-mini",))
    assert len(profiles) == len(cells) == 12
    for cell, p in zip(cells, profiles):
        assert os.path.exists(MZ.cache_path(cell, MZ.FULL_CHECKED_IN_DIR))
        assert p.meta["fingerprint"] == MZ.cell_fingerprint(cell)
        assert (p.arch, p.shape, p.mesh) == (
            "jamba2-mini", cell.shape.name, "pod16x16")
        assert p.meta["variant"] == "fsdp" and p.num_devices == 256
        assert p.flops > 0 and p.hbm_bytes > 0
        assert sum(p.collective_bytes.values()) > 0
    decode = MZ.resolve_suite("zoo@jamba2-mini:serve-decode",
                              extract_missing=False)
    assert [p.meta["scenario"] for p in decode] == ["serve-decode"] * 4
    # the whole full suite still needs every other architecture's cells
    with pytest.raises(RuntimeError, match="model_zoo"):
        MZ.resolve_suite("zoo", extract_missing=False)


# --------------------------------------------------------------------------- #
# Golden-profile regression (recompiles the smoke suite: ~30-60 s)
# --------------------------------------------------------------------------- #


def test_smoke_goldens_checked_in_and_fresh():
    """Cheap guard: every smoke cell has a golden whose fingerprint matches
    the *current* config/shape/extraction version -- catches config drift
    without recompiling anything."""
    for cell in MZ.zoo_cells(smoke=True):
        path = MZ.cache_path(cell, MZ.SMOKE_CACHE_DIR)
        assert os.path.exists(path), f"missing golden {path}"
        profile = WorkloadProfile.load(path)
        assert profile.meta["fingerprint"] == MZ.cell_fingerprint(cell), (
            f"stale golden {path}: re-run "
            f"PYTHONPATH=src python -m repro.core.model_zoo --smoke --refresh")
        assert profile.meta["scenario"] == cell.scenario
        # canonical form: volatile wall-clock fields zeroed
        assert profile.compile_seconds == 0.0
        assert "probe_seconds" not in profile.meta


def test_golden_profiles_pin_extraction_math(tmp_path):
    """Re-extract the smoke suite from scratch and compare to the goldens.

    Byte-for-byte when the golden was produced by this jax version; across
    jax versions, a structural comparison with tolerance on the measured
    cost fields (XLA codegen may legitimately shift them slightly)."""
    import jax

    fresh = MZ.profiles_from_configs(smoke=True, cache_dir=str(tmp_path),
                                     refresh=True)
    assert len(fresh) >= 6
    for cell in MZ.zoo_cells(smoke=True):
        golden_path = MZ.cache_path(cell, MZ.SMOKE_CACHE_DIR)
        new_path = MZ.cache_path(cell, str(tmp_path))
        with open(golden_path, "rb") as f:
            golden_bytes = f.read()
        golden = json.loads(golden_bytes)
        if golden["meta"].get("jax_version") == jax.__version__:
            with open(new_path, "rb") as f:
                new_bytes = f.read()
            assert new_bytes == golden_bytes, (
                f"extraction output changed for {cell.name}: if the change "
                f"is intentional, bump ZOO_EXTRACTION_VERSION and refresh "
                f"the goldens (python -m repro.core.model_zoo --smoke "
                f"--refresh)")
        else:  # pragma: no cover - exercised on CI's floating jax
            with open(new_path) as f:
                new = json.load(f)
            assert new["meta"]["fingerprint"] == golden["meta"]["fingerprint"]
            for field in ("flops", "hbm_bytes", "model_flops",
                          "num_devices", "tokens"):
                assert new[field] == pytest.approx(golden[field], rel=0.25), \
                    (cell.name, field)


# --------------------------------------------------------------------------- #
# Calibration: Eq.1 batched kernels vs scalar roofline on every zoo cell
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def smoke_profiles():
    return MZ.resolve_suite("zoo-smoke", extract_missing=False)


def test_calibration_report_smoke(smoke_profiles):
    rep = MZ.calibration_report(smoke_profiles)
    assert len(rep.cells) >= 6
    for c in rep.cells:
        # acceptance: calibration ratio finite and positive on every cell
        assert math.isfinite(c.ratio) and c.ratio > 0.0, c
        assert c.dominant_eq1 in ("compute", "memory", "interconnect")
    # acceptance: dominant-term match on >= 80% of smoke cells
    assert rep.dominant_agreement >= 0.8
    # the two code paths share the same kernel math: ratio is ~exactly 1
    for c in rep.cells:
        assert c.ratio == pytest.approx(1.0, rel=1e-9), c


def test_calibration_report_protocol(smoke_profiles):
    rep = MZ.calibration_report(smoke_profiles, timing_model="overlap")
    blob = rep.to_json(top_k=3)
    json.dumps(blob, allow_nan=False)  # strict-JSON clean
    assert blob["num_cells"] == len(rep.cells)
    assert len(blob["cells"]) == 3
    md = rep.markdown(top_k=2)
    assert "dominant-term agreement" in md
    assert len(rep.worst_offenders(4)) == 4


def test_zoo_cells_measured_invariants(smoke_profiles):
    # Measured (not assumed) sanity on every extracted cell.  NOTE:
    # useful_ratio <= 1 does NOT hold for tiny smoke configs (model FLOPs
    # 6*N*D can exceed the HLO count when vocab/width are toy-sized), so
    # here we pin finite-and-positive; the <= 1 direction is a *math*
    # property tested in test_useful_ratio_bounded below.
    for p in smoke_profiles:
        assert p.flops > 0 and p.hbm_bytes > 0
        assert math.isfinite(p.useful_flops_ratio)
        assert p.useful_flops_ratio > 0
        rep = analyze(p, TPU_V5E)
        assert rep.dominant in ("compute", "memory", "interconnect")
        assert rep.step_time_serial_s >= rep.step_time_overlap_s > 0


# --------------------------------------------------------------------------- #
# Roofline property tests (hypothesis when available, seeded loop otherwise)
# --------------------------------------------------------------------------- #


def _profile(fe, me, ce, model_frac=0.5, ndev=4):
    # exponent-parameterized so draws cover many orders of magnitude
    return WorkloadProfile(
        name="prop", flops=10.0 ** fe, hbm_bytes=10.0 ** me,
        collective_bytes={"all-reduce": 10.0 ** ce}, num_devices=ndev,
        model_flops=model_frac * (10.0 ** fe) * ndev)


@floats_property(fe=(8.0, 16.0), me=(6.0, 14.0), ce=(5.0, 13.0))
def test_dominant_is_argmax(fe, me, ce):
    p = _profile(fe, me, ce)
    t = subsystem_times(p, TPU_V5E)
    terms = [t.term(s) for s in ALL_SUBSYSTEMS]
    if len({terms[0], terms[1], terms[2]}) < 3:
        return  # exact tie: any winner is acceptable
    assert t.dominant == ALL_SUBSYSTEMS[int(np.argmax(terms))]


@floats_property(fe=(8.0, 16.0), me=(6.0, 14.0), ce=(5.0, 13.0),
                 scale=(1.0, 100.0))
def test_step_time_monotone_in_every_rate(fe, me, ce, scale):
    p = _profile(fe, me, ce)
    base = step_time(p, TPU_V5E)
    for field in ("peak_flops", "hbm_bw", "ici_bw", "inter_pod_bw"):
        faster = dataclasses.replace(
            TPU_V5E, **{field: getattr(TPU_V5E, field) * scale})
        assert step_time(p, faster) <= base * (1 + 1e-12), field


@floats_property(fe=(8.0, 16.0), frac=(1e-6, 1.0), ndev=(1.0, 512.0))
def test_useful_ratio_bounded(fe, frac, ndev):
    # Whenever the HLO actually performs at least the model FLOPs (the
    # dense-train regime), useful_ratio = model/global is <= 1 -- and it is
    # always positive and finite for positive inputs.
    p = _profile(fe, fe - 2, fe - 3, model_frac=frac, ndev=int(ndev))
    r = p.useful_flops_ratio
    assert 0.0 < r <= 1.0
    # conversely, model_flops above the HLO count pushes it above 1
    p2 = dataclasses.replace(p, model_flops=p.global_flops * 1.5)
    assert p2.useful_flops_ratio > 1.0


@floats_property(fe=(8.0, 16.0), me=(6.0, 14.0), ce=(5.0, 13.0))
def test_roofline_report_round_trip(fe, me, ce):
    rep = analyze(_profile(fe, me, ce), TPU_V5E)
    d = rep.as_dict()
    json.dumps(d, allow_nan=False)          # strict JSON always
    assert RooflineReport.from_dict(d) == rep


def test_roofline_round_trip_non_finite():
    # zero-rate machines / zero-FLOP cells produce inf and nan terms; the
    # satellite contract: as_dict stays strict-JSON-safe and from_dict is
    # an exact inverse (including sign of inf and nan-ness).
    dead = dataclasses.replace(TPU_V5E, hbm_bw=0.0)
    rep = analyze(_profile(12, 10, 9), dead)
    assert math.isinf(rep.memory_s)
    d = rep.as_dict()
    json.dumps(d, allow_nan=False)
    back = RooflineReport.from_dict(d)
    for f in dataclasses.fields(RooflineReport):
        a, b = getattr(rep, f.name), getattr(back, f.name)
        if isinstance(a, float) and math.isnan(a):
            assert math.isnan(b), f.name
        else:
            assert a == b, f.name
    # hand-built corners: -inf and nan survive exactly
    rep2 = dataclasses.replace(rep, mfu_bound=-math.inf,
                               roofline_fraction=math.nan)
    d2 = rep2.as_dict()
    assert d2["mfu_bound"] == "-inf" and d2["roofline_fraction"] == "nan"
    back2 = RooflineReport.from_dict(d2)
    assert back2.mfu_bound == -math.inf
    assert math.isnan(back2.roofline_fraction)
    with pytest.raises(ValueError, match="unknown RooflineReport"):
        RooflineReport.from_dict({**d, "bogus": 1})


# --------------------------------------------------------------------------- #
# Zoo suites end-to-end: sweep, frontier, service, CLI
# --------------------------------------------------------------------------- #


def test_run_sweep_accepts_suite_name(smoke_profiles):
    by_name = run_sweep("zoo-smoke", n=24, seed=3)
    by_list = run_sweep(smoke_profiles, n=24, seed=3)
    assert by_name.to_json(top_k=5) == by_list.to_json(top_k=5)
    assert len(by_name.profiles) >= 6


def test_frontier_accepts_suite_name():
    from repro.core.frontier import frontier_codesign

    res = frontier_codesign("zoo-smoke", VARIANTS, budgets=[0.9, 1.2],
                            steps=2, refine_steps=1)
    assert len(res) == 2
    assert np.all(np.isfinite(res.objective))


def test_service_resolves_spec_suite(smoke_profiles):
    from repro.serving.codesign_service import (
        CodesignRequest,
        CodesignService,
    )

    svc = CodesignService(auto_start=False)
    jid = svc.submit(CodesignRequest(
        kind="sweep", profiles=None,
        spec=CodesignSpec(suite="zoo-smoke", n=16, seed=1)))
    svc.drain()
    got = svc.result(jid)
    want = run_sweep(smoke_profiles, n=16, seed=1)
    assert got.to_json(top_k=4) == want.to_json(top_k=4)
    # profiles=None with no suite on the spec is rejected up front
    with pytest.raises(ValueError, match="spec.suite"):
        CodesignRequest(kind="sweep", profiles=None)


def test_sweep_cli_suite_smoke():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "sweep.py"),
         "--suite", "zoo-smoke", "--num", "16", "--format", "md",
         "--top", "3"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={**os.environ, "PYTHONPATH": SRC + os.pathsep + ROOT})
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "suite zoo-smoke:" in out.stderr and "profiles" in out.stderr
    assert "| variant |" in out.stdout
    # bad suite names die at argparse time
    bad = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "sweep.py"),
         "--suite", "zoo:bogus"],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
        env={**os.environ, "PYTHONPATH": SRC + os.pathsep + ROOT})
    assert bad.returncode == 2
    assert "unknown zoo scenario" in bad.stderr


# --------------------------------------------------------------------------- #
# XLA_FLAGS satellite: append (not clobber) + loud device-count failure
# --------------------------------------------------------------------------- #


def test_request_host_devices_appends(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_cpu_enable_fast_math=false")
    xla_flags.request_host_devices(512)
    flags = os.environ["XLA_FLAGS"]
    assert "--xla_cpu_enable_fast_math=false" in flags   # preserved
    assert f"{xla_flags.HOST_PLATFORM_FLAG}=512" in flags
    assert xla_flags.requested_host_devices() == 512
    # a second request never duplicates or overrides the flag
    xla_flags.request_host_devices(8)
    assert os.environ["XLA_FLAGS"] == flags


def test_requested_host_devices_empty(monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    assert xla_flags.requested_host_devices() is None


def test_dryrun_import_preserves_existing_flags():
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            import os
            import repro.launch.dryrun  # the import requests 512 devices
            flags = os.environ["XLA_FLAGS"]
            assert "--xla_cpu_enable_fast_math=false" in flags, flags
            assert "--xla_force_host_platform_device_count=512" in flags
            print("PRESERVED")
        """)],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": SRC,
             "XLA_FLAGS": "--xla_cpu_enable_fast_math=false"})
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "PRESERVED" in out.stdout


def test_ensure_host_device_count_fails_loudly():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            import jax
            jax.devices()  # lock the backend at the default 1 device
            from repro.launch import xla_flags
            try:
                xla_flags.ensure_host_device_count(256)
            except RuntimeError as e:
                assert "jax locks the device count" in str(e), e
                print("LOUD-FAILURE")
        """)],
        capture_output=True, text=True, timeout=600,
        env={**env, "PYTHONPATH": SRC})
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "LOUD-FAILURE" in out.stdout
