"""Kernel backend layer: numpy/jax/pallas registry, selection, and
equivalence, plus the CostModel area/power proxies.

Pinned equivalence tolerances:
  * jax == numpy to 1e-6 (actually ~1e-12 -- the JAX backend runs x64).
  * pallas == numpy to 5e-4 -- the fused Pallas kernel computes in f32
    (TPUs have no f64), and the Eq. 1 cancellation (alpha - beta) /
    (gamma - beta) amplifies f32 epsilon; measured worst case is ~1e-5,
    5e-4 is the pin.  On CPU CI the kernel runs in interpreter mode --
    the same tiling and f32 math the TPU compile sees.
"""

import dataclasses
import os

import numpy as np
import pytest

from conftest import hypothesis_shim

given, settings, st = hypothesis_shim(seed=0xD1FF, trials=12)

from repro.core import (
    CostModel,
    DEFAULT_COST_MODEL,
    TPU_V5E,
    VARIANTS,
    available_backends,
    evaluate,
    get_backend,
)
from repro.core.kernels_xp import Backend, NumpyBackend
from repro.core.sweep import (
    MachineBatch,
    ParamSpace,
    batched_congruence,
    batched_step_time,
    default_beta_batched,
    run_sweep,
)
from test_sweep import candidate_machines, random_profiles

JAX_RTOL = 1e-6
PALLAS_RTOL = 5e-4


# --------------------------------------------------------------------------- #
# registry + selection
# --------------------------------------------------------------------------- #


def test_registry_has_numpy_and_jax():
    assert "numpy" in available_backends()
    assert "jax" in available_backends()
    assert get_backend("numpy").name == "numpy"
    assert get_backend("jax").name == "jax"
    assert get_backend("jax").differentiable
    assert not get_backend("numpy").differentiable


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="unknown backend"):
        get_backend("bogus")


def test_backend_instance_passthrough():
    be = get_backend("numpy")
    assert get_backend(be) is be


def test_env_var_selects_default_backend(monkeypatch):
    monkeypatch.delenv("REPRO_SWEEP_BACKEND", raising=False)
    assert get_backend().name == "numpy"
    monkeypatch.setenv("REPRO_SWEEP_BACKEND", "jax")
    assert get_backend().name == "jax"
    res = batched_congruence(random_profiles(2, seed=1),
                             MachineBatch.from_models(VARIANTS))
    assert res.backend == "jax"
    monkeypatch.setenv("REPRO_SWEEP_BACKEND", "numpy")
    assert get_backend().name == "numpy"


def test_register_backend_roundtrip():
    from repro.core import register_backend

    class Tagged(NumpyBackend):
        name = "tagged"

    register_backend("tagged", Tagged)
    try:
        assert "tagged" in available_backends()
        res = batched_congruence(random_profiles(2, seed=2),
                                 MachineBatch.from_models(VARIANTS),
                                 backend="tagged")
        assert res.backend == "tagged"
    finally:
        from repro.core.kernels_xp import _BACKEND_CACHE, _BACKEND_FACTORIES
        _BACKEND_FACTORIES.pop("tagged", None)
        _BACKEND_CACHE.pop("tagged", None)


# --------------------------------------------------------------------------- #
# numpy == jax (the 1e-6 acceptance property)
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("timing_model", ["serial", "overlap"])
@pytest.mark.parametrize("clamp", [False, True])
def test_jax_matches_numpy_congruence(timing_model, clamp):
    profiles = random_profiles(6, seed=3)
    machines = candidate_machines(24, seed=1)
    res_n = batched_congruence(profiles, machines, timing_model=timing_model,
                               clamp=clamp, backend="numpy")
    res_j = batched_congruence(profiles, machines, timing_model=timing_model,
                               clamp=clamp, backend="jax")
    np.testing.assert_allclose(res_j.beta, res_n.beta, rtol=JAX_RTOL)
    np.testing.assert_allclose(res_j.gamma, res_n.gamma, rtol=JAX_RTOL)
    for k in res_n.scores:
        np.testing.assert_allclose(res_j.scores[k], res_n.scores[k],
                                   rtol=JAX_RTOL, atol=JAX_RTOL)
    for k in res_n.alphas:
        np.testing.assert_allclose(res_j.alphas[k], res_n.alphas[k],
                                   rtol=JAX_RTOL)
    np.testing.assert_allclose(res_j.aggregate, res_n.aggregate,
                               rtol=JAX_RTOL, atol=JAX_RTOL)
    # the jax tensors come home as NumPy; downstream extractions identical
    assert isinstance(res_j.aggregate, np.ndarray)
    assert res_j.pareto_front() == res_n.pareto_front()
    assert res_j.pareto_front_3d() == res_n.pareto_front_3d()


def test_jax_matches_numpy_step_time_and_beta():
    profiles = random_profiles(5, seed=7)
    machines = candidate_machines(16, seed=2)
    for tm in ("serial", "overlap"):
        t_n = batched_step_time(profiles, machines, timing_model=tm,
                                backend="numpy")
        t_j = batched_step_time(profiles, machines, timing_model=tm,
                                backend="jax")
        np.testing.assert_allclose(t_j, t_n, rtol=JAX_RTOL)
    b_n = default_beta_batched(profiles, machines, backend="numpy")
    b_j = default_beta_batched(profiles, machines, backend="jax")
    np.testing.assert_allclose(b_j, b_n, rtol=JAX_RTOL)


def test_evaluate_and_run_sweep_accept_backend():
    profiles = random_profiles(3, seed=9)
    t_n = evaluate(profiles, backend="numpy")
    t_j = evaluate(profiles, backend="jax")
    assert t_j.result.backend == "jax"
    for app in t_n.apps:
        assert t_j.best_fit(app) == t_n.best_fit(app)
        for v in t_n.variants:
            assert t_j._aggregate(app, v) == pytest.approx(
                t_n._aggregate(app, v), rel=JAX_RTOL, abs=JAX_RTOL)
    res = run_sweep(profiles, n=32, include_named=VARIANTS, backend="jax")
    assert res.backend == "jax"
    ref = run_sweep(profiles, n=32, include_named=VARIANTS, backend="numpy")
    np.testing.assert_allclose(res.aggregate, ref.aggregate,
                               rtol=JAX_RTOL, atol=JAX_RTOL)


def test_jax_backend_is_reused_and_cached():
    assert get_backend("jax") is get_backend("jax")


# --------------------------------------------------------------------------- #
# pallas == numpy (the fused-kernel acceptance property)
# --------------------------------------------------------------------------- #


def test_registry_has_pallas():
    """The fused backend registers lazily via the register_backend hook."""
    assert "pallas" in available_backends()
    be = get_backend("pallas")
    assert be.name == "pallas"
    assert not be.differentiable
    assert be is get_backend("pallas")  # cached like the others
    # interpret mode exactly where jax runs on the CPU, compiled elsewhere
    import jax
    assert be.interpret == (jax.default_backend() == "cpu")


@pytest.mark.parametrize("timing_model", ["serial", "overlap"])
@pytest.mark.parametrize("clamp", [False, True])
def test_pallas_matches_numpy_congruence(timing_model, clamp):
    profiles = random_profiles(6, seed=3)
    machines = candidate_machines(24, seed=1)
    res_n = batched_congruence(profiles, machines, timing_model=timing_model,
                               clamp=clamp, backend="numpy")
    res_p = batched_congruence(profiles, machines, timing_model=timing_model,
                               clamp=clamp, backend="pallas")
    np.testing.assert_allclose(res_p.beta, res_n.beta, rtol=PALLAS_RTOL)
    np.testing.assert_allclose(res_p.gamma, res_n.gamma, rtol=PALLAS_RTOL)
    for k in res_n.scores:
        np.testing.assert_allclose(res_p.scores[k], res_n.scores[k],
                                   rtol=PALLAS_RTOL, atol=PALLAS_RTOL)
    for k in res_n.alphas:
        np.testing.assert_allclose(res_p.alphas[k], res_n.alphas[k],
                                   rtol=PALLAS_RTOL)
    np.testing.assert_allclose(res_p.aggregate, res_n.aggregate,
                               rtol=PALLAS_RTOL, atol=PALLAS_RTOL)
    assert isinstance(res_p.aggregate, np.ndarray)
    assert res_p.backend == "pallas"


def test_pallas_matches_numpy_step_time_and_beta():
    profiles = random_profiles(5, seed=7)
    machines = candidate_machines(16, seed=2)
    for tm in ("serial", "overlap"):
        t_n = batched_step_time(profiles, machines, timing_model=tm,
                                backend="numpy")
        t_p = batched_step_time(profiles, machines, timing_model=tm,
                                backend="pallas")
        np.testing.assert_allclose(t_p, t_n, rtol=PALLAS_RTOL)
    b_n = default_beta_batched(profiles, machines, backend="numpy")
    b_p = default_beta_batched(profiles, machines, backend="pallas")
    np.testing.assert_allclose(b_p, b_n, rtol=PALLAS_RTOL)


def test_pallas_variant_padding_edges():
    """The variant axis is padded to a tile multiple and sliced back out;
    pin the boundary populations (V=1, sub-lane, exact-tile)."""
    profiles = random_profiles(2, seed=13)
    space = ParamSpace.default()
    for v in (1, 5, 127, 128, 129):
        machines = space.sample(v, seed=2)
        res_n = batched_congruence(profiles, machines, backend="numpy")
        res_p = batched_congruence(profiles, machines, backend="pallas")
        assert res_p.aggregate.shape == res_n.aggregate.shape == (2, v)
        np.testing.assert_allclose(res_p.aggregate, res_n.aggregate,
                                   rtol=PALLAS_RTOL, atol=PALLAS_RTOL)
        assert np.all(np.isfinite(res_p.aggregate))


def test_run_sweep_pallas_4096_matches_numpy():
    """ISSUE acceptance: run_sweep(n=4096, backend='pallas') == numpy
    within the pinned tolerance, under interpreter mode on CPU CI."""
    profiles = random_profiles(3, seed=11)
    res_p = run_sweep(profiles, n=4096, backend="pallas")
    res_n = run_sweep(profiles, n=4096, backend="numpy")
    assert res_p.backend == "pallas"
    np.testing.assert_allclose(res_p.aggregate, res_n.aggregate,
                               rtol=PALLAS_RTOL, atol=PALLAS_RTOL)
    np.testing.assert_allclose(res_p.beta, res_n.beta, rtol=PALLAS_RTOL)
    # extractions agree on the clear winners even under f32
    assert res_p.best_fit_indices().shape == res_n.best_fit_indices().shape


def test_pallas_interpret_env_override(monkeypatch):
    """No environment variable picks the mode: the default follows jax's
    backend (interpreted on the CPU only), and only the explicit
    ``interpret=`` argument overrides it."""
    import jax
    from repro.core.kernels_pallas import PallasBackend

    on_cpu = jax.default_backend() == "cpu"
    for env in ("1", "0"):
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", env)
        assert PallasBackend().interpret == on_cpu
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
    assert PallasBackend().interpret == on_cpu
    assert PallasBackend(interpret=True).interpret
    assert not PallasBackend(interpret=False).interpret


def test_pallas_variant_tile_fits_scoped_vmem():
    """The variant tile shrinks with the app count so the double-buffered
    (8, A, tile) f32 output block stays inside half of v5e's 16 MiB scoped
    VMEM; small suites keep the full 512-lane tile."""
    from repro.core.kernels_pallas import TILE_V, _variant_tile

    for apps in (1, 6, 128):
        assert _variant_tile(apps, 1 << 20) == TILE_V
    assert _variant_tile(1000, 1 << 20) == 128
    for apps in (1, 6, 128, 200, 513, 1000):
        tile = _variant_tile(apps, 1 << 20)
        assert tile % 128 == 0
        assert 2 * 8 * (-(-apps // 8) * 8) * tile * 4 <= 8 << 20
    # never wider than the lane-padded population
    assert _variant_tile(6, 5) == 128 and _variant_tile(6, 300) == 384


# --------------------------------------------------------------------------- #
# adversarial cross-backend differential fuzz
# --------------------------------------------------------------------------- #


def _fuzz_profile(name, flops, hbm, coll, nd=64, model_flops=None):
    from repro.core import WorkloadProfile

    return WorkloadProfile(
        name=name, flops=flops, hbm_bytes=hbm, bytes_accessed=hbm,
        collective_bytes={"all-reduce": coll}, num_devices=nd,
        model_flops=(0.5 * flops * nd if model_flops is None
                     else model_flops))


def _assert_backends_agree(profiles, machines, beta=None):
    res_n = batched_congruence(profiles, machines, beta=beta, clamp=True,
                               backend="numpy")
    res_j = batched_congruence(profiles, machines, beta=beta, clamp=True,
                               backend="jax")
    res_p = batched_congruence(profiles, machines, beta=beta, clamp=True,
                               backend="pallas")
    for res in (res_n, res_j, res_p):
        assert np.isfinite(res.aggregate).all(), res.backend
        assert np.isfinite(res.beta).all() and np.isfinite(res.gamma).all()
    np.testing.assert_allclose(res_j.aggregate, res_n.aggregate,
                               rtol=JAX_RTOL, atol=JAX_RTOL)
    np.testing.assert_allclose(res_p.aggregate, res_n.aggregate,
                               rtol=PALLAS_RTOL, atol=PALLAS_RTOL)


@given(
    flops=st.floats(1e6, 1e16),
    intensity=st.floats(1.0, 4096.0),
    coll_frac=st.floats(0.0, 1.0),
    rate_scale=st.floats(1e-3, 1e3),
    beta=st.floats(1e-4, 1e3),
)
@settings(max_examples=24, deadline=None)
def test_backends_agree_on_fuzzed_cells(flops, intensity, coll_frac,
                                        rate_scale, beta):
    """Differential fuzz: numpy == jax to 1e-6 and numpy == pallas to
    5e-4 must hold across the whole (workload x machine x beta) knob
    space, not just the curated suites -- ten decades of FLOPs, rates
    scaled 1e-3..1e3x off nominal, betas from microseconds to ks."""
    prof = _fuzz_profile("fuzz", flops, flops / intensity,
                         coll_frac * flops / intensity)
    machines = MachineBatch.from_models([
        TPU_V5E,
        dataclasses.replace(TPU_V5E,
                            peak_flops=TPU_V5E.peak_flops * rate_scale),
        dataclasses.replace(TPU_V5E, hbm_bw=TPU_V5E.hbm_bw * rate_scale),
        dataclasses.replace(TPU_V5E, ici_bw=TPU_V5E.ici_bw * rate_scale),
    ])
    _assert_backends_agree([prof], machines, beta=beta)


def test_backends_agree_on_degenerate_cells():
    """Deterministic adversarial pins: zero-FLOP and zero-collective
    apps, near-zero and huge machine rates, extreme betas.  Every
    backend must return finite clamped scores and agree."""
    profiles = [
        _fuzz_profile("zero-flop", 0.0, 1e9, 1e8, nd=8, model_flops=0.0),
        _fuzz_profile("zero-coll", 1e12, 1e9, 0.0, nd=8),
        _fuzz_profile("tiny", 1.0, 1.0, 0.0, nd=8, model_flops=0.5),
        _fuzz_profile("hbm-bound", 1e9, 1e12, 1e10, nd=8),
    ]
    machines = MachineBatch.from_models([
        TPU_V5E,
        dataclasses.replace(TPU_V5E,
                            peak_flops=TPU_V5E.peak_flops * 1e-6),
        dataclasses.replace(TPU_V5E, hbm_bw=TPU_V5E.hbm_bw * 1e6),
        dataclasses.replace(TPU_V5E, ici_bw=TPU_V5E.ici_bw * 1e-6,
                            inter_pod_bw=TPU_V5E.inter_pod_bw * 1e-6),
    ])
    for beta in (None, 1e-6, 1e3):
        _assert_backends_agree(profiles, machines, beta=beta)


def test_backends_agree_on_generated_population():
    """The gen:* stress suites run through the same pinned tolerances --
    the population that exists precisely to catch off-suite drift."""
    from repro.core.model_zoo import resolve_suite

    profiles = resolve_suite("gen:16:seed=9")
    machines = candidate_machines(24, seed=6)
    _assert_backends_agree(profiles, machines)


# --------------------------------------------------------------------------- #
# CLI --backend validation (fail at parse time, not deep in the registry)
# --------------------------------------------------------------------------- #


def _load_sweep_cli():
    import importlib.util

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "sweep_cli", os.path.join(root, "scripts", "sweep.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_sweep_cli_rejects_unknown_backend(capsys):
    cli = _load_sweep_cli()
    with pytest.raises(SystemExit) as exc:
        cli.main(["--num", "4", "--backend", "bogus"])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "unknown backend" in err and "pallas" in err


def test_sweep_cli_accepts_registered_backends():
    cli = _load_sweep_cli()
    ap_stub = __import__("argparse").ArgumentParser()
    for name in available_backends():
        cli.validate_backend(ap_stub, name)  # must not raise


def test_hillclimb_rejects_unknown_backend(capsys):
    from repro.launch import hillclimb

    with pytest.raises(SystemExit) as exc:
        hillclimb.main(["--arch", "chatglm3-6b", "--shape", "train_4k",
                        "--backend", "bogus"])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "unknown backend" in err and "pallas" in err


# --------------------------------------------------------------------------- #
# CostModel: area + power proxies
# --------------------------------------------------------------------------- #


def test_default_area_matches_legacy_proxy():
    """Equal weights must reproduce PR 1's four-rate mean exactly."""
    batch = candidate_machines(20, seed=4)
    legacy = (
        batch.peak_flops / TPU_V5E.peak_flops
        + batch.hbm_bw / TPU_V5E.hbm_bw
        + batch.ici_bw_total / (TPU_V5E.ici_bw * TPU_V5E.ici_links)
        + batch.inter_pod_bw / TPU_V5E.inter_pod_bw
    ) / 4.0
    np.testing.assert_allclose(DEFAULT_COST_MODEL.area(batch), legacy,
                               rtol=1e-12)
    np.testing.assert_allclose(batch.area(), legacy, rtol=1e-12)


def test_cost_model_reference_point():
    ref_batch = MachineBatch.from_models([TPU_V5E])
    assert DEFAULT_COST_MODEL.area(ref_batch)[0] == pytest.approx(1.0)
    assert DEFAULT_COST_MODEL.power(ref_batch)[0] == pytest.approx(
        1.0 + DEFAULT_COST_MODEL.static_power)
    # scalar MachineModel works too (duck-typed rate fields)
    assert DEFAULT_COST_MODEL.area(TPU_V5E) == pytest.approx(1.0)


def test_power_superlinear_in_compute():
    """Doubling peak_flops must cost more than 2x its dynamic share
    (DVFS-flavored exponent), while hbm scales linearly."""
    m1 = MachineBatch.from_models([TPU_V5E])
    import dataclasses
    m2 = MachineBatch.from_models(
        [dataclasses.replace(TPU_V5E, peak_flops=TPU_V5E.peak_flops * 2)])
    cm = CostModel()
    d1 = cm.power(m1)[0] - cm.static_power
    d2 = cm.power(m2)[0] - cm.static_power
    # compute contributes 1/4 at reference; superlinear term: 2**1.5 > 2
    assert d2 - d1 > (2.0 - 1.0) / 4.0
    assert d2 - d1 == pytest.approx((2.0 ** 1.5 - 1.0) / 4.0)


def test_cost_model_weights_change_ranking():
    space = ParamSpace.default()
    batch = space.sample(32, seed=5)
    heavy_compute = CostModel(area_weights={"peak_flops": 10.0, "hbm_bw": 1.0,
                                            "ici_bw_total": 1.0,
                                            "inter_pod_bw": 1.0})
    a_eq = DEFAULT_COST_MODEL.area(batch)
    a_hc = heavy_compute.area(batch)
    assert not np.allclose(np.argsort(a_eq), np.argsort(a_hc))


def test_cost_model_rejects_unknown_field():
    with pytest.raises(KeyError):
        CostModel(area_weights={"nonsense": 1.0})


def test_cost_model_rejects_degenerate_weights():
    """Empty or all-zero weight maps fail at construction, not mid-sweep."""
    with pytest.raises(ValueError, match="positive total"):
        CostModel(area_weights={})
    with pytest.raises(ValueError, match="positive total"):
        CostModel(power_weights={"peak_flops": 0.0})


def test_backend_base_class_is_abstract():
    be = Backend()
    with pytest.raises(NotImplementedError):
        be.asarray([1.0])
