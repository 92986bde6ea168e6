"""The plain reference equals the program's numpy backend at small sizes.

The reference imports nothing of the program; the program's float64 numpy
backend runs the same mathematics, so the two agree to rounding (here
mostly bit for bit).
"""

import ast
from pathlib import Path

import numpy as np
import pytest

from reference import codesign, congruence, pareto, population, sweep

REF_DIR = Path(__file__).resolve().parents[1] / "reference"

SPACE = {"nominal": {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9,
                     "ici_links": 1, "inter_pod_bw": 25e9},
         "span": 4.0, "max_links": 8}
COST = {"reference": SPACE["nominal"],
        "area_weights": {r: 1.0 for r in pareto.COST_RATES},
        "power_weights": {r: 1.0 for r in pareto.COST_RATES},
        "power_exponents": {"peak_flops": 1.5, "hbm_bw": 1.0,
                            "ici_bw_total": 1.0, "inter_pod_bw": 1.0},
        "static_power": 0.1}
SEED = 2**31 + 77


@pytest.fixture(scope="module")
def profiles():
    from repro.core.model_zoo import resolve_suite

    return (list(resolve_suite("zoo-smoke", extract_missing=False))
            + list(resolve_suite(f"gen:58:seed={SEED}")))


@pytest.fixture(scope="module")
def program(profiles):
    from repro.core.sweep import run_sweep

    return run_sweep(profiles, n=2048, seed=SEED, backend="numpy")


def test_reference_imports_nothing_of_the_program():
    for path in REF_DIR.glob("*.py"):
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.module:
                names = [node.module]
            for name in names:
                assert not name.startswith("repro"), (path.name, name)


def test_population_equals_program(program):
    M = population.population(SPACE, np.arange(2048), SEED)
    for k, col in M.items():
        np.testing.assert_array_equal(col, getattr(program.machines, k))


def test_scores_equal_numpy_backend(profiles, program):
    P = congruence.profile_columns(profiles)
    beta = congruence.default_beta(P, SPACE["nominal"])
    np.testing.assert_allclose(beta, program.beta, rtol=1e-14)
    M = population.population(SPACE, np.arange(2048), SEED)
    out = congruence.congruence(P, M, beta, 1e-3, clamp=True)
    got = {"gamma": program.gamma, "aggregate": program.aggregate,
           "alpha_compute": program.alphas["compute"],
           "alpha_memory": program.alphas["memory"],
           "alpha_interconnect": program.alphas["interconnect"],
           "lbcs": program.scores["LBCS"], "hrcs": program.scores["HRCS"],
           "ics": program.scores["ICS"]}
    for k in congruence.OUTPUTS:
        np.testing.assert_allclose(out[k], got[k], rtol=1e-12, atol=1e-15,
                                   err_msg=k)


def test_choices_equal_numpy_backend(profiles, program):
    from repro.core import DEFAULT_COST_MODEL

    P = congruence.profile_columns(profiles)
    beta = congruence.default_beta(P, SPACE["nominal"])
    M = population.population(SPACE, np.arange(2048), SEED)
    sol = sweep.solve(P, M, beta, 1e-3, True, COST, threads=2)
    np.testing.assert_array_equal(sol["best_fit"],
                                  program.best_fit_indices())
    np.testing.assert_allclose(sol["mean"], program.aggregate_mean(),
                               rtol=1e-13)
    np.testing.assert_allclose(sol["area"], program.area(), rtol=1e-15)
    np.testing.assert_allclose(sol["power"],
                               DEFAULT_COST_MODEL.power(program.machines),
                               rtol=1e-15)
    assert list(sol["front2"]) == program.pareto_front()
    assert sorted(sol["front3"]) == sorted(program.pareto_front_3d())


def test_front_3d_matches_brute_force():
    rng = np.random.default_rng(3)
    pts = rng.random((3, 600)).round(2)     # ties on every axis
    got = set(pareto.front_3d(*pts, chunk=64).tolist())
    le = np.all(pts[:, :, None] <= pts[:, None, :], axis=0)
    lt = np.any(pts[:, :, None] < pts[:, None, :], axis=0)
    dominated = np.any(le & lt, axis=0)
    assert got == set(np.nonzero(~dominated)[0].tolist())


def test_objective_and_descent_equal_program(profiles):
    from repro.core import VARIANTS, grad_codesign
    from repro.core.codesign import scalarized_objective
    from repro.core.sweep import MachineBatch

    seeds_mb = MachineBatch.from_models(VARIANTS)
    seeds = population.machine([{k: getattr(m, k) for k in
                                 ("peak_flops", "hbm_bw", "ici_bw",
                                  "ici_links", "inter_pod_bw")}
                                for m in VARIANTS])
    P = congruence.profile_columns(profiles)
    beta = congruence.default_beta(P, {k: seeds[k][0] for k in seeds})
    req = {"steps": 6, "lr": 0.1, "span": 16.0, "w_area": 0.1,
           "w_power": 0.05}
    theta0 = np.log(np.stack([seeds[f] for f in codesign.THETA_FIELDS], 1))
    J = codesign.objective(P, theta0, seeds, beta, req, 1e-3,
                           codesign.cast_cost(COST, np.float64))
    # exp(log(rate)) moves a rate by an ulp, which Eq. 1 amplifies
    np.testing.assert_allclose(J, scalarized_objective(profiles, seeds_mb),
                               rtol=1e-12)
    theta, final, steps = codesign.descend(P, seeds, beta, req, 1e-3, COST)
    res = grad_codesign(profiles, seeds_mb, steps=6)
    np.testing.assert_allclose(final, res.objective_final, rtol=1e-12)
    np.testing.assert_allclose(steps, res.trajectory, rtol=1e-12)
    got = np.log([[p[f] for f in codesign.THETA_FIELDS]
                  for p in res.final_params])
    np.testing.assert_allclose(theta, got, rtol=1e-10)
