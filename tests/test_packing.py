"""Multi-tenant packing (core/packing.py).

The acceptance pin: on a generated stress population (>= 32 apps) packed
across 4 machine instances under one fleet-total area budget,
``pack_codesign`` must beat the uniform baseline -- the best single
machine ``constrained_codesign`` finds at budget/M, replicated M times --
on the exact fleet objective (``fleet_objective``), while every returned
machine stays envelope-feasible and the fleet total stays inside the
budget to 1e-9 relative.

Plus the structural properties: alternation's trajectory is monotone
non-increasing, softmax never regresses past the seed (incumbent
guarantee), the fleet frontier J*(total budget) is monotone, the
reported objective IS the yardstick objective, and ``PackingResult``
speaks the uniform markdown/to_json serving protocol.
"""

import numpy as np
import pytest

from conftest import hypothesis_shim

# Few fallback trials -- each trial here is a full jax packing descent.
given, settings, st = hypothesis_shim(seed=0x9ACC, trials=4)

from repro.core import VARIANTS
from repro.core.constrained import (
    FEASIBLE_RTOL,
    budget_feasible,
    constrained_codesign,
)
from repro.core.costmodel import DEFAULT_COST_MODEL
from repro.core.model_zoo import resolve_suite
from repro.core.packing import (
    PACK_MODES,
    PackingResult,
    _pack_weights,
    _soft_weights,
    fleet_objective,
    pack_codesign,
)
from repro.core.spec import CodesignSpec
from repro.core.sweep import MachineBatch

BETA = 1.5  # one explicit target for both fleets: beta derivation must
            # not differ between the strategies being compared


def small_pack(**kw):
    kw.setdefault("num_machines", 2)
    kw.setdefault("rounds", 2)
    kw.setdefault("steps", 6)
    kw.setdefault("beta", BETA)
    return pack_codesign("gen:6", VARIANTS, **kw)


# --------------------------------------------------------------------------- #
# acceptance: packed fleet beats the uniform fleet under the same budget
# --------------------------------------------------------------------------- #


def test_pack_beats_uniform_fleet_acceptance():
    """ISSUE acceptance: >= 32 generated apps x 4 machines, one total
    area budget.  Packing must beat M replicas of the best single
    constrained machine on the exact fleet objective, and every returned
    machine must be feasible to 1e-9."""
    apps = resolve_suite("gen:32")
    seeds = MachineBatch.from_models(VARIANTS)
    m, budget = 4, 2.0

    uni = constrained_codesign(apps, seeds, steps=30, beta=BETA,
                               area_budget=budget / m)
    uniform_fleet = MachineBatch.from_models([uni.best_model()] * m)
    j_uniform = fleet_objective(apps, uniform_fleet, beta=BETA)

    res = pack_codesign(apps, seeds, num_machines=m, steps=30, beta=BETA,
                        area_budget=budget)
    j_pack = fleet_objective(apps, res.machines, beta=BETA)

    assert j_pack < j_uniform, (j_pack, j_uniform)
    # the fleet total respects the budget to 1e-9 relative
    assert res.area_total <= budget * (1.0 + FEASIBLE_RTOL)
    assert res.feasible is True
    # the reported objective IS the yardstick objective
    assert res.objective_final == pytest.approx(j_pack, rel=1e-9)
    assert len(res.assignment) == 32 and len(res.machines) == m


def test_objective_final_matches_fleet_objective_unconstrained():
    res = small_pack()
    j = fleet_objective(resolve_suite("gen:6"), res.machines, beta=BETA)
    assert res.objective_final == pytest.approx(j, rel=1e-9)
    assert res.feasible is None  # unconstrained: no feasibility claim
    assert res.improvement >= -1e-12


# --------------------------------------------------------------------------- #
# structural properties of the descent
# --------------------------------------------------------------------------- #


def test_alternate_trajectory_monotone_nonincreasing():
    res = small_pack(mode="alternate", steps=12, rounds=3)
    diffs = np.diff(res.trajectory)
    assert (diffs <= 1e-9).all(), res.trajectory
    assert res.trajectory[0] == pytest.approx(res.objective_seed)
    assert res.trajectory[-1] == pytest.approx(res.objective_final)


def test_softmax_never_regresses_past_seed():
    res = small_pack(mode="softmax", steps=12, rounds=3)
    assert res.objective_final <= res.objective_seed + 1e-9
    assert res.mode == "softmax"


def test_assignment_is_argmin_of_final_fleet():
    res = small_pack()
    from repro.core.codesign import descent_suite
    from repro.core import kernels_xp as K

    s = descent_suite(resolve_suite("gen:6"), res.machines, beta=BETA)
    out = K.congruence_kernel(np, s.p, res.machines.arrays(), s.beta,
                              "serial", K.IDEAL_EPS, clamp=False)
    agg = np.asarray(out.aggregate)
    np.testing.assert_array_equal(res.assignment, np.argmin(agg, axis=1))
    np.testing.assert_allclose(
        res.per_app_aggregate, agg[np.arange(6), res.assignment], rtol=1e-12)
    # apps_on partitions the app list
    names = sum((res.apps_on(i) for i in range(len(res.machines))), [])
    assert sorted(names) == sorted(res.app_names)


def test_pack_weights_shapes():
    agg = np.array([[0.3, 0.1], [0.2, 0.5], [0.4, 0.45]])
    w = _pack_weights(agg)
    assert w.sum() == pytest.approx(1.0)
    np.testing.assert_array_equal(np.nonzero(w)[1], [1, 0, 0])
    ws = _soft_weights(agg, temp=0.5)
    np.testing.assert_allclose(ws.sum(axis=1), 1.0 / 3.0, rtol=1e-12)
    # hardening limit: temp -> 0 recovers the one-hot weights (no ties)
    np.testing.assert_allclose(_soft_weights(agg, 1e-12), w, atol=1e-12)


# --------------------------------------------------------------------------- #
# envelopes: no returned machine may violate its per-subsystem caps
# --------------------------------------------------------------------------- #


def test_every_machine_envelope_feasible():
    env = {"peak_flops": 1.2, "hbm_bw": 0.9}
    res = small_pack(area_envelope=env, area_budget=1.8)
    feas = budget_feasible(np, res.machines.arrays(), DEFAULT_COST_MODEL,
                           None, None, rtol=FEASIBLE_RTOL, area_envelope=env)
    assert np.asarray(feas).all()  # every instance, not just assigned ones
    assert res.feasible is True
    assert res.area_total <= 1.8 * (1.0 + FEASIBLE_RTOL)
    # apps only ever land on machines that exist and are feasible
    assert set(int(i) for i in res.assignment) <= set(range(2))


@given(budget=st.floats(0.8, 4.0))
@settings(max_examples=4, deadline=None)
def test_random_total_budget_met(budget):
    res = small_pack(area_budget=float(budget))
    assert res.area_total <= float(budget) * (1.0 + FEASIBLE_RTOL)
    assert res.feasible is True
    assert res.objective_final <= res.objective_seed + 1e-9


# --------------------------------------------------------------------------- #
# the fleet frontier J*(total budget)
# --------------------------------------------------------------------------- #


def test_fleet_frontier_monotone():
    res = small_pack(budgets=[0.9, 1.4, 2.4])
    np.testing.assert_allclose(res.budgets, [0.9, 1.4, 2.4])
    # J* never increases as the total budget loosens
    assert (np.diff(res.frontier_objective) <= 1e-9).all()
    # feasible points respect their budgets
    for j, b in enumerate(res.budgets):
        if res.frontier_feasible[j]:
            assert res.frontier_area[j] <= b * (1.0 + FEASIBLE_RTOL)
    # main fields describe the tightest budget's fleet
    assert res.area_budget == pytest.approx(0.9)
    assert res.objective_final == pytest.approx(
        float(res.frontier_objective[0]))


def test_budgets_and_area_budget_are_exclusive():
    with pytest.raises(ValueError, match="not both"):
        small_pack(budgets=[1.0, 2.0], area_budget=1.5)


# --------------------------------------------------------------------------- #
# validation + spec plumbing
# --------------------------------------------------------------------------- #


def test_pack_validates_arguments():
    with pytest.raises(ValueError, match="unknown packing mode"):
        small_pack(mode="bogus")
    assert "bogus" not in PACK_MODES
    with pytest.raises(ValueError, match="num_machines"):
        small_pack(num_machines=0)
    with pytest.raises(ValueError, match="positive"):
        small_pack(area_budget=-1.0)
    with pytest.raises(ValueError, match="seed machine"):
        pack_codesign("gen:4", MachineBatch.from_models([]), num_machines=2)


def test_spec_drives_pack_and_explicit_wins():
    spec = CodesignSpec(num_machines=3, steps=4, mode="alternate",
                        beta=BETA).validate()
    res = pack_codesign("gen:6", VARIANTS, rounds=2, spec=spec)
    assert len(res.machines) == 3 and res.steps == 4
    # an explicitly-passed keyword beats the spec field
    res2 = pack_codesign("gen:6", VARIANTS, rounds=2, num_machines=2,
                         spec=spec)
    assert len(res2.machines) == 2
    # fleet instance names cycle the seeds and carry the instance index
    assert res2.machine_names[0].startswith("pack0-")
    assert res2.machine_names[1].startswith("pack1-")


# --------------------------------------------------------------------------- #
# result protocol: markdown / to_json / serving front door
# --------------------------------------------------------------------------- #


def test_packing_result_protocol():
    res = small_pack(area_budget=1.8, budgets=None)
    md = res.markdown(top_k=3)
    assert "packing: 6 apps across 2 machines" in md
    assert "| machine |" in md and "feasible=True" in md
    blob = res.to_json(top_k=3)
    assert blob["num_apps"] == 6 and blob["num_machines"] == 2
    assert set(blob["assignment"]) == set(res.app_names)
    assert set(blob["assignment"].values()) <= set(res.machine_names)
    assert blob["feasible"] is True
    assert len(blob["trajectory"]) == len(res.trajectory)
    import json
    json.dumps(blob)  # strictly JSON-serializable


def test_pack_serves_through_front_door():
    from repro.serving.codesign_service import (
        CodesignRequest,
        CodesignService,
        render_result,
    )

    svc = CodesignService(auto_start=False)
    jid = svc.submit(CodesignRequest(
        kind="pack", profiles="gen:6",
        spec=CodesignSpec(steps=4, num_machines=2, beta=BETA)))
    svc.drain()
    got = svc.result(jid)
    assert isinstance(got, PackingResult)
    want = small_pack(steps=4, rounds=4)  # service uses pack defaults
    assert got.to_json(top_k=4) == want.to_json(top_k=4)
    assert "packing: 6 apps" in render_result(got, top_k=4)
