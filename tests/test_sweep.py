"""Sweep engine: batched == scalar equivalence, population generators,
Pareto invariants, and the lazy DSE table.

The batched kernels in ``repro.core.sweep`` re-implement the scalar timing +
Eq. 1 pipeline as (A, V) array ops; these tests pin them to the scalar
reference (``profile_congruence`` / ``evaluate(method="scalar")``) to within
1e-9, which is what licenses the fast path as the ``evaluate()`` default.
"""

import random

import numpy as np
import pytest

from repro.core import (
    ALL_SUBSYSTEMS,
    MachineModel,
    TPU_V5E,
    VARIANTS,
    WorkloadProfile,
    profile_congruence,
)
from repro.core.congruence import default_beta
from repro.core.dse import DseTable, LazyDseTable, evaluate
from repro.core import sweep as sweep_module
from repro.core.sweep import (
    _DOMINANCE_BLOCK,
    Dim,
    MachineBatch,
    ParamSpace,
    ProfileBatch,
    batched_congruence,
    batched_step_time,
    halton,
    pareto_front_indices,
    pareto_front_indices_3d,
    run_sweep,
)
from repro.core.timing import step_time, subsystem_times

RTOL = 1e-9


def random_profiles(n, seed=0):
    rng = random.Random(seed)
    out = []
    for i in range(n):
        p = WorkloadProfile(
            name=f"app{i}",
            flops=10 ** rng.uniform(9, 15),
            hbm_bytes=10 ** rng.uniform(6, 12),
            bytes_accessed=10 ** rng.uniform(6, 12),
            collective_bytes={
                "all-reduce": 10 ** rng.uniform(6, 12),
                "all-gather": 10 ** rng.uniform(5, 11),
            },
            num_devices=rng.choice([1, 8, 256]),
            model_flops=(10 ** rng.uniform(12, 18)
                         if rng.random() < 0.8 else 0.0),
        )
        if i % 3 == 0:
            p.pod_collective_bytes = 0.3 * p.total_collective_bytes
        if i % 5 == 0:
            p.hbm_bytes = 0.0  # exercise the bytes_accessed fallback
        out.append(p)
    return out


def candidate_machines(n=24, seed=1):
    return MachineBatch.concat(
        MachineBatch.from_models(VARIANTS),
        ParamSpace.default().sample(n, seed=seed))


# --------------------------------------------------------------------------- #
# batched vs scalar equivalence (the ISSUE's 1e-9 property)
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("timing_model", ["serial", "overlap"])
@pytest.mark.parametrize("clamp", [False, True])
def test_batched_matches_scalar(timing_model, clamp):
    profiles = random_profiles(6, seed=3)
    machines = candidate_machines(24, seed=1)
    res = batched_congruence(
        profiles, machines, timing_model=timing_model, clamp=clamp)
    for a, p in enumerate(profiles):
        beta = default_beta(p, machines.model(0))
        assert res.beta[a] == pytest.approx(beta, rel=RTOL)
        for v in range(len(machines)):
            rep = profile_congruence(
                p, machines.model(v), beta=beta,
                timing_model=timing_model, clamp=clamp)
            assert res.gamma[a, v] == pytest.approx(rep.gamma, rel=RTOL)
            for sub, alpha in rep.alphas.items():
                assert res.alphas[sub][a, v] == pytest.approx(alpha, rel=RTOL)
            for k, s in rep.scores.items():
                assert res.scores[k][a, v] == pytest.approx(
                    s, rel=RTOL, abs=RTOL)
            assert res.aggregate[a, v] == pytest.approx(
                rep.aggregate, rel=RTOL, abs=RTOL)


def test_batched_step_time_matches_scalar():
    profiles = random_profiles(5, seed=7)
    machines = candidate_machines(16, seed=2)
    for tm in ("serial", "overlap"):
        t = batched_step_time(profiles, machines, timing_model=tm)
        for a, p in enumerate(profiles):
            for v in range(len(machines)):
                assert t[a, v] == pytest.approx(
                    step_time(p, machines.model(v), tm), rel=RTOL)


def test_explicit_beta_forms():
    profiles = random_profiles(4, seed=11)
    machines = candidate_machines(8, seed=4)
    scalar = batched_congruence(profiles, machines, beta=0.0)
    assert np.all(scalar.beta == 0.0)
    per_app = np.array([1e-4, 2e-4, 3e-4, 4e-4])
    res = batched_congruence(profiles, machines, beta=per_app)
    for a, p in enumerate(profiles):
        rep = profile_congruence(p, machines.model(2), beta=per_app[a])
        assert res.aggregate[a, 2] == pytest.approx(rep.aggregate, rel=RTOL)


@pytest.mark.parametrize("timing_model", ["serial", "overlap"])
@pytest.mark.parametrize("beta_frac", [0.0, 0.5, 0.9, 2.0])
@pytest.mark.parametrize("clamp", [False, True])
def test_clamp_semantics_scalar_equals_batched(timing_model, beta_frac, clamp):
    """Clamp pin (one kernel, one semantic): scalar and batched must agree
    cell-for-cell for every clamp setting, including betas that push raw
    Eq. 1 scores above 1 (beta between alpha and gamma) and below 0
    (beta > gamma, negative denominator)."""
    profiles = random_profiles(4, seed=31)
    machines = candidate_machines(10, seed=6)
    gamma0 = np.array([step_time(p, machines.model(0), timing_model)
                       for p in profiles])
    beta = beta_frac * gamma0
    res = batched_congruence(profiles, machines, beta=beta,
                             timing_model=timing_model, clamp=clamp)
    saw_out_of_unit = False
    for a, p in enumerate(profiles):
        for v in range(len(machines)):
            rep = profile_congruence(p, machines.model(v), beta=beta[a],
                                     timing_model=timing_model, clamp=clamp)
            for k, s in rep.scores.items():
                if clamp:
                    assert 0.0 <= s <= 1.0
                elif s < 0.0 or s > 1.0:
                    saw_out_of_unit = True
                assert res.scores[k][a, v] == pytest.approx(
                    s, rel=RTOL, abs=RTOL)
            assert res.aggregate[a, v] == pytest.approx(
                rep.aggregate, rel=RTOL, abs=RTOL)
    if not clamp and beta_frac in (0.9, 2.0):
        assert saw_out_of_unit, "fixture must exercise scores outside [0, 1]"


def test_clamp_applies_to_extended_decomposition():
    """A clamped report is clamped throughout, including §II-B sub-scores."""
    p = random_profiles(1, seed=33)[0]
    gamma = step_time(p, TPU_V5E)
    rep = profile_congruence(p, TPU_V5E, beta=2.0 * gamma, clamp=True)
    assert all(0.0 <= v <= 1.0 for v in rep.scores.values())
    assert all(0.0 <= v <= 1.0 for v in rep.extended.values())
    raw = profile_congruence(p, TPU_V5E, beta=2.0 * gamma, clamp=False)
    assert any(v < 0.0 or v > 1.0 for v in raw.extended.values())


def test_default_beta_accepts_threaded_baseline():
    """Satellite fix: the baseline TimingBreakdown is shared, not recomputed
    -- passing it explicitly must be an exact no-op."""
    for p in random_profiles(4, seed=35):
        baseline = subsystem_times(p, TPU_V5E)
        assert default_beta(p, TPU_V5E, baseline=baseline) \
            == default_beta(p, TPU_V5E)


def test_degenerate_gamma_equals_beta_scores_zero():
    p = random_profiles(1)[0]
    machines = MachineBatch.from_models(VARIANTS)
    gamma = step_time(p, VARIANTS[0])
    res = batched_congruence([p], machines, beta=gamma)
    for k in ("ICS", "HRCS", "LBCS"):
        assert np.isfinite(res.scores[k][0, 0])
    assert res.scores["ICS"][0, 0] == 0.0 or res.gamma[0, 0] != gamma


# --------------------------------------------------------------------------- #
# evaluate(): lazy table == eager table
# --------------------------------------------------------------------------- #


def test_evaluate_batched_equals_scalar_table():
    profiles = random_profiles(5, seed=5)
    suites = {"even": [p.name for p in profiles[::2]],
              "odd": [p.name for p in profiles[1::2]]}
    lazy = evaluate(profiles, suites=suites, method="batched")
    eager = evaluate(profiles, suites=suites, method="scalar")
    assert isinstance(lazy, LazyDseTable) and isinstance(eager, DseTable)
    assert lazy.apps == eager.apps
    assert lazy.variants == eager.variants
    for app in eager.apps:
        assert lazy.best_fit(app) == eager.best_fit(app)
        for v in eager.variants:
            assert lazy.cell(app, v).aggregate == pytest.approx(
                eager.cell(app, v).aggregate, rel=RTOL, abs=RTOL)
    for suite in suites:
        for v in eager.variants:
            assert lazy.suite_mean(suite, v) == pytest.approx(
                eager.suite_mean(suite, v), rel=RTOL)
        assert lazy.suite_best_fit(suite) == eager.suite_best_fit(suite)
    assert lazy.overall_best_fit() == eager.overall_best_fit()
    # identical rendering, including per-cell extended reports on demand
    assert lazy.markdown() == eager.markdown()
    assert lazy.radar_markdown() == eager.radar_markdown()
    a, v = eager.apps[0], eager.variants[0]
    assert (lazy.cell(a, v).report.extended.keys()
            == eager.cell(a, v).report.extended.keys())


def test_evaluate_default_is_batched_and_auto():
    profiles = random_profiles(3, seed=9)
    assert isinstance(evaluate(profiles), LazyDseTable)
    assert isinstance(evaluate(profiles, method="auto"), LazyDseTable)
    with pytest.raises(ValueError):
        evaluate(profiles, method="bogus")


def test_evaluate_accepts_machine_batch():
    profiles = random_profiles(3, seed=13)
    machines = ParamSpace.default().sample(10, seed=3)
    lazy = evaluate(profiles, variants=machines)
    eager = evaluate(profiles, variants=machines, method="scalar")
    for app in eager.apps:
        assert lazy.best_fit(app) == eager.best_fit(app)


def test_lazy_cells_materialize_on_demand():
    profiles = random_profiles(2, seed=15)
    lazy = evaluate(profiles)
    assert not lazy._cell_cache
    c = lazy.cell(profiles[0].name, "baseline")
    assert c.report.name == profiles[0].name
    assert len(lazy._cell_cache) == 1
    assert c is lazy.cell(profiles[0].name, "baseline")  # cached
    assert len(lazy.cells) == len(profiles) * len(VARIANTS)


# --------------------------------------------------------------------------- #
# population generators
# --------------------------------------------------------------------------- #


def test_halton_is_low_discrepancy_and_deterministic():
    pts = halton(256, 5, seed=0)
    assert pts.shape == (256, 5)
    assert np.all((pts >= 0.0) & (pts < 1.0))
    # every dimension covers the unit interval reasonably evenly
    for j in range(5):
        hist, _ = np.histogram(pts[:, j], bins=8, range=(0, 1))
        assert hist.min() >= 16  # perfectly uniform would be 32
    assert np.array_equal(pts, halton(256, 5, seed=0))
    assert not np.array_equal(pts, halton(256, 5, seed=1))


def _plain_halton(indices, d, seed):
    """The seeded Halton rows by the plain per-digit radical-inverse loop."""
    idx = np.asarray(indices, dtype=np.int64) + 1
    shifts = np.random.default_rng(seed).random(d)
    out = np.empty((idx.size, d), dtype=np.float64)
    for j, base in enumerate((2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37)[:d]):
        n = idx.copy()
        inv = np.zeros(n.shape, dtype=np.float64)
        frac = 1.0 / base
        while np.any(n > 0):
            inv += frac * (n % base)
            n //= base
            frac /= base
        out[:, j] = (inv + shifts[j]) % 1.0
    return out


@pytest.mark.parametrize("offset", [0, 65536 * 15, 2**31 - 10,
                                    3_000_000_000])
def test_halton_at_is_bit_identical_to_the_digit_loop(offset):
    """The table-driven radical inverse adds the same terms in the same
    order as the per-digit loop, for every prime, on both sides of the
    int32/int64 switch; 70,000 rows cross every prime's table size."""
    from repro.core.sweep import halton_at

    rng = np.random.default_rng(offset)
    idx = np.concatenate([offset + np.arange(70_000),
                          rng.integers(0, offset + 70_000, 500)])
    got = halton_at(idx, 12, seed=offset + 1)
    want = _plain_halton(idx, 12, seed=offset + 1)
    assert got.shape == (idx.size, 12)
    np.testing.assert_array_equal(got.view(np.int64), want.view(np.int64))


def test_param_space_sample_bounds():
    space = ParamSpace.default(span=4.0, max_links=8)
    batch = space.sample(128, seed=2)
    assert len(batch) == 128
    for name, dim in space.dims.items():
        vals = getattr(batch, name)
        assert np.all(vals >= dim.lo) and np.all(vals <= dim.hi), name
    assert np.array_equal(batch.ici_links, np.rint(batch.ici_links))
    # unswept params pinned at nominal
    assert np.all(batch.scale_compute == 1.0)


def test_param_space_grid_cross_product():
    space = ParamSpace.default()
    batch = space.grid({"peak_flops": 3, "hbm_bw": 2, "ici_links": 4})
    links = space.dims["ici_links"].points(4)
    assert len(batch) == 3 * 2 * len(links)
    assert len({(f, h, l) for f, h, l in
                zip(batch.peak_flops, batch.hbm_bw, batch.ici_links)}) \
        == len(batch)


def test_dim_points_and_unit_mapping():
    d = Dim(1.0, 100.0, log=True)
    pts = d.points(3)
    assert pts == pytest.approx([1.0, 10.0, 100.0])
    di = Dim(1, 4, log=False, integer=True)
    vals = di.from_unit(np.linspace(0.0, 0.999, 64))
    assert set(vals) == {1.0, 2.0, 3.0, 4.0}


def test_machine_batch_roundtrip():
    batch = MachineBatch.from_models(VARIANTS)
    for i, m in enumerate(VARIANTS):
        back = batch.model(i)
        assert back.name == m.name
        assert back.peak_flops == m.peak_flops
        assert back.hbm_bw == m.hbm_bw
        assert back.ici_bw_total == m.ici_bw_total
    assert batch.area()[0] == pytest.approx(1.0)  # baseline vs itself


def test_profile_batch_mem_fallback():
    p = random_profiles(1)[0]
    p.hbm_bytes = 0.0
    p.bytes_accessed = 123.0
    pb = ProfileBatch.from_profiles([p])
    assert pb.mem_bytes[0] == 123.0


# --------------------------------------------------------------------------- #
# extractions: best fit + Pareto front
# --------------------------------------------------------------------------- #


def test_pareto_front_has_no_dominated_point():
    profiles = random_profiles(6, seed=21)
    res = run_sweep(profiles, n=200, seed=4, include_named=VARIANTS)
    area, agg = res.area(), res.aggregate_mean()
    front = res.pareto_front()
    assert front, "front must be non-empty"
    assert area[front] == pytest.approx(sorted(area[front]))  # sorted by area
    for i in front:
        dominated = ((area <= area[i]) & (agg <= agg[i])
                     & ((area < area[i]) | (agg < agg[i])))
        assert not dominated.any(), f"front point {i} is dominated"
    # the global congruence optimum is always on the front
    assert int(np.argmin(agg)) in front


def test_best_fit_matches_argmin():
    profiles = random_profiles(4, seed=23)
    res = batched_congruence(profiles, candidate_machines(12), clamp=True)
    for a, p in enumerate(profiles):
        v = int(np.argmin(res.aggregate[a]))
        assert res.best_fit(p.name) == res.machines.names[v]


def test_pareto_front_3d_has_no_dominated_point():
    profiles = random_profiles(5, seed=27)
    res = run_sweep(profiles, n=150, seed=6, include_named=VARIANTS)
    agg = res.aggregate_mean()
    area = np.asarray(res.area())
    power = np.asarray(res.power())
    front = res.pareto_front_3d()
    assert front, "3-D front must be non-empty"
    assert area[front] == pytest.approx(sorted(area[front]))
    for i in front:
        dominated = ((area <= area[i]) & (agg <= agg[i]) & (power <= power[i])
                     & ((area < area[i]) | (agg < agg[i]) | (power < power[i])))
        assert not dominated.any(), f"3-D front point {i} is dominated"
    # every non-front point is dominated by someone (front completeness)
    for i in set(range(len(res.machines))) - set(front):
        dominated = ((area <= area[i]) & (agg <= agg[i]) & (power <= power[i])
                     & ((area < area[i]) | (agg < agg[i]) | (power < power[i])))
        assert dominated.any(), f"non-front point {i} is non-dominated"



# The Python-loop Pareto extractions the vectorized ones replaced, kept
# verbatim as oracles: the vectorized functions must return the very same
# indices in the very same order.


def loop_pareto_front_indices(area, aggregate):
    area = np.asarray(area)
    aggregate = np.asarray(aggregate)
    order = sorted(range(len(area)), key=lambda i: (area[i], aggregate[i]))
    front = []
    best = np.inf
    for i in order:
        if aggregate[i] < best:
            front.append(i)
            best = aggregate[i]
    return front


def loop_pareto_front_indices_3d(aggregate, area, power):
    aggregate = np.asarray(aggregate)
    area = np.asarray(area)
    power = np.asarray(power)
    order = sorted(range(len(area)),
                   key=lambda i: (area[i], power[i], aggregate[i]))
    front = []
    for i in order:
        dominated = any(
            area[j] <= area[i] and power[j] <= power[i]
            and aggregate[j] <= aggregate[i]
            and (area[j] < area[i] or power[j] < power[i]
                 or aggregate[j] < aggregate[i])
            for j in front)
        if not dominated:
            front.append(i)
    return front


def _pareto_points(n, kind, seed):
    """``(area, power, aggregate)`` rows of ``n`` points.

    ``uniform``: continuous, no ties.  ``quantized``: four levels per axis,
    so exact ties and duplicate points abound.  ``inf``: a tenth of the
    entries ``+inf``.  ``earlier_block``: a chain of mutually non-dominated
    points longer than one dominance pass, screened by neither extreme,
    and one point whose only dominator lies in the first pass."""
    rng = np.random.default_rng(seed)
    if kind == "earlier_block":
        t = np.arange(n - 3, dtype=np.float64)
        chain = np.stack([1.0 + t, 9.0 - 8.0 * t / n, 5.0 + t / n])
        extremes = np.array([[0.0, 0.5], [0.0, 10.0], [10.0, 0.0]])
        late = np.array([[n + 1.0], [chain[1, 0]], [chain[2, 0] + 0.5 / n]])
        pts = np.concatenate([chain, extremes, late], axis=1)
        return pts[:, rng.permutation(n)]
    pts = rng.random((3, n))
    if kind == "quantized":
        pts = np.floor(pts * 4.0) / 4.0
    elif kind == "inf":
        pts[rng.random((3, n)) < 0.1] = np.inf
    return pts


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("kind,n", [
    *((kind, n) for kind in ("uniform", "quantized", "inf")
      for n in (0, 1, 2, 17, _DOMINANCE_BLOCK - 1, _DOMINANCE_BLOCK + 1,
                5000)),
    ("earlier_block", _DOMINANCE_BLOCK + 8),
])
def test_pareto_extraction_equals_loop_oracle(kind, n, dtype):
    """The vectorized 2-D and 3-D extractions return exactly the indices
    of the Python loops, in order, as Python ints."""
    area, power, aggregate = _pareto_points(n, kind, seed=n).astype(dtype)
    for got, want in (
            (pareto_front_indices(area, aggregate),
             loop_pareto_front_indices(area, aggregate)),
            (pareto_front_indices_3d(aggregate, area, power),
             loop_pareto_front_indices_3d(aggregate, area, power))):
        assert type(got) is list
        assert all(type(i) is int for i in got)
        assert got == want
    if n == 1 and kind == "uniform":
        assert pareto_front_indices(area, aggregate) == [0]
        assert pareto_front_indices_3d(aggregate, area, power) == [0]
    if kind == "earlier_block":
        late = int(np.argmax(area))
        assert late not in pareto_front_indices_3d(aggregate, area, power)


def test_sweep_result_reports():
    profiles = random_profiles(3, seed=25)
    res = run_sweep(profiles, n=20, include_named=VARIANTS)
    md = res.markdown(top_k=5)
    assert "pareto front" in md and "mean aggregate" in md
    assert "power" in md and "3-D pareto front" in md
    blob = res.to_json(top_k=5)
    assert blob["num_variants"] == 23
    assert set(blob["best_fit"]) == {p.name for p in profiles}
    assert len(blob["top_variants"]) == 5
    assert blob["backend"] in ("numpy", "jax")
    assert blob["pareto_front_3d"], "3-D front serialized"
    import json
    json.dumps(blob)  # fully serializable


# --------------------------------------------------------------------------- #
# per-subsystem scale_* sweeps (degradation analysis)
# --------------------------------------------------------------------------- #


def scale_space(span=4.0):
    """Degradation sweep: rate dims plus the per-subsystem delay scale_*
    dims UNpinned -- now the ``ParamSpace.scale_space`` preset (pinned
    further in tests/test_genload.py)."""
    return ParamSpace.scale_space(span=span, scale_span=4.0)


def test_scale_dims_sample_and_vary():
    batch = scale_space().sample(64, seed=8)
    for name in ("scale_compute", "scale_memory", "scale_interconnect"):
        vals = getattr(batch, name)
        assert np.all((vals >= 0.25) & (vals <= 4.0))
        assert len(np.unique(vals)) > 8, f"{name} must actually vary"


@pytest.mark.parametrize("timing_model", ["serial", "overlap"])
def test_scale_sweep_batched_matches_scalar(timing_model):
    """Degradation sweep equivalence: with all scale_* dims unpinned, the
    batched path must still match the scalar with_scales path to 1e-9."""
    profiles = random_profiles(4, seed=41)
    machines = scale_space().sample(16, seed=9)
    res = batched_congruence(profiles, machines, timing_model=timing_model)
    for a, p in enumerate(profiles):
        beta = default_beta(p, machines.model(0))
        for v in range(len(machines)):
            m = machines.model(v)
            # the materialized model carries the sampled non-default scales
            scales = [m.scale_for(s) for s in ALL_SUBSYSTEMS]
            assert any(abs(x - 1.0) > 1e-6 for x in scales)
            rep = profile_congruence(p, m, beta=beta,
                                     timing_model=timing_model)
            assert res.gamma[a, v] == pytest.approx(rep.gamma, rel=RTOL)
            for k, s in rep.scores.items():
                assert res.scores[k][a, v] == pytest.approx(
                    s, rel=RTOL, abs=RTOL)


def test_machine_model_json_roundtrip_with_scales():
    m = TPU_V5E.with_scales(compute=1.3, memory=0.7, interconnect=2.5)
    back = MachineModel.from_json(m.to_json())
    assert back == m
    # and through a sampled batch: model(i) -> json -> model survives
    batch = scale_space().sample(4, seed=10)
    for i in range(len(batch)):
        v = batch.model(i)
        assert MachineModel.from_json(v.to_json()) == v


def test_machine_model_with_rates():
    m = TPU_V5E.with_scales(memory=0.7).with_rates(
        name="tweaked", peak_flops=2 * TPU_V5E.peak_flops, ici_links=3.6)
    assert m.name == "tweaked"
    assert m.peak_flops == 2 * TPU_V5E.peak_flops
    assert m.ici_links == 4  # rounded to int
    assert m.hbm_bw == TPU_V5E.hbm_bw  # untouched rates preserved
    assert m.scale["memory"] == 0.7    # scales preserved
    with pytest.raises(KeyError):
        TPU_V5E.with_rates(bogus=1.0)


# --------------------------------------------------------------------------- #
# shard_sweep: sharded mega-sweeps must reproduce the single-device answer
# --------------------------------------------------------------------------- #


def _front_names(res):
    return ([res.machines.names[i] for i in res.pareto_front()],
            [res.machines.names[i] for i in res.pareto_front_3d()])


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_shard_sweep_matches_run_sweep(backend):
    """ISSUE acceptance: shard_sweep produces the same Pareto fronts and
    best fits as a single-device run_sweep over the identical population.
    backend="jax" exercises the NamedSharding mesh path (1-device mesh on
    CI); backend="numpy" the chunked shard loop."""
    from repro.core.sweep import shard_sweep

    profiles = random_profiles(4, seed=5)
    single = run_sweep(profiles, n=150, include_named=VARIANTS,
                       backend=backend)
    sharded = shard_sweep(profiles, n=150, include_named=VARIANTS,
                          backend=backend, num_shards=4)
    f2, f3 = _front_names(single)
    sf2, sf3 = _front_names(sharded.result)
    assert sharded.pareto_names() == sf2 == f2
    assert sf3 == f3
    for app in single.apps:
        assert sharded.best_fit(app) == single.best_fit(app)
    # pre-filtering actually filtered, and survivors are scored identically
    assert sharded.num_variants == len(single.machines)
    assert 0 < len(sharded.result.machines) < sharded.num_variants
    np.testing.assert_allclose(
        sharded.result.aggregate,
        single.aggregate[:, sharded.candidate_indices], rtol=1e-12)


def test_shard_sweep_single_shard_and_reports():
    from repro.core.sweep import shard_sweep

    profiles = random_profiles(3, seed=21)
    single = run_sweep(profiles, n=64)
    sharded = shard_sweep(profiles, n=64, num_shards=1)
    assert sharded.num_shards == 1
    assert sharded.pareto_names() == [
        single.machines.names[i] for i in single.pareto_front()]
    md = sharded.markdown(top_k=4)
    assert md.startswith("sharded sweep: 64 variants across 1 shards")
    blob = sharded.to_json(top_k=4)
    assert blob["num_variants"] == 64
    assert blob["num_shards"] == 1
    assert blob["num_candidates"] == len(sharded.result.machines)
    assert set(blob["best_fit"]) == set(sharded.apps)


def test_shard_sweep_pallas_backend():
    """The fused f32 backend shards too; fronts are checked for set-level
    agreement with its own single-device pass (bitwise within backend)."""
    from repro.core.sweep import shard_sweep

    profiles = random_profiles(3, seed=8)
    single = run_sweep(profiles, n=96, backend="pallas")
    sharded = shard_sweep(profiles, n=96, backend="pallas", num_shards=3)
    assert sharded.pareto_names() == [
        single.machines.names[i] for i in single.pareto_front()]
    for app in single.apps:
        assert sharded.best_fit(app) == single.best_fit(app)


def test_shard_bounds_cover_and_balance():
    from repro.core.sweep import _shard_bounds

    for v, s in [(10, 3), (7, 7), (5, 2), (1, 1), (128, 4)]:
        bounds = _shard_bounds(v, s)
        assert bounds[0][0] == 0 and bounds[-1][1] == v
        sizes = [hi - lo for lo, hi in bounds]
        assert sum(sizes) == v
        assert max(sizes) - min(sizes) <= 1
        for (_, hi), (lo, _) in zip(bounds, bounds[1:]):
            assert hi == lo


def test_shard_sweep_custom_cost_model_front_complete():
    """Fronts are extracted under the SAME cost model the shards were
    pre-filtered with (stored on the result), so reweighted sweeps stay
    front-complete vs the single-device reference."""
    from repro.core.costmodel import CostModel
    from repro.core.sweep import (pareto_front_indices, shard_sweep)

    cm = CostModel(area_weights={"peak_flops": 4.0, "hbm_bw": 1.0,
                                 "ici_bw_total": 0.5, "inter_pod_bw": 0.5})
    profiles = random_profiles(3, seed=31)
    single = run_sweep(profiles, n=120)
    sharded = shard_sweep(profiles, n=120, num_shards=5, cost_model=cm)
    # single-device reference fronts under the same custom model
    ref2 = [single.machines.names[i] for i in pareto_front_indices(
        cm.area(single.machines), single.aggregate_mean())]
    ref3 = [single.machines.names[i] for i in single.pareto_front_3d(cm)]
    assert sharded.pareto_names() == ref2
    assert [sharded.result.machines.names[i]
            for i in sharded.pareto_front_3d()] == ref3
    assert sharded.cost_model is cm



def test_shard_sweep_prefilter_equals_loop_oracles(monkeypatch):
    """The per-shard pre-filter keeps the same survivors, and the sweep
    the same fronts, as with the Python-loop extractions patched in; both
    are called through the module's attributes."""
    from repro.core.sweep import shard_sweep

    profiles = random_profiles(4, seed=13)
    kwargs = dict(n=3000, num_shards=6, include_named=VARIANTS)
    vectorized = shard_sweep(profiles, **kwargs)
    calls = []

    def counted(fn):
        def inner(*args):
            calls.append(fn.__name__)
            return fn(*args)
        return inner

    monkeypatch.setattr(sweep_module, "pareto_front_indices",
                        counted(loop_pareto_front_indices))
    monkeypatch.setattr(sweep_module, "pareto_front_indices_3d",
                        counted(loop_pareto_front_indices_3d))
    looped = shard_sweep(profiles, **kwargs)
    front2, front3 = looped.pareto_front(), looped.pareto_front_3d()
    assert len(calls) == 2 * looped.num_shards + 2
    assert (vectorized.candidate_indices.tolist()
            == looped.candidate_indices.tolist())
    assert vectorized.pareto_front() == front2
    assert vectorized.pareto_front_3d() == front3


def test_shard_sweep_multidevice_pad_masking():
    """Regression: on a multi-device mesh with V not divisible by the
    device count, the benign all-1.0 pad machines must never win an app's
    argmin in the sharded jax statistics pass.  Needs a forced 8-device
    host, so it runs in a subprocess (XLA_FLAGS must precede jax import).
    """
    import os
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        from repro.core import WorkloadProfile, run_sweep, shard_sweep
        # interconnect-dominated profile: makes cheap pad machines look good
        apps = [WorkloadProfile(name="app0", flops=1e10, hbm_bytes=1e9,
                                collective_bytes={"all-reduce": 5e13},
                                num_devices=256, model_flops=1e12)]
        sharded = shard_sweep(apps, n=1001, backend="jax")   # 1001 % 8 != 0
        single = run_sweep(apps, n=1001, backend="jax")
        assert sharded.best_fit("app0") == single.best_fit("app0"), (
            sharded.best_fit("app0"), single.best_fit("app0"))
        assert sharded.pareto_names() == [
            single.machines.names[i] for i in single.pareto_front()]
        print("OK", sharded.num_shards)
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src")
    env.pop("REPRO_SWEEP_BACKEND", None)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.startswith("OK 8")


# --------------------------------------------------------------------------- #
# streamed populations + resumable mega-sweeps
# --------------------------------------------------------------------------- #


def _assert_batch_equal(a, b):
    from repro.core.sweep import SWEEP_PARAMS

    assert a.names == b.names
    for field in SWEEP_PARAMS:
        np.testing.assert_array_equal(getattr(a, field), getattr(b, field))


@pytest.mark.parametrize("mode", ["random", "grid"])
def test_population_stream_matches_materialized(mode):
    """Index-addressed regeneration: any batch()/take() of the stream is
    byte-identical to slicing the materialized population -- the property
    that makes streamed sweep results exact, not approximate."""
    from repro.core.sweep import PopulationStream, _population

    space = ParamSpace.default()
    stream = PopulationStream(space, 200, mode=mode, seed=5,
                              include_named=VARIANTS)
    full = _population(space, 200, mode, 5, VARIANTS)
    assert len(stream) == len(full)
    _assert_batch_equal(stream.materialize(), full)
    # shard spanning the named/generated boundary, plus interior shards
    for lo, hi in [(0, 7), (1, 40), (50, 120), (len(full) - 9, len(full))]:
        _assert_batch_equal(stream.batch(lo, hi), full.slice(lo, hi))
    # arbitrary gather mixing named + generated rows (the survivor path)
    idx = np.array([0, 2, 17, 5, 100, 1, len(full) - 1])
    _assert_batch_equal(stream.take(idx), full.take(idx))


def test_index_named_batch_behaves_like_its_name_list(tmp_path):
    """A generated batch carries a prefix and global indices instead of
    names; every sub-batch keeps them unformatted, and the names read
    back are exactly the eager ``f"sweep-{i:05d}"`` list."""
    from repro.core import spans
    from repro.core.sweep import load_population, save_population

    lazy = ParamSpace.default().sample_at(np.arange(100, 160), seed=4)
    eager = [f"sweep-{i:05d}" for i in range(100, 160)]
    named = MachineBatch.from_models(VARIANTS)
    built = spans.counters()["names_built"]
    rows = [3, 0, 59, 7]
    parts = {
        "slice": (lazy.slice(5, 20), eager[5:20]),
        "take": (lazy.take(rows), [eager[i] for i in rows]),
        "select": (lazy.select(7), [eager[7]]),
        "concat": (MachineBatch.concat(lazy.slice(0, 10), lazy.slice(30, 40)),
                   eager[:10] + eager[30:40]),
    }
    mixed = MachineBatch.concat(named, lazy.slice(0, 3))
    assert mixed.names == [m.name for m in VARIANTS] + eager[:3]
    assert spans.counters()["names_built"] == built + 3
    for kind, (batch, want) in parts.items():
        assert batch.name_index is not None, kind
        assert len(batch) == len(want), kind
        assert batch.names == want, kind
    assert isinstance(lazy.names, list) and lazy.names == eager
    assert list(iter(lazy.names)) == eager
    assert lazy.names.index("sweep-00150") == 50
    assert lazy.model(2).name == "sweep-00102"
    # a slice of a formatted batch reads the formatted list
    assert lazy.slice(1, 3).names == eager[1:3]
    save_population(str(tmp_path / "pop"), lazy.take(rows))
    back = load_population(str(tmp_path / "pop")).materialize()
    assert back.names == [eager[i] for i in rows]
    np.testing.assert_array_equal(back.peak_flops, lazy.take(rows).peak_flops)


def test_machine_batch_takes_names_or_an_index():
    cols = {name: np.ones(2) for name in sweep_module.SWEEP_PARAMS}
    with pytest.raises(TypeError):
        MachineBatch(**cols)
    with pytest.raises(TypeError):
        MachineBatch(names=["a", "b"], name_index=np.arange(2), **cols)
    with pytest.raises(TypeError):
        MachineBatch(names=["a", "b"], peak_flops=np.ones(2))


def test_save_load_population_roundtrip(tmp_path):
    from repro.core.sweep import (PopulationStream, _population,
                                  load_population, save_population)

    space = ParamSpace.default()
    full = _population(space, 150, "random", 9, VARIANTS)
    save_population(str(tmp_path / "pop"), full, shard_size=64)
    loaded = load_population(str(tmp_path / "pop"))
    assert len(loaded) == len(full)
    _assert_batch_equal(loaded.materialize(), full)
    _assert_batch_equal(loaded.batch(10, 90), full.slice(10, 90))
    _assert_batch_equal(loaded.take([3, 77, 0, 149]),
                        full.take([3, 77, 0, 149]))
    assert loaded.signature().startswith("mmap:")
    # saving a STREAM (not a batch) never materializes but writes the same
    stream = PopulationStream(space, 150, seed=9, include_named=VARIANTS)
    save_population(str(tmp_path / "pop2"), stream, shard_size=32)
    _assert_batch_equal(load_population(str(tmp_path / "pop2")).materialize(),
                        full)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_streamed_shard_sweep_byte_identical(backend):
    """ISSUE acceptance: stream=True changes memory behavior, not results.
    Candidates, fronts, best fits and aggregates match the materialized
    shard_sweep AND run_sweep bit for bit."""
    from repro.core.sweep import shard_sweep

    profiles = random_profiles(4, seed=5)
    kw = dict(n=150, include_named=VARIANTS, backend=backend, num_shards=5)
    materialized = shard_sweep(profiles, **kw)
    streamed = shard_sweep(profiles, stream=True, **kw)
    assert streamed.streamed and not materialized.streamed
    np.testing.assert_array_equal(streamed.candidate_indices,
                                  materialized.candidate_indices)
    assert streamed.result.machines.names == materialized.result.machines.names
    np.testing.assert_array_equal(streamed.result.aggregate,
                                  materialized.result.aggregate)
    assert streamed.pareto_names() == materialized.pareto_names()
    assert streamed.best_fit_map == materialized.best_fit_map
    single = run_sweep(profiles, n=150, include_named=VARIANTS,
                       backend=backend)
    assert streamed.pareto_names() == [
        single.machines.names[i] for i in single.pareto_front()]
    for app in single.apps:
        assert streamed.best_fit(app) == single.best_fit(app)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_stream_batch_sweep_matches_materialized(backend):
    """A population of ``PopulationStream.batch`` rows, names unformatted,
    swept as a batch gives the fronts and best fits of the streamed and
    the materialized sweeps."""
    from repro.core.sweep import PopulationStream, shard_sweep

    profiles = random_profiles(4, seed=11)
    stream = PopulationStream(ParamSpace.default(), 300, seed=13)
    population = stream.batch(0, len(stream))
    assert population.name_index is not None
    kw = dict(n=300, seed=13, backend=backend, num_shards=3)
    streamed = shard_sweep(profiles, stream=True, **kw)
    materialized = shard_sweep(profiles, **kw)
    from_batch = shard_sweep(profiles, population=population, **kw)
    for res in (streamed, from_batch):
        assert res.pareto_names() == materialized.pareto_names()
        assert res.best_fit_map == materialized.best_fit_map
        for app in materialized.apps:
            assert res.best_fit(app) == materialized.best_fit(app)


def test_mmap_population_sweep_matches_generated(tmp_path):
    from repro.core.sweep import load_population, save_population, shard_sweep

    profiles = random_profiles(3, seed=19)
    direct = shard_sweep(profiles, n=96, num_shards=3)
    save_population(str(tmp_path / "pop"),
                    run_sweep(profiles, n=96).machines)
    via_mmap = shard_sweep(profiles, population=load_population(
        str(tmp_path / "pop")), num_shards=3)
    assert via_mmap.streamed
    assert via_mmap.pareto_names() == direct.pareto_names()
    assert via_mmap.best_fit_map == direct.best_fit_map
    np.testing.assert_array_equal(via_mmap.result.aggregate,
                                  direct.result.aggregate)


def _sharded_equal(a, b):
    np.testing.assert_array_equal(a.candidate_indices, b.candidate_indices)
    assert a.result.machines.names == b.result.machines.names
    np.testing.assert_array_equal(a.result.aggregate, b.result.aggregate)
    assert a.pareto_names() == b.pareto_names()
    assert a.best_fit_map == b.best_fit_map


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_resumed_sweep_identical_to_uninterrupted(tmp_path, backend):
    """ISSUE acceptance: kill after shard k, resume -> byte-identical
    result, with resumed_shards reporting the skipped prefix."""
    from repro.core.sweep import shard_sweep

    profiles = random_profiles(3, seed=29)
    kw = dict(n=120, stream=True, num_shards=6, backend=backend,
              checkpoint_dir=str(tmp_path / "ck"))

    class Kill(Exception):
        pass

    def die_after_2(s, num_shards, lo, hi):
        if s >= 2:
            raise Kill

    with pytest.raises(Kill):
        shard_sweep(profiles, progress=die_after_2, **kw)
    events = []
    resumed = shard_sweep(profiles, resume=True,
                          progress=lambda s, n_, lo, hi:
                          events.append(s), **kw)
    assert resumed.resumed_shards == 3   # shards 0-2 checkpointed pre-raise
    assert events == [3, 4, 5]           # only the remaining shards ran
    straight = shard_sweep(profiles, n=120, stream=True, num_shards=6,
                           backend=backend)
    assert straight.resumed_shards == 0
    _sharded_equal(resumed, straight)
    # markdown/json agree modulo the resume being invisible in the result
    assert resumed.markdown(top_k=4) == straight.markdown(top_k=4)


def test_resume_refuses_config_mismatch(tmp_path):
    from repro.core.sweep import shard_sweep

    profiles = random_profiles(2, seed=3)
    shard_sweep(profiles, n=64, num_shards=4,
                checkpoint_dir=str(tmp_path / "ck"))
    with pytest.raises(ValueError, match="different sweep configuration"):
        shard_sweep(profiles, n=64, num_shards=4, seed=1, resume=True,
                    checkpoint_dir=str(tmp_path / "ck"))
    with pytest.raises(ValueError, match="requires checkpoint_dir"):
        shard_sweep(profiles, n=64, resume=True)


def test_shard_progress_events_all_backends():
    """Satellite regression: every backend (including the mesh-distributed
    jax path, which once collapsed to a single progress(0, 1, ...) call)
    emits one event per shard with covering [lo, hi) bounds."""
    from repro.core.sweep import shard_sweep

    profiles = random_profiles(2, seed=7)
    for backend in ("numpy", "jax", "pallas"):
        events = []
        shard_sweep(profiles, n=64, num_shards=4, backend=backend,
                    progress=lambda s, n_, lo, hi:
                    events.append((s, n_, lo, hi)))
        assert [e[0] for e in events] == [0, 1, 2, 3], backend
        assert all(n_ == 4 for _, n_, _lo, _hi in events)
        assert events[0][2] == 0 and events[-1][3] == 64
        for (_, _, _, hi), (_, _, lo, _) in zip(events, events[1:]):
            assert hi == lo


def test_pallas_shard_map_multidevice_streamed_resume():
    """The tentpole end to end on a forced 8-device host: ONE fused
    pallas_call under shard_map scores each chunk with the variant axis
    split over the mesh, streamed + resumed, and the result matches the
    numpy host-chunked reference exactly.  Subprocess because XLA_FLAGS
    must precede the jax import."""
    import os
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import numpy as np, tempfile
        from repro.core import VARIANTS, WorkloadProfile, shard_sweep

        apps = [WorkloadProfile(name="app0", flops=2e14, hbm_bytes=1.5e11,
                                collective_bytes={"all-reduce": 2e10},
                                num_devices=256, model_flops=5e16),
                WorkloadProfile(name="app1", flops=8e13, hbm_bytes=4e11,
                                collective_bytes={"all-gather": 6e10},
                                num_devices=64, model_flops=1e16)]
        kw = dict(n=517, stream=True, include_named=VARIANTS, num_shards=4)
        ref = shard_sweep(apps, backend="numpy", **kw)
        pal = shard_sweep(apps, backend="pallas", **kw)
        assert pal.mesh_axis == "variants=8 mesh", pal.mesh_axis
        assert pal.pareto_names() == ref.pareto_names()
        assert pal.best_fit_map == ref.best_fit_map
        np.testing.assert_array_equal(pal.candidate_indices,
                                      ref.candidate_indices)

        d = tempfile.mkdtemp()
        class Kill(Exception):
            pass
        def die(s, n_, lo, hi):
            if s >= 1:
                raise Kill
        try:
            shard_sweep(apps, backend="pallas", checkpoint_dir=d,
                        progress=die, **kw)
        except Kill:
            pass
        resumed = shard_sweep(apps, backend="pallas", checkpoint_dir=d,
                              resume=True, **kw)
        assert resumed.resumed_shards == 2
        assert resumed.pareto_names() == pal.pareto_names()
        assert resumed.best_fit_map == pal.best_fit_map
        np.testing.assert_array_equal(resumed.result.aggregate,
                                      pal.result.aggregate)
        print("PALLAS-MEGA-OK")
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src")
    env.pop("REPRO_SWEEP_BACKEND", None)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "PALLAS-MEGA-OK" in proc.stdout


@pytest.mark.slow
def test_streamed_million_variant_sweep():
    """ISSUE acceptance: V = 1M streams through a single host without the
    population ever materializing (each shard holds <= 64k variants)."""
    from repro.core.sweep import STREAM_SHARD_VARIANTS, shard_sweep

    profiles = random_profiles(2, seed=1)
    events = []
    sharded = shard_sweep(profiles, n=1_000_000, stream=True,
                          progress=lambda s, n_, lo, hi:
                          events.append(hi - lo))
    assert sharded.streamed
    assert sharded.num_variants == 1_000_000
    assert max(events) <= STREAM_SHARD_VARIANTS
    assert sharded.num_shards == len(events) >= 16
    assert 0 < len(sharded.result.machines) < 5000
    assert set(sharded.best_fit_map) == {p.name for p in profiles}
    front = sharded.pareto_names()
    assert front and all(isinstance(n, str) for n in front)
