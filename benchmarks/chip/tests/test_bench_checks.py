"""The checks catch what they exist to catch, at a size a CPU test holds.

* The control -- the reference one precision lower (bfloat16 for the
  float32 sweeps, float32 for the float64 co-design) answering in the
  program's place -- comes out not correct in every cell.
* A run with the timed path broken underneath comes out not correct, once
  for each fault the cell can have: half of the batch left out of a mean,
  an answer altered where it is produced, a descent step that returns
  its state unchanged, and a descent that stops moving after the steps
  compared one by one.  No cell runs on four chips yet; a mega-sweep cell
  on a four-device mesh, built here, shows that leaving out the exchange
  between chips is caught too.
"""

import json
import time

import numpy as np
import pytest

import control
import harness

SEED = 2**31 + 4242


@pytest.mark.parametrize("cell", ["zoo128.mega", "gen1000.sweep",
                                  "zoo128.codesign"])
def test_control_is_not_correct(tiny, cell):
    spec = harness.Spec.load(tiny, tiny / "bench")
    worst, limits = control.control_judged(spec, cell, SEED)
    assert any(not v <= limits[k] for k, v in worst.items()), worst


# --------------------------------------------------------------------------- #
# Faults planted in the program
# --------------------------------------------------------------------------- #


def half_batch_sweep(mp):
    """The suite mean taken over the first half of the apps only."""
    from repro.core.sweep import SweepResult

    mp.setattr(SweepResult, "aggregate_mean",
               lambda self: self.aggregate[: len(self.aggregate) // 2]
               .mean(axis=0))


def altered_sweep(mp):
    """One app's aggregates altered where the kernel produces them."""
    from repro.core.kernels_pallas import PallasBackend

    original = PallasBackend.congruence

    def congruence(self, *args, **kwargs):
        out = original(self, *args, **kwargs)
        agg = np.array(out.aggregate)
        agg[0] += 0.5
        return out._replace(aggregate=agg)

    mp.setattr(PallasBackend, "congruence", congruence)


def state_unchanged(mp):
    """Every descent step returns its state unchanged."""
    import repro.core.codesign as cd

    original = cd.backtracking_descent

    def descent(jax, jnp, theta0, obj_fn, steps, *args, **kwargs):
        return original(jax, jnp, theta0, obj_fn, 0, *args, **kwargs)

    mp.setattr(cd, "backtracking_descent", descent)


def frozen_after_compared_steps(mp):
    """The descent takes its first steps, then returns its state unchanged
    at every later step."""
    import repro.core.codesign as cd

    compared = harness.Spec(None, harness.HERE).module(
        "entries", "grad_codesign").COMPARED_STEPS
    original = cd.backtracking_descent

    def descent(jax, jnp, theta0, obj_fn, steps, *args, **kwargs):
        theta, f, history, aux, lr = original(
            jax, jnp, theta0, obj_fn, compared, *args, **kwargs)
        history = history + [history[-1]] * (steps - compared)
        return theta, f, history, aux, lr

    mp.setattr(cd, "backtracking_descent", descent)


def half_batch_codesign(mp):
    """The objective's mean over apps taken over the first half only."""
    import repro.core.codesign as cd

    original = cd._objective_terms

    def terms(xp, p, m, beta, *args, app_weights=None, **kwargs):
        a = p.flops.shape[0]
        v = m.peak_flops.shape[0]
        w = np.zeros((a, v))
        w[: a // 2] = 2.0 / a
        return original(xp, p, m, beta, *args, app_weights=xp.asarray(w),
                        **kwargs)

    mp.setattr(cd, "_objective_terms", terms)


def altered_codesign(mp):
    """The reported objective altered where the descent produces it."""
    import repro.core.codesign as cd

    original = cd.backtracking_descent

    def descent(*args, **kwargs):
        theta, f, history, aux, lr = original(*args, **kwargs)
        return theta, f * (1 + 1e-6), history, aux, lr

    mp.setattr(cd, "backtracking_descent", descent)


def exchange_left_out(mp):
    """The merge of per-chip minima sees only the first chip's."""
    from repro.core.kernels_pallas import PallasBackend

    original = PallasBackend._sharded_stats_fn

    def stats_fn(self, *args, **kwargs):
        fn = original(self, *args, **kwargs)

        def first_chip_only(p, m):
            agg, mins, idxs = fn(p, m)
            mins = np.array(mins)
            mins[1:] = np.inf          # other chips' minima never arrive
            return agg, mins, idxs
        return first_chip_only

    mp.setattr(PallasBackend, "_sharded_stats_fn", stats_fn)


FAULTS = [
    ("zoo128.mega", half_batch_sweep),
    ("zoo128.mega", altered_sweep),
    ("gen1000.sweep", half_batch_sweep),
    ("gen1000.sweep", altered_sweep),
    ("zoo128.codesign", state_unchanged),
    ("zoo128.codesign", frozen_after_compared_steps),
    ("zoo128.codesign", half_batch_codesign),
    ("zoo128.codesign", altered_codesign),
]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_fault_is_not_correct(tiny, monkeypatch, cell, fault):
    spec = harness.Spec.load(tiny, tiny / "bench")
    fault(monkeypatch)
    result = harness.run_cell(spec, cell, SEED, 0.2, False,
                              time.perf_counter())
    assert not result["correct"], result["checks"]
    assert result["failed"] >= 1


MESH4 = "zoo128.mega.mesh4"


def add_mesh4_cell(root):
    """``zoo128.mega`` on a four-device mesh, a cell of these tests alone."""
    bench = root / "bench"
    traffic = json.loads((bench / "traffic" / "mega.json").read_text())
    (bench / "traffic" / "mega.mesh4.json").write_text(
        json.dumps(dict(traffic, devices=4)))
    (bench / "limits" / f"{MESH4}.json").write_text(
        (bench / "limits" / "zoo128.mega.json").read_text())
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": MESH4, "config": "zoo128",
                              "traffic": "mega.mesh4", "chips": 4,
                              "why": "a test cell on a four-device mesh"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.mark.parametrize("fault", ["sound", "fault"])
def test_exchange_between_chips_left_out(tiny, monkeypatch, fault):
    """On four host devices the mesh splits each shard; with the other
    chips' minima dropped from the merge the run is not correct."""
    add_mesh4_cell(tiny)
    if fault == "fault":
        exchange_left_out(monkeypatch)
    result = harness.run_cell(harness.Spec.load(tiny, tiny / "bench"), MESH4,
                              SEED, 0.2, False, time.perf_counter())
    assert result["device"]["count"] == 4
    assert result["correct"] == (fault == "sound"), result["checks"]
