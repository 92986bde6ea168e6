#!/usr/bin/env python3
"""Drive the scoring path once on a TPU and check it against numpy.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the mesh-sharded path only

One process, no children.  The workload is 128 apps: the six measured
``zoo-smoke`` cells checked in under ``src/repro/core/zoo_cache/`` plus the
seeded generated suite ``gen:122:seed=0``.  The machine populations are
Halton samples of ``ParamSpace.default()`` from seed 0.

On one chip:

* **sweep** -- ``run_sweep`` on the fused Pallas kernel at V = 65,536,
  against ``backend="numpy"`` on the same population;
* **mega-sweep** -- streamed ``shard_sweep`` on pallas at V = 1,048,576
  (16 shards of 65,536), against the numpy streamed ``shard_sweep``;
* **co-design** -- ``grad_codesign`` (20 steps) and ``frontier_codesign``
  (budgets 0.5 and 1.0) on the x64 jax backend from the named variants,
  each optimum re-evaluated by the numpy objective;
* **service** -- one ``CodesignService``: four pallas sweep requests that
  are micro-batched into one pass, one mega-sweep and one frontier request.

With ``--chips 4``: the streamed ``shard_sweep`` at V = 1,048,576 on a
4-device ``("variants",)`` mesh, on pallas and on jax, against numpy, plus a
check that the per-device argmin merge picks the first occurrence whatever
order the mesh gives the devices.

Scores must agree with numpy within the pinned 5e-4 (absolute plus
relative, as ``tests/test_backends.py`` pins pallas).  Best fits and Pareto
fronts must be identical, except where numpy's own scores for the two
candidates lie within that tolerance.  Any failed check exits 1.  The last
line of standard output is a JSON object naming the device, printed only
when every phase passed.  Off a TPU the script exits 1 before any work.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

try:
    import numpy as np

    from repro.launch.compile_cache import enable_compile_cache
except ImportError as exc:
    print(f"chip_smoke: the repro package is not importable from {ROOT}/src "
          f"({exc}); run this script from a checkout of the repository",
          file=sys.stderr)
    sys.exit(2)

TOL = 5e-4            # pallas == numpy pin, absolute + relative
CODESIGN_RTOL = 1e-6  # jax optimum re-evaluated in numpy (tests/test_codesign.py)
SWEEP_V = 1 << 16
MEGA_V = 1 << 20


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class Clock:
    """Host wall time of one phase, compilation included."""

    def __init__(self, label: str):
        self.label = label

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            print(f"[{self.label}] {time.perf_counter() - self.t0:.1f} s wall "
                  "(run, compile included)", flush=True)


# --------------------------------------------------------------------------- #
# Comparison helpers
# --------------------------------------------------------------------------- #


def _tol(ref) -> np.ndarray:
    return TOL + TOL * np.abs(ref)


def compare_scores(label: str, got, ref) -> None:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    check(got.shape == ref.shape, f"{label}: shape {got.shape} != {ref.shape}")
    check(bool(np.isfinite(got).all()), f"{label}: non-finite values")
    err = np.abs(got - ref)
    worst = float(np.max(err / _tol(ref))) if err.size else 0.0
    print(f"  {label}: max |diff| {float(err.max()):.3e} over {err.size} "
          f"cells ({worst:.3f} of the 5e-4 pin)")
    check(worst <= 1.0, f"{label}: outside the 5e-4 pin ({worst:.3f}x)")


def compare_best_fits(label: str, got_idx, ref_idx, ref_agg) -> None:
    """``ref_agg`` is numpy's (A, N) aggregate over the index space of the
    two ``(A,)`` best-fit index vectors."""
    got_idx, ref_idx = np.asarray(got_idx), np.asarray(ref_idx)
    apps = np.arange(len(ref_idx))
    differ = got_idx != ref_idx
    gap = ref_agg[apps, got_idx] - ref_agg[apps, ref_idx]
    bad = np.nonzero(differ & (gap > _tol(ref_agg[apps, ref_idx])))[0]
    print(f"  {label}: {int(differ.sum())} of {len(apps)} differ, "
          f"{len(bad)} beyond a near-tie")
    check(not len(bad), f"{label}: best fits differ for apps {bad[:10]}")


def compare_fronts(label: str, got, ref, agg, area, power=None) -> None:
    """Fronts as index lists into the candidate arrays ``agg`` (numpy's
    suite-mean aggregate), ``area`` and ``power`` (3-D only).

    An index only on ``got`` must be nondominated in numpy up to the
    tolerance; an index only on ``ref`` must be nearly dominated, in
    numpy, by some other candidate.  Anything else is a real difference.
    """
    bad = []
    for i in sorted(set(got) ^ set(ref)):
        below = area <= area[i]
        if power is not None:
            below &= power <= power[i]
        below[i] = False
        if i in got:   # numpy drops it: its dominators may win only by a tie
            ok = not np.any(below & (agg < agg[i] - _tol(agg[i])))
        else:          # the backend drops it: a near-dominator must exist
            ok = bool(np.any(below & (agg <= agg[i] + _tol(agg[i]))))
        if not ok:
            bad.append(i)
    diff = len(set(got) ^ set(ref))
    print(f"  {label}: {len(got)} vs {len(ref)} points, {diff} differ, "
          f"{len(bad)} beyond a near-tie")
    check(not bad, f"{label}: fronts differ at candidates {bad[:10]}")


# --------------------------------------------------------------------------- #
# Phases
# --------------------------------------------------------------------------- #


def load_profiles():
    from repro.core.model_zoo import resolve_suite

    zoo = resolve_suite("zoo-smoke", extract_missing=False)
    gen = resolve_suite("gen:122:seed=0")
    profiles = list(zoo) + list(gen)
    print(f"profiles: {len(zoo)} zoo-smoke cells + {len(gen)} gen:122:seed=0 "
          f"= {len(profiles)} apps")
    return profiles


def phase_sweep(profiles, v: int) -> None:
    from repro.core import DEFAULT_COST_MODEL as CM
    from repro.core.sweep import run_sweep

    with Clock(f"sweep pallas V={v}"):
        res_p = run_sweep(profiles, n=v, seed=0, backend="pallas")
    with Clock(f"sweep numpy V={v}"):
        res_n = run_sweep(profiles, n=v, seed=0, backend="numpy")
    check(res_p.machines.names == res_n.machines.names, "populations differ")
    compare_scores("beta", res_p.beta, res_n.beta)
    compare_scores("aggregate", res_p.aggregate, res_n.aggregate)
    for k in res_n.scores:
        compare_scores(f"score {k}", res_p.scores[k], res_n.scores[k])
    compare_best_fits("best fits", res_p.best_fit_indices(),
                      res_n.best_fit_indices(), res_n.aggregate)
    agg = res_n.aggregate_mean()
    area, power = CM.area(res_n.machines), CM.power(res_n.machines)
    compare_fronts("2-D front", res_p.pareto_front(), res_n.pareto_front(),
                   agg, area)
    compare_fronts("3-D front", res_p.pareto_front_3d(),
                   res_n.pareto_front_3d(), agg, area, power)


def _stream_sweep(profiles, v: int, backend: str, mesh=None):
    from repro.core.sweep import shard_sweep

    with Clock(f"mega-sweep {backend} V={v}"):
        return shard_sweep(profiles, n=v, seed=0, stream=True,
                           backend=backend, mesh=mesh)


def compare_mega(label: str, got, ref, profiles) -> None:
    """Compare two streamed ``shard_sweep`` results over one population."""
    from repro.core import DEFAULT_COST_MODEL as CM
    from repro.core.sweep import ParamSpace, PopulationStream, batched_congruence

    check(got.num_variants == ref.num_variants, f"{label}: V differs")
    union = np.union1d(got.candidate_indices, ref.candidate_indices)
    pos = {int(g): j for j, g in enumerate(union)}
    src = PopulationStream(ParamSpace.default(), ref.num_variants, seed=0)
    batch = src.take(union)
    ref_all = batched_congruence(profiles, batch, beta=ref.result.beta,
                                 clamp=True, backend="numpy")
    print(f"  {label}: {len(got.candidate_indices)} vs "
          f"{len(ref.candidate_indices)} survivors of {ref.num_variants}")
    mine = [pos[int(g)] for g in got.candidate_indices]
    compare_scores(f"{label} survivor aggregate", got.result.aggregate,
                   ref_all.aggregate[:, mine])
    names = {n: j for j, n in enumerate(batch.names)}
    got_best = [names[got.best_fit(a)] for a in ref.apps]
    ref_best = [names[ref.best_fit(a)] for a in ref.apps]
    compare_best_fits(f"{label} best fits", got_best, ref_best,
                      ref_all.aggregate)

    def front(res, three_d):
        idx = res.pareto_front_3d() if three_d else res.pareto_front()
        return [pos[int(res.candidate_indices[i])] for i in idx]

    agg = ref_all.aggregate_mean()
    area, power = CM.area(batch), CM.power(batch)
    compare_fronts(f"{label} 2-D front", front(got, False), front(ref, False),
                   agg, area)
    compare_fronts(f"{label} 3-D front", front(got, True), front(ref, True),
                   agg, area, power)


def phase_codesign(profiles) -> None:
    from repro.core import VARIANTS, frontier_codesign, grad_codesign
    from repro.core.codesign import scalarized_objective
    from repro.core.sweep import MachineBatch, default_beta_batched

    seeds = MachineBatch.from_models(VARIANTS)
    beta = default_beta_batched(profiles, seeds)  # the seed-0 convention
    with Clock("grad_codesign jax 20 steps"):
        cd = grad_codesign(profiles, seeds, steps=20)
    check(bool(np.all(cd.objective_final <= cd.objective_seed)),
          "grad_codesign regressed a seed")
    ref = scalarized_objective(profiles, MachineBatch.from_models(cd.models()),
                               beta=beta)
    rel = np.abs(ref - cd.objective_final) / np.abs(ref)
    print(f"  grad_codesign: J {cd.objective_seed.round(6).tolist()} -> "
          f"{cd.objective_final.round(6).tolist()}; numpy re-evaluation "
          f"max rel diff {float(rel.max()):.3e}")
    check(float(rel.max()) <= CODESIGN_RTOL,
          f"grad_codesign optimum disagrees with numpy ({float(rel.max()):.3e})")

    with Clock("frontier_codesign jax budgets [0.5, 1.0]"):
        fr = frontier_codesign(profiles, seeds, [0.5, 1.0], steps=6,
                               refine_steps=2)
    check(bool(fr.feasible.any()), "frontier has no feasible point")
    for b, j, ok in zip(fr.budgets, fr.objective, fr.feasible):
        if not ok:
            continue
        ref = scalarized_objective(
            profiles, MachineBatch.from_models([fr.best_at(float(b))]),
            beta=beta)[0]
        rel = abs(ref - j) / abs(ref)
        print(f"  frontier budget {b}: J* {j:.6f}, numpy {ref:.6f}, "
              f"rel diff {rel:.3e}")
        check(rel <= CODESIGN_RTOL, f"frontier J* at {b} disagrees with numpy")


def phase_service(profiles, v: int) -> None:
    from repro.core import CodesignSpec
    from repro.core.sweep import run_sweep
    from repro.serving.codesign_service import (
        DONE,
        CodesignRequest,
        CodesignService,
    )

    suites = [profiles[i::4] for i in range(4)]
    sweep_spec = CodesignSpec(n=v, seed=0, backend="pallas")
    svc = CodesignService(auto_start=False)
    try:
        with Clock(f"service: 4 sweeps V={v} + mega-sweep + frontier"):
            sweeps = [svc.submit(CodesignRequest(kind="sweep", profiles=s,
                                                 spec=sweep_spec))
                      for s in suites]
            mega = svc.submit(CodesignRequest(
                kind="mega_sweep", profiles=suites[0], stream=True,
                spec=CodesignSpec(n=4 * v, seed=0, backend="pallas")))
            frontier = svc.submit(CodesignRequest(
                kind="frontier", profiles=suites[0],
                spec=CodesignSpec(budgets=[0.5, 1.0], steps=6,
                                  refine_steps=2)))
            svc.drain()
        for jid in sweeps + [mega, frontier]:
            state = svc.poll(jid)
            check(state["state"] == DONE,
                  f"service job {state['kind']} ended {state['state']}")
        print(f"  service: stats {dict(svc.stats)}")
        check(svc.stats["batched_requests"] == 4,
              "the four sweep requests were not micro-batched into one pass")
        for s, jid in zip(suites, sweeps):
            got = svc.result(jid)
            direct = run_sweep(s, n=v, seed=0, backend="pallas")
            check(np.array_equal(got.aggregate, direct.aggregate)
                  and np.array_equal(got.beta, direct.beta),
                  "a micro-batched sweep differs from its direct run_sweep")
        print("  service: every batched result equals its direct run_sweep")
    finally:
        svc.shutdown()


def phase_merge_order(profiles, mesh) -> None:
    """The host merge of per-device minima must pick the first occurrence
    in variant order, whatever order the mesh holds the devices in."""
    from jax.sharding import Mesh

    from repro.core import get_backend
    from repro.core.sweep import (
        MachineBatch,
        ParamSpace,
        ProfileBatch,
        default_beta_batched,
    )

    half = ParamSpace.default().sample(128 * mesh.size // 2, seed=1)
    twice = MachineBatch.concat(half, half)  # every minimum occurs twice
    pb = ProfileBatch.from_profiles(profiles[:8])
    beta = default_beta_batched(pb, half)
    p = pb.arrays()
    reordered = Mesh(mesh.devices[::-1], mesh.axis_names)
    print(f"  mesh device ids {[d.id for d in mesh.devices.flat]}, "
          f"reversed {[d.id for d in reordered.devices.flat]}")
    for name in ("pallas", "jax"):
        be = get_backend(name)
        a = be.sharded_stats(p, twice.arrays(), beta, mesh, clamp=True)
        b = be.sharded_stats(p, twice.arrays(), beta, reordered, clamp=True)
        flat = be.congruence(p, twice.arrays(), beta, clamp=True)
        first = np.argmin(be.to_numpy(flat.aggregate), axis=1)
        check(all(np.array_equal(x, y) for x, y in zip(a, b)),
              f"{name}: sharded stats depend on the mesh's device order")
        check(np.array_equal(a[2], first),
              f"{name}: merged argmin {a[2]} is not the first occurrence "
              f"{first}")
        check(bool(np.all(a[2] < len(half))), f"{name}: argmin in the copy")
    print("  merge: first-occurrence argmins on both device orders "
          "(pallas, jax)")


# --------------------------------------------------------------------------- #
# Entry point
# --------------------------------------------------------------------------- #


def device_check(chips: int):
    import jax

    from repro.core import get_backend

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SmokeFailure(f"no TPU: jax found platform {platform!r} "
                           f"({len(devices)} device(s))")
    if len(devices) < chips:
        raise SmokeFailure(f"--chips {chips} but jax found {len(devices)}")
    if get_backend("pallas").interpret:
        raise SmokeFailure("the pallas backend chose interpret mode on a TPU")
    print(f"device: {devices[0].device_kind}, {len(devices)} device(s), "
          f"platform {platform}", flush=True)
    return devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-sharded mega-sweep on four "
                         "chips")
    args = ap.parse_args(argv)

    enable_compile_cache()
    try:
        devices = device_check(args.chips)
        t0 = time.perf_counter()
        profiles = load_profiles()
        if args.chips == 1:
            print("== sweep", flush=True)
            phase_sweep(profiles, SWEEP_V)
            print("== mega-sweep", flush=True)
            compare_mega("pallas vs numpy",
                         _stream_sweep(profiles, MEGA_V, "pallas"),
                         _stream_sweep(profiles, MEGA_V, "numpy"), profiles)
            print("== co-design", flush=True)
            phase_codesign(profiles)
            print("== service", flush=True)
            phase_service(profiles, SWEEP_V)
        else:
            from repro.launch.mesh import make_variant_mesh

            mesh = make_variant_mesh(args.chips)
            print(f"== mega-sweep on a {mesh.size}-device mesh", flush=True)
            ref = _stream_sweep(profiles, MEGA_V, "numpy")
            for name in ("pallas", "jax"):
                got = _stream_sweep(profiles, MEGA_V, name, mesh=mesh)
                check(got.mesh_axis == f"variants={args.chips} mesh",
                      f"{name} ran {got.mesh_axis}, not on the mesh")
                compare_mega(f"{name} vs numpy", got, ref, profiles)
            print("== merge order", flush=True)
            phase_merge_order(profiles, mesh)
        print(f"all phases passed in {time.perf_counter() - t0:.1f} s wall",
              flush=True)
    except SmokeFailure as exc:
        print(f"chip_smoke FAILED: {exc}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
