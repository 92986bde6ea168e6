import os

from repro.launch import xla_flags

xla_flags.request_host_devices(512)

"""Hillclimb tooling: measured substitution of the Pallas flash-attention
kernel into a dry-run profile.

The CPU dry-run artifact materializes (S x T) attention scores per layer (no
TPU fusion pipeline, no flash kernel -- Pallas can't compile for the CPU
backend).  On the TPU target, kernels/flash_attention.py keeps score tiles in
VMEM: per-layer attention HBM traffic collapses to the q/k/v/o streams.

Method (measured, not hand-modelled): attention-score traffic is the ONLY
HBM component quadratic in sequence length.  We compile three unrolled
depth-2 probes at S, S/2, S/4 and fit  h(s) = c + a*s + q*s^2 ; the
quadratic term q*S^2 is exactly the per-2-layer score traffic, which the
substitution removes and replaces with the kernel's linear q/k/v/o traffic.
FLOPs and collectives are untouched (the kernel does the same math; flash
backward recomputation is already covered by the remat-full baseline).

Usage:
  PYTHONPATH=src python -m repro.launch.hillclimb --arch chatglm3-6b \
      --shape train_4k [--mesh pod] [--moe-impl capacity] --out DIR \
      [--sweep N [--backend jax]] [--grad STEPS]

Co-design modes (after the kernel substitution):
  --sweep N      score N generated machine variants (batched kernels,
                 --backend numpy|jax) and report best fit + Pareto front.
  --grad STEPS   continuous co-design: jax.grad of the scalarized
                 (congruence, area, power) objective through the shared
                 kernels_xp layer, descending machine log-rates from the
                 named-variant seeds.
  --area-budget B / --power-budget P
                 constrain --grad to CostModel.area(m) <= B (and/or
                 power <= P) via repro.core.constrained; --constraint-mode
                 picks projected gradient (default) or augmented
                 Lagrangian, --opt-links relaxes ici_links continuously
                 and rounds with repair.
  --joint        joint (machine, sharding-variant) descent: compiles the
                 cell under every sharding variant (tp/zero1/fsdp) and
                 lets the descent pick per machine variant.  The kernel
                 substitution applies to the primary --variant cell only;
                 the other shardings enter as baseline compiles.
  --budget-sweep LO:HI:N
                 trace the feasibility frontier J*(budget) over N area
                 budgets from LO to HI by warm-started continuation
                 (repro.core.frontier) instead of a single budgeted run.
  --area-envelope K=V[,K=V...]
                 per-subsystem area envelopes (e.g. peak_flops=1.5,
                 hbm_bw=0.8) added as one constraint per entry to --grad
                 descent or to every --budget-sweep point.
  --pack M       multi-tenant packing: place the optimized profile plus
                 --pack-gen generated co-tenant workloads across M
                 machine instances (repro.core.packing); scalar budgets
                 read as fleet TOTALS in this mode.
"""

import argparse
import dataclasses
import json
import sys
import time

import numpy as np

import jax

from repro import configs as C
from repro.configs.shapes import ShapeSpec, resolve_shape
from repro.core import costs as CO
from repro.core import machine as M
from repro.core import roofline as R
from repro.distributed import ctx as CTX
from repro.distributed import sharding as SH
from repro.launch import mesh as MESH
from repro.launch.extract import (
    _cost_dict,
    _probe_cfg,
    default_variant,
    run_cell,
)
from repro.launch.specs import input_specs
from repro.models.config import Family


def _probe_hbm(cfg, shape, mesh, sc, seq_len: int, batch: int,
               state_dim: int = 0) -> float:
    pshape = ShapeSpec(shape.name, seq_len, batch, shape.kind)
    pcfg = _probe_cfg(cfg, 2)
    if state_dim and pcfg.ssm is not None:
        pcfg = pcfg.replace(
            ssm=dataclasses.replace(pcfg.ssm, state_dim=state_dim))
    cell = input_specs(pcfg, pshape, mesh, sc)
    with jax.set_mesh(mesh), CTX.use_rules(
            SH.activation_rules(mesh, sc, kind=shape.kind)):
        compiled = jax.jit(
            cell.step_fn, in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
            donate_argnums=cell.donate_argnums,
        ).lower(*cell.args).compile()
    return _cost_dict(compiled, 0)["hbm"]


def quadratic_attention_bytes(cfg, shape, mesh, sc) -> float:
    """q*S^2 for the 2-layer probe: measured score-related HBM traffic."""
    S, B = shape.seq_len, shape.global_batch
    ss = np.array([S, S // 2, S // 4], dtype=np.float64)
    hs = np.array([_probe_hbm(cfg, shape, mesh, sc, int(s), B) for s in ss])
    coeffs = np.polyfit(ss, hs, 2)  # [q, a, c]
    q = max(coeffs[0], 0.0)
    return float(q * S * S)


def flash_kernel_bytes_per_layer(cfg, shape, n_dev: int) -> float:
    """Linear q/k/v/o HBM traffic of the Pallas kernel (fwd+bwd), per device."""
    B, S = shape.global_batch, shape.seq_len
    bytes_q = B * S * cfg.q_dim * 2       # bf16
    bytes_kv = 2 * B * S * cfg.kv_dim * 2
    # fwd: read q,k,v write o ; bwd: read q,k,v,o,do write dq,dk,dv (+lse)
    total = 4 * (bytes_q * 2 + bytes_kv) if shape.kind == "train" else (
        bytes_q * 2 + bytes_kv)
    return total / n_dev


def scan_state_bytes(cfg, shape, mesh, sc) -> float:
    """Measured HBM traffic proportional to the SSM state dim N for the
    2-layer probe: exactly the dA/dBx/h chunk buffers the Pallas
    selective-scan kernel keeps in VMEM."""
    N = cfg.ssm.state_dim
    S, B = shape.seq_len, shape.global_batch
    h_full = _probe_hbm(cfg, shape, mesh, sc, S, B, state_dim=N)
    h_half = _probe_hbm(cfg, shape, mesh, sc, S, B, state_dim=N // 2)
    per_n = (h_full - h_half) / (N - N // 2)
    return max(per_n * N, 0.0)


def scan_kernel_bytes_per_layer(cfg, shape, n_dev: int) -> float:
    """Linear xi/dt/B/C/y traffic of the Pallas scan kernel, per device."""
    B, S = shape.global_batch, shape.seq_len
    d_in = cfg.ssm.expand * cfg.d_model
    n = cfg.ssm.state_dim
    io = B * S * (3 * d_in + 2 * n) * 2  # xi, dt, y (d_in) + B, C (n), bf16
    mult = 3.0 if shape.kind == "train" else 1.0
    return io * mult / n_dev


def machine_candidates(n: int, seed: int = 0):
    """Candidate generator for the co-design step: the paper's three named
    variants plus ``n`` low-discrepancy designs from the default ParamSpace.

    The named variants come first so the batched default-beta reference
    stays the baseline chip (same convention as ``dse.evaluate``)."""
    from repro.core.sweep import MachineBatch, ParamSpace

    return MachineBatch.concat(
        MachineBatch.from_models(M.VARIANTS),
        ParamSpace.default().sample(n, seed=seed))


def codesign_sweep(profile, n: int, seed: int = 0,
                   backend: str = None) -> dict:
    """Score one profile against a sweep population and summarize the
    co-design answer: best-fit variant + (area, congruence) Pareto front."""
    from repro.core.sweep import batched_congruence

    machines = machine_candidates(n, seed=seed)
    res = batched_congruence([profile], machines, clamp=True,
                             backend=backend)
    best = int(res.best_fit_indices()[0])
    front = res.pareto_front()
    return {
        "num_variants": len(machines),
        "backend": res.backend,
        "best_variant": machines.names[best],
        "best_aggregate": float(res.aggregate[0, best]),
        "best_params": machines.params_row(best),
        "pareto": [
            {"variant": machines.names[i],
             "area": float(res.area()[i]),
             "aggregate": float(res.aggregate[0, i])}
            for i in front],
    }


def codesign_grad(profile, steps: int, lr: float = 0.1,
                  area_budget: float = None, power_budget: float = None,
                  constraint_mode: str = "projected",
                  opt_links: bool = False, area_envelope: dict = None,
                  sensitivities: bool = False) -> dict:
    """Gradient co-design: descend the scalarized (congruence, area, power)
    objective from the named-variant seeds by jax.grad through the shared
    kernels (``repro.core.codesign``); the optimized continuous designs
    answer "where should the machine move?" rather than "which sampled
    point wins?".  With a budget (scalar area/power and/or a
    per-subsystem envelope) the descent is constrained
    (``repro.core.constrained``): projected-gradient or augmented-
    Lagrangian, optionally relaxing ici_links with rounding-and-repair."""
    from repro.core.codesign import grad_codesign
    from repro.core.constrained import constrained_codesign
    from repro.core.sweep import MachineBatch

    seeds = MachineBatch.from_models(M.VARIANTS)
    if area_budget is None and power_budget is None and not area_envelope:
        res = grad_codesign([profile], seeds, steps=steps, lr=lr)
    else:
        res = constrained_codesign(
            [profile], seeds, steps=steps, lr=lr, area_budget=area_budget,
            power_budget=power_budget, area_envelope=area_envelope,
            mode=constraint_mode, optimize_links=opt_links)
    out = res.to_json()
    if sensitivities and (area_budget is not None
                          or power_budget is not None or area_envelope):
        # KKT shadow prices at the optimum (repro.core.implicit): which
        # budget is worth relaxing, and by how much per unit of budget.
        from repro.core.implicit import sensitivities_of
        rep = sensitivities_of(res, [profile])
        out["sensitivities"] = rep.to_json()
    return out


def codesign_bilevel(profile, total_budget: float, steps: int,
                     lr: float = 0.1, area_envelope: dict = None):
    """Bilevel budget descent (``repro.core.implicit``): outer descent on
    the area/power split of one total silicon budget, differentiated
    through the inner constrained optimum by the implicit custom-VJP."""
    from repro.core.implicit import bilevel_codesign
    from repro.core.sweep import MachineBatch

    return bilevel_codesign(
        [profile], MachineBatch.from_models(M.VARIANTS),
        total_budget=total_budget, steps=steps, lr=lr,
        area_envelope=area_envelope)


def codesign_frontier(profile, budgets, steps: int, lr: float = 0.1,
                      power_budget: float = None,
                      area_envelope: dict = None):
    """Feasibility frontier J*(budget) from the named-variant seeds
    (``repro.core.frontier``): one warm-started continuation over the
    budget schedule instead of one cold constrained run per budget."""
    from repro.core.frontier import frontier_codesign
    from repro.core.sweep import MachineBatch

    return frontier_codesign(
        [profile], MachineBatch.from_models(M.VARIANTS), budgets,
        steps=steps, lr=lr, power_budget=power_budget,
        area_envelope=area_envelope)


def codesign_joint(profile_group, steps: int, lr: float = 0.1,
                   area_budget: float = None,
                   power_budget: float = None) -> dict:
    """Joint (machine, sharding-variant) co-design over one app's group of
    sharding-variant profiles (``repro.core.constrained.joint_codesign``,
    alternation mode), optionally under the same budgets."""
    from repro.core.constrained import joint_codesign
    from repro.core.sweep import MachineBatch

    res = joint_codesign([profile_group],
                         MachineBatch.from_models(M.VARIANTS),
                         steps=steps, lr=lr, area_budget=area_budget,
                         power_budget=power_budget)
    return res.to_json()


def codesign_pack(profile, num_machines: int, gen: int = 31,
                  lr: float = None, area_budget: float = None,
                  power_budget: float = None, area_envelope: dict = None):
    """Multi-tenant packing: place the optimized profile plus ``gen``
    generated co-tenant stress workloads across ``num_machines`` machine
    instances (``repro.core.packing.pack_codesign``).  Scalar budgets
    read as fleet TOTALS here, not per-machine caps -- the question is
    "how should a shared fleet split its silicon across tenants?"."""
    from repro.core.model_zoo import resolve_suite
    from repro.core.packing import pack_codesign
    from repro.core.sweep import MachineBatch

    apps = [profile] + (resolve_suite(f"gen:{gen}") if gen > 0 else [])
    return pack_codesign(apps, MachineBatch.from_models(M.VARIANTS),
                         num_machines=num_machines, lr=lr,
                         area_budget=area_budget, power_budget=power_budget,
                         area_envelope=area_envelope)


def attention_layers(cfg) -> int:
    if cfg.family == Family.HYBRID:
        from repro.models.transformer import hybrid_layout
        n_groups, _ = hybrid_layout(cfg)
        return n_groups
    if cfg.family == Family.AUDIO:
        return cfg.n_layers * 2 + cfg.n_encoder_layers  # self+cross / enc
    if cfg.family == Family.SSM:
        return 0
    return cfg.n_layers


def parse_budget_sweep(parser, spec):
    """``LO:HI:N`` -> N evenly spaced area budgets, validated at parse
    time (like ``--backend``) so a bogus schedule fails before any
    compile work."""
    if spec is None:
        return None
    parts = spec.split(":")
    if len(parts) != 3:
        parser.error(f"--budget-sweep expects LO:HI:N, got {spec!r}")
    try:
        lo, hi, n = float(parts[0]), float(parts[1]), int(parts[2])
    except ValueError:
        parser.error(f"--budget-sweep expects numeric LO:HI:N, got {spec!r}")
    if not 0.0 < lo < hi:
        parser.error(f"--budget-sweep needs 0 < LO < HI, got {spec!r}")
    if n < 2:
        parser.error(f"--budget-sweep needs N >= 2 budgets, got {n}")
    return [float(b) for b in np.linspace(lo, hi, n)]


def parse_area_envelope(parser, spec):
    """``K=V[,K=V...]`` -> validated envelope dict (keys checked against
    the cost model's rate fields at parse time)."""
    if spec is None:
        return None
    from repro.core.constrained import validate_area_envelope

    env = {}
    for item in spec.split(","):
        key, sep, value = item.partition("=")
        if not sep:
            parser.error(f"--area-envelope expects K=V[,K=V...], "
                         f"got {item!r}")
        try:
            env[key.strip()] = float(value)
        except ValueError:
            parser.error(f"--area-envelope value for {key.strip()!r} must "
                         f"be a number, got {value!r}")
    try:
        return validate_area_envelope(env)
    except ValueError as exc:
        parser.error(str(exc))


def validate_codesign_args(parser, args) -> None:
    """Reject inconsistent co-design flags at parse time (like --backend):
    budgets must be positive, and every constrained/joint flag needs the
    --grad mode it modifies -- not an error minutes into compile work."""
    for name, value in (("--area-budget", args.area_budget),
                        ("--power-budget", args.power_budget)):
        if value is not None and not value > 0.0:
            parser.error(f"{name} must be positive, got {value}")
    budget_sweep = getattr(args, "budget_sweep", None)
    envelope = getattr(args, "area_envelope", None)
    pack = getattr(args, "pack", 0) or 0
    if pack < 0 or getattr(args, "pack_gen", 0) < 0:
        parser.error("--pack/--pack-gen must be non-negative")
    has_budget = (args.area_budget is not None
                  or args.power_budget is not None or envelope is not None)
    if (args.joint or args.opt_links
            or args.constraint_mode or budget_sweep is not None) \
            and not args.grad:
        parser.error("--constraint-mode/--opt-links/--joint/--budget-sweep "
                     "require --grad STEPS")
    if has_budget and not args.grad and not pack:
        parser.error("--area-budget/--power-budget/--area-envelope "
                     "require --grad STEPS or --pack M")
    if pack and (args.grad or args.joint or budget_sweep is not None
                 or args.opt_links or args.constraint_mode):
        parser.error("--pack is its own co-design mode (fleet-total "
                     "budgets); drop --grad/--joint/--budget-sweep/"
                     "--opt-links/--constraint-mode")
    if (args.constraint_mode or args.opt_links) \
            and not has_budget and budget_sweep is None:
        parser.error("--constraint-mode/--opt-links require "
                     "--area-budget and/or --power-budget")
    if args.joint and (args.constraint_mode or args.opt_links):
        parser.error("--joint supports budgets only through the projected "
                     "retraction; drop --constraint-mode/--opt-links")
    if budget_sweep is not None:
        if args.area_budget is not None:
            parser.error("--budget-sweep IS the area-budget axis; "
                         "drop --area-budget")
        if args.joint or args.opt_links or args.constraint_mode:
            parser.error("--budget-sweep traces the frontier by projected "
                         "continuation; drop --joint/--opt-links/"
                         "--constraint-mode")
    if args.joint and envelope is not None:
        parser.error("--joint does not support --area-envelope; use scalar "
                     "--area-budget/--power-budget")
    bilevel = getattr(args, "bilevel", None)
    if bilevel is not None:
        if not bilevel > 0.0:
            parser.error(f"--bilevel must be positive, got {bilevel}")
        if not args.grad:
            parser.error("--bilevel requires --grad STEPS (inner solves)")
        if args.area_budget is not None or args.power_budget is not None:
            parser.error("--bilevel derives the area/power budgets from "
                         "the learned split; drop --area-budget/"
                         "--power-budget")
        if args.joint or args.opt_links or args.constraint_mode \
                or budget_sweep is not None or pack:
            parser.error("--bilevel is its own co-design mode; drop "
                         "--joint/--opt-links/--constraint-mode/"
                         "--budget-sweep/--pack")
    if getattr(args, "sensitivities", False):
        if not args.grad:
            parser.error("--sensitivities requires --grad STEPS")
        if args.joint:
            parser.error("--sensitivities does not support --joint "
                         "(per-variant selection has no single optimum "
                         "to differentiate through)")
        if not has_budget and budget_sweep is None and bilevel is None:
            parser.error("--sensitivities needs a constraint to price; "
                         "add --area-budget/--power-budget/"
                         "--area-envelope")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", choices=("pod", "multipod"), default="pod")
    ap.add_argument("--variant", default=None)
    ap.add_argument("--moe-impl", default=None)
    ap.add_argument("--out", default="benchmarks/artifacts_opt")
    ap.add_argument("--tag", default=None)
    ap.add_argument("--mode", choices=("flash", "scan"), default="flash")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config for --arch (fast "
                         "compiles; CI exercises the full pipeline)")
    ap.add_argument("--sp", choices=("on", "off"), default="on")
    ap.add_argument("--sweep", type=int, default=0, metavar="N",
                    help="after substitution, sweep N generated machine "
                         "variants and report the best fit + Pareto front")
    ap.add_argument("--sweep-seed", type=int, default=0)
    ap.add_argument("--backend", default=None,
                    help="kernel backend for the co-design sweep "
                         "(numpy/jax/pallas or any registered name; "
                         "default: $REPRO_SWEEP_BACKEND, then numpy)")
    ap.add_argument("--grad", type=int, default=0, metavar="STEPS",
                    help="after substitution, gradient co-design: optimize "
                         "machine log-rates from the named-variant seeds by "
                         "jax.grad of the scalarized (congruence, area, "
                         "power) objective for STEPS steps")
    ap.add_argument("--grad-lr", type=float, default=0.1,
                    help="initial log-rate step size for --grad")
    ap.add_argument("--area-budget", type=float, default=None, metavar="B",
                    help="constrain --grad descent to CostModel.area <= B "
                         "(repro.core.constrained)")
    ap.add_argument("--power-budget", type=float, default=None, metavar="P",
                    help="constrain --grad descent to CostModel.power <= P")
    ap.add_argument("--constraint-mode", default=None,
                    choices=("projected", "lagrangian"),
                    help="budgeted-descent algorithm (default: projected); "
                         "requires --area-budget/--power-budget")
    ap.add_argument("--opt-links", action="store_true",
                    help="relax ici_links continuously during --grad and "
                         "round with repair (requires a budget)")
    ap.add_argument("--joint", action="store_true",
                    help="joint (machine, sharding-variant) descent: "
                         "compile every sharding variant and let --grad "
                         "choose per machine variant")
    ap.add_argument("--budget-sweep", default=None, metavar="LO:HI:N",
                    help="trace the feasibility frontier J*(budget) over N "
                         "area budgets from LO to HI (warm-started "
                         "continuation; requires --grad, replaces "
                         "--area-budget)")
    ap.add_argument("--area-envelope", default=None, metavar="K=V[,K=V...]",
                    help="per-subsystem area envelopes for --grad / "
                         "--budget-sweep, e.g. peak_flops=1.5,hbm_bw=0.8 "
                         "(keys from repro.core.costmodel.RATE_FIELDS)")
    ap.add_argument("--sensitivities", action="store_true",
                    help="after a budgeted --grad run, report KKT shadow "
                         "prices and dJ*/d(budget) at the optimum "
                         "(repro.core.implicit); with --budget-sweep the "
                         "frontier rows carry them automatically")
    ap.add_argument("--bilevel", type=float, default=None, metavar="T",
                    help="bilevel budget descent: split one total silicon "
                         "budget T between area and power by outer "
                         "descent through the inner constrained optimum "
                         "(implicit custom-VJP gradient; requires --grad "
                         "STEPS for the inner solves)")
    ap.add_argument("--pack", type=int, default=0, metavar="M",
                    help="multi-tenant packing: place the optimized "
                         "profile plus --pack-gen generated co-tenants "
                         "across M machine instances "
                         "(repro.core.packing); --area-budget/"
                         "--power-budget read as fleet TOTALS")
    ap.add_argument("--pack-gen", type=int, default=31, metavar="N",
                    help="generated co-tenant workloads for --pack "
                         "(AppSpace.default Halton suite gen:N; 0 packs "
                         "the substituted profile alone)")
    args = ap.parse_args(argv)
    # Fail at parse time with the registry's current contents, not deep
    # inside get_backend() after minutes of compile work.
    from repro.core.kernels_xp import validate_backend_arg
    validate_backend_arg(ap, args.backend)
    budgets = parse_budget_sweep(ap, args.budget_sweep)
    envelope = parse_area_envelope(ap, args.area_envelope)
    validate_codesign_args(ap, args)

    cfg = C.get_config(args.arch, smoke=args.smoke)
    if args.moe_impl and cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, impl=args.moe_impl))
    shape = resolve_shape(args.shape)  # assigned SHAPES or a zoo-grid shape
    multi_pod = args.mesh == "multipod"
    xla_flags.ensure_host_device_count(512 if multi_pod else 256)
    mesh = MESH.make_production_mesh(multi_pod=multi_pod)
    mesh_label = "pods2x16x16" if multi_pod else "pod16x16"
    variant = args.variant or default_variant(cfg)
    sc = SH.ShardingConfig(variant=variant, multi_pod=multi_pod)
    tag = args.tag or args.mode

    if args.mode == "flash" and attention_layers(cfg) == 0:
        print("arch is attention-free; flash substitution not applicable")
        return 1
    if args.mode == "scan" and cfg.ssm is None:
        print("arch has no SSM; scan substitution not applicable")
        return 1

    # 1. baseline cell (compile + calibrate) -- the pre-substitution profile
    profile = run_cell(cfg, shape, mesh, mesh_label, variant, None,
                       multi_pod=multi_pod, verbose=False)
    before = R.analyze(profile, M.TPU_V5E)
    print("before:", before.one_liner())

    # 2. measured traffic isolation + kernel substitution
    t0 = time.time()
    if args.mode == "flash":
        quad2 = quadratic_attention_bytes(cfg, shape, mesh, sc)
        L_att = attention_layers(cfg)
        per_layer = quad2 / 2.0
        removed = per_layer * L_att
        added = flash_kernel_bytes_per_layer(cfg, shape, mesh.size) * L_att
        n_layers = L_att
    else:
        per2 = scan_state_bytes(cfg, shape, mesh, sc)
        per_layer = per2 / 2.0
        removed = per_layer * cfg.n_layers
        added = scan_kernel_bytes_per_layer(
            cfg, shape, mesh.size) * cfg.n_layers
        n_layers = cfg.n_layers
    new_hbm = max(profile.hbm_bytes - removed + added, added)
    print(f"measured fit: {time.time()-t0:.1f}s  kernel-replaced "
          f"traffic/layer {per_layer/1e9:.2f} GB -> kernel "
          f"{added/max(n_layers,1)/1e9:.3f} GB")

    profile.hbm_bytes = new_hbm
    profile.meta[f"{args.mode}_substitution"] = {
        "removed_bytes": removed, "added_bytes": added, "layers": n_layers,
    }
    profile.name += f"+{args.mode}"
    after = R.analyze(profile, M.TPU_V5E)
    print("after: ", after.one_liner())

    if args.sweep > 0:
        # Co-design: which machine design fits the OPTIMIZED workload best?
        cd = codesign_sweep(profile, args.sweep, seed=args.sweep_seed,
                            backend=args.backend)
        profile.meta["codesign_sweep"] = cd
        print(f"codesign sweep over {cd['num_variants']} variants "
              f"({cd['backend']} backend): best={cd['best_variant']} "
              f"aggregate={cd['best_aggregate']:.4f} "
              f"pareto={len(cd['pareto'])} points")

    if args.grad > 0:
        if args.bilevel is not None:
            # Bilevel co-design: how should one silicon budget be SPLIT
            # between area and power?  Outer descent through the inner
            # optimum via the implicit-function-theorem gradient.
            bl = codesign_bilevel(profile, args.bilevel, args.grad,
                                  lr=args.grad_lr, area_envelope=envelope)
            profile.meta["bilevel_codesign"] = bl.to_json()
            print(f"bilevel codesign (total={args.bilevel:.4g}, "
                  f"{bl.outer_steps} outer steps): split "
                  f"{bl.split_trajectory[0]:.3f} -> {bl.split_final:.3f}, "
                  f"J* {bl.objective_trajectory[0]:.4f} -> "
                  f"{bl.objective_final:.4f} "
                  f"(+{bl.improvement_over_uniform:.4f} vs uniform split)")
        elif args.joint:
            # Joint co-design: which (machine, sharding) pair wins?  The
            # primary cell keeps its kernel substitution; the remaining
            # sharding variants enter as baseline compiles.
            group = [profile]
            for sv in SH.SHARDING_VARIANTS:
                if sv == variant:
                    continue
                alt = run_cell(cfg, shape, mesh, mesh_label, sv, None,
                               multi_pod=multi_pod, verbose=False)
                alt.name += f"@{sv}"
                group.append(alt)
            gd = codesign_joint(group, args.grad, lr=args.grad_lr,
                                area_budget=args.area_budget,
                                power_budget=args.power_budget)
            profile.meta["joint_codesign"] = gd
            print(f"joint codesign over {len(group)} shardings: "
                  f"best={gd['best_variant']} picks="
                  f"{gd['selection'][gd['best_variant']]}")
        elif budgets is not None:
            # Feasibility frontier: how much fabric does this workload
            # actually need?  One continuation over the budget schedule.
            fr = codesign_frontier(profile, budgets, args.grad,
                                   lr=args.grad_lr,
                                   power_budget=args.power_budget,
                                   area_envelope=envelope)
            profile.meta["frontier_codesign"] = fr.to_json()
            n_feas = int(fr.feasible.sum())
            knee = f"{fr.knee():.4g}" if n_feas else "n/a"
            print(f"frontier over {len(fr)} budgets "
                  f"[{fr.budgets[0]:.4g}, {fr.budgets[-1]:.4g}]: "
                  f"J* {fr.objective[-1]:.4f} (loosest) .. "
                  f"{fr.objective[0]:.4f} (tightest), "
                  f"feasible {n_feas}/{len(fr)}, knee={knee}")
            if args.sensitivities and fr.shadow_prices is not None:
                pts = ", ".join(
                    f"{b:.4g}->{p:.4f}"
                    for b, p in zip(fr.budgets, fr.shadow_prices[:, 0])
                    if np.isfinite(p))
                print(f"area shadow prices (budget -> -dJ*/db): {pts}")
        else:
            # Continuous co-design: in which direction should the machine
            # move (optionally under an area/power budget)?
            gd = codesign_grad(
                profile, args.grad, lr=args.grad_lr,
                area_budget=args.area_budget,
                power_budget=args.power_budget,
                constraint_mode=args.constraint_mode or "projected",
                opt_links=args.opt_links, area_envelope=envelope,
                sensitivities=args.sensitivities)
            profile.meta["grad_codesign"] = gd
            lines = ", ".join(
                f"{v['name']}: {v['objective_seed']:.4f}->"
                f"{v['objective_final']:.4f}" for v in gd["variants"])
            print(f"grad codesign ({gd['steps']} steps, {gd['mode']}): "
                  f"{lines}; best={gd['best_variant']}")
            if "feasibility" in gd:
                feas = gd["feasibility"]
                print(f"feasibility ({feas['mode']}): "
                      f"area_budget={feas['area_budget']} "
                      f"power_budget={feas['power_budget']} "
                      f"all_feasible={feas['all_feasible']}")
            if "sensitivities" in gd:
                sens = gd["sensitivities"]
                lines = "; ".join(
                    f"{v['name']}: " + ", ".join(
                        f"{c}={v['shadow_prices'][c]:.4f}"
                        for c in sens["constraints"])
                    + (f" (relax {v['best_relaxation']} first)"
                       if v["best_relaxation"] else "")
                    for v in sens["variants"])
                print(f"shadow prices (dJ*/d(budget), sign flipped): "
                      f"{lines}")

    if args.pack > 0:
        # Multi-tenant packing: how should a shared fleet split its
        # silicon across this workload and a generated stress population?
        pk = codesign_pack(profile, args.pack, gen=args.pack_gen,
                           lr=args.grad_lr, area_budget=args.area_budget,
                           power_budget=args.power_budget,
                           area_envelope=envelope)
        profile.meta["pack_codesign"] = pk.to_json(top_k=8)
        feas = ("" if pk.feasible is None
                else f", feasible={bool(pk.feasible)}")
        print(f"pack codesign: {len(pk.app_names)} apps across "
              f"{len(pk.machine_names)} machines ({pk.mode}): objective "
              f"{pk.objective_seed:.4f} -> {pk.objective_final:.4f}, "
              f"fleet area {pk.area_total:.3f}{feas}")

    if args.out:
        os.makedirs(args.out, exist_ok=True)
        fname = (f"{cfg.name}__{shape.name}__{mesh_label}__{variant}"
                 f"__{tag}.json")
        profile.save(os.path.join(args.out, fname))
    return 0


if __name__ == "__main__":
    sys.exit(main())
