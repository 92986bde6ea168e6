"""Compile-and-extract core of the dry-run pipeline (no env side effects).

``launch/dryrun.py`` owns the CLI and the ``XLA_FLAGS`` request for 512
fake host devices; this module owns the actual work -- lower + compile one
(architecture x shape x mesh) cell and extract its ``WorkloadProfile`` --
so in-process callers (``core/model_zoo.py``, tests) can reuse the exact
production extraction path without mutating the process environment at
import time.
"""

import dataclasses
import os
import time

import jax

from repro.core import costs as CO
from repro.core import machine as M
from repro.core import roofline as R
from repro.distributed import ctx as CTX
from repro.distributed import sharding as SH
from repro.launch import mesh as MESH
from repro.launch.specs import input_specs
from repro.models.config import Family


def default_variant(cfg) -> str:
    """Big archs need FSDP-style sharding to fit 16 GB/chip (DESIGN.md §6)."""
    total, _ = cfg.param_counts()
    return "fsdp" if total > 20e9 else "zero1"


# --------------------------------------------------------------------------- #
# Cost calibration (depth-extrapolated unrolled probes)
#
# XLA's cost_analysis counts a while-loop body ONCE, so a scan-over-layers
# model under-reports FLOPs/bytes/collectives by ~n_layers.  Per-layer costs
# are exactly linear in depth for homogeneous stacks, so we compile two (or
# three, for a hybrid with a tail) UNROLLED probes at reduced depth and
# full width/batch/mesh, and extrapolate:  total(L) = c(a) + (L-a)*body where
# body = (c(b)-c(a))/(b-a); a hybrid stack probes one and two periods of its
# pattern (plus the tail).  The full-depth scanned artifact is still what we
# ship (memory_analysis comes from it); only the cost terms are calibrated.
# Sequential SSM/LRU elementwise scans stay loops even in probes; their FLOPs
# are added analytically (DESIGN.md §2 note; < ~5% of model FLOPs).
#
# A routed expert's grouped matmul (``lax.ragged_dot``, which the CPU lowers
# to one dot over every expert held here) is counted for every routed row:
# n_experts times the rows those experts receive under balanced routing.
# Its FLOPs are linear in the expert width, so one more probe at half the
# width measures them, and the calibration keeps 1/n_experts of them.
# --------------------------------------------------------------------------- #


def _cost_dict(compiled, devices_per_pod) -> dict:
    cost_list = compiled.cost_analysis()
    cost = cost_list[0] if isinstance(cost_list, (list, tuple)) else (cost_list or {})
    stats = CO.parse_hlo_stats(compiled.as_text(),
                               devices_per_pod=devices_per_pod)
    return {
        "flops": float(cost.get("flops", 0.0) or 0.0),
        "bytes": float(cost.get("bytes accessed", 0.0) or 0.0),
        "hbm": stats.hbm_bytes,
        "transc": float(cost.get("transcendentals", 0.0) or 0.0),
        "dot_flops": stats.dot_flops,
        "coll": dict(stats.collective_bytes),
        "pod_coll": stats.pod_collective_bytes,
    }


def _lincomb(*terms):
    """terms: (scale, cost_dict) pairs -> elementwise linear combination."""

    def comb(key):
        if key == "coll":
            kinds = set()
            for _, d in terms:
                kinds.update(d["coll"])
            return {k: sum(s * d["coll"].get(k, 0.0) for s, d in terms)
                    for k in kinds}
        return sum(s * d[key] for s, d in terms)

    return {k: comb(k) for k in ("flops", "bytes", "hbm", "transc",
                                 "dot_flops", "coll", "pod_coll")}


def _probe_cfg(cfg, depth):
    c = cfg.replace(n_layers=depth, scan_layers=False, logits_chunk=0,
                    attn_q_chunk=0)
    if cfg.family == Family.AUDIO:
        c = c.replace(n_encoder_layers=depth)
    if cfg.ssm is not None:
        c = c.replace(ssm=dataclasses.replace(cfg.ssm, scan_chunk=1 << 30))
    return c


def _analytic_scan_flops(cfg, shape, n_devices: int) -> float:
    """Per-device FLOPs of the sequential elementwise recurrences (the
    loops cost_analysis counts once): 8 per Mamba state element and 10 per
    RG-LRU channel, per token and layer, split evenly over the mesh."""
    mult = 3.0 if shape.kind == "train" else 1.0  # fwd(+bwd recompute)
    if shape.kind == "decode":
        tokens = shape.global_batch
    else:
        tokens = shape.global_batch * shape.seq_len
    if cfg.family == Family.SSM:
        mixers = ["mamba"] * cfg.n_layers
    elif cfg.family == Family.HYBRID:
        h = cfg.hybrid
        mixers = [h.mixer(h.pattern[i % len(h.pattern)])
                  for i in range(cfg.n_layers)]
    else:
        return 0.0
    per_token = {
        "mamba": (cfg.ssm.expand * cfg.d_model * cfg.ssm.state_dim * 8.0
                  if cfg.ssm is not None else 0.0),
        "rec": (cfg.hybrid.lru_width or cfg.d_model) * 10.0
        if cfg.hybrid is not None else 0.0,
    }
    return mult * tokens * sum(per_token.get(m, 0.0)
                               for m in mixers) / n_devices


def _probe_costs(cfg, shape, mesh, sc, depth, *, multi_pod=False,
                 rules_kind=None) -> dict:
    """Costs of ``cfg`` cut to ``depth`` blocks, compiled unrolled."""
    dpp = MESH.DEVICES_PER_POD if multi_pod else 0
    cell = input_specs(_probe_cfg(cfg, depth), shape, mesh, sc)
    with jax.set_mesh(mesh), CTX.use_rules(
            SH.activation_rules(mesh, sc, kind=rules_kind or shape.kind)):
        compiled = jax.jit(
            cell.step_fn, in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
            donate_argnums=cell.donate_argnums,
        ).lower(*cell.args).compile()
    return _cost_dict(compiled, dpp)


def _n_moe_blocks(cfg, depth: int) -> int:
    """MoE blocks among the first ``depth`` blocks of the stack."""
    if cfg.family == Family.HYBRID:
        p = cfg.hybrid.pattern
        return sum(cfg.hybrid.is_moe(p[i % len(p)]) for i in range(depth))
    return depth if cfg.family == Family.MOE else 0


def _routed_excess(cfg, shape, mesh, sc, depth, c_depth, **kw) -> float:
    """Dot FLOPs, per device at full depth, that the compiled program counts
    beyond the routed experts' expected work: the probe at ``depth`` (costs
    ``c_depth``) against the same probe at half the expert width gives half
    the routed dot FLOPs counted, of which all but 1/n_experts are excess.
    (The elementwise work on the routed rows is done for every row, and
    stays.)"""
    m = cfg.moe
    if m is None or m.impl != "gmm" or not _n_moe_blocks(cfg, depth):
        return 0.0
    half = cfg.replace(moe=dataclasses.replace(
        m, d_ff_expert=m.d_ff_expert // 2))
    c_half = _probe_costs(half, shape, mesh, sc, depth, **kw)
    return (2.0 * (1.0 - 1.0 / m.n_experts)
            * _n_moe_blocks(cfg, cfg.n_layers) / _n_moe_blocks(cfg, depth)
            * (c_depth["dot_flops"] - c_half["dot_flops"]))


def calibrate_costs(cfg, shape, mesh, mesh_label, sc, *, multi_pod,
                    verbose=True, rules_kind=None) -> dict:
    def probe(depth):
        return _probe_costs(cfg, shape, mesh, sc, depth, multi_pod=multi_pod,
                            rules_kind=rules_kind)

    t0 = time.time()
    if cfg.family == Family.HYBRID:
        from repro.models.transformer import hybrid_layout
        period = len(cfg.hybrid.pattern)
        n_groups, n_tail = hybrid_layout(cfg)
        # one period's body = c(2P) - c(P); the tail's = c(P + tail) - c(P)
        a = period
        ca = probe(a)
        terms = [(1.0, ca)]
        if n_groups > 1:
            c2 = probe(2 * period)
            terms += [(float(n_groups - 1), c2), (-float(n_groups - 1), ca)]
        if n_tail:
            terms += [(1.0, probe(period + n_tail)), (-1.0, ca)]
        total = _lincomb(*terms)
    else:
        a, b = 2, 4
        ca, cb = probe(a), probe(b)
        L = cfg.n_layers
        scale = (L - a) / (b - a)
        total = _lincomb((1.0, ca), (scale, cb), (-scale, ca))
    excess = _routed_excess(cfg, shape, mesh, sc, a, ca, multi_pod=multi_pod,
                            rules_kind=rules_kind)
    total["flops"] -= excess
    total["dot_flops"] -= excess
    total["routed_excess_flops"] = excess
    total["flops"] += _analytic_scan_flops(cfg, shape, mesh.size)
    total["probe_seconds"] = time.time() - t0
    if verbose:
        print(f"  probes done in {total['probe_seconds']:.1f}s "
              f"(calibrated flops/dev {total['flops']:.3e})")
    return total


def run_cell(cfg, shape, mesh, mesh_label, variant, out_dir, *,
             multi_pod: bool, verbose: bool = True, calibrate: bool = True,
             tag: str = "", sp: bool = True):
    sc = SH.ShardingConfig(variant=variant, multi_pod=multi_pod)
    t0 = time.time()
    rules_kind = shape.kind if sp else "decode"  # "decode" = no seq sharding
    cell = input_specs(cfg, shape, mesh, sc)
    with jax.set_mesh(mesh), CTX.use_rules(
            SH.activation_rules(mesh, sc, kind=rules_kind)):
        jitted = jax.jit(
            cell.step_fn,
            in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
            donate_argnums=cell.donate_argnums,
        )
        lowered = jitted.lower(*cell.args)
        compiled = lowered.compile()
    compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    cost_list = compiled.cost_analysis()
    cost = (cost_list[0] if isinstance(cost_list, (list, tuple))
            else (cost_list or {}))
    n_dev = mesh.size
    model_flops = R.model_flops_for(
        params_active=cell.meta["params_active"],
        tokens=cell.meta["tokens"],
        step_kind="train" if shape.kind == "train" else "infer",
    )
    profile = CO.profile_from_compiled(
        f"{cfg.name}/{shape.name}@{mesh_label}",
        compiled,
        arch=cfg.name,
        shape=shape.name,
        mesh=mesh_label,
        step_kind=shape.kind,
        num_devices=n_dev,
        model_flops=model_flops,
        tokens=cell.meta["tokens"],
        params=cell.meta["params"],
        params_active=cell.meta["params_active"],
        compile_seconds=compile_s,
        devices_per_pod=MESH.DEVICES_PER_POD if multi_pod else 0,
        meta={"variant": variant},
    )

    if calibrate:
        raw = {"flops": profile.flops, "bytes": profile.bytes_accessed,
               "coll": dict(profile.collective_bytes)}
        cal = calibrate_costs(cfg, shape, mesh, mesh_label, sc,
                              multi_pod=multi_pod, verbose=verbose,
                              rules_kind=rules_kind)
        profile.flops = cal["flops"]
        profile.bytes_accessed = cal["bytes"]
        profile.hbm_bytes = cal["hbm"]
        profile.transcendentals = cal["transc"]
        profile.dot_flops = cal["dot_flops"]
        profile.collective_bytes = dict(cal["coll"])
        profile.pod_collective_bytes = cal["pod_coll"]
        profile.meta["raw_uncalibrated"] = raw
        profile.meta["probe_seconds"] = cal["probe_seconds"]
        if cal["routed_excess_flops"]:
            profile.meta["routed_excess_flops"] = cal["routed_excess_flops"]
    if verbose:
        print(f"  memory_analysis: {mem}")
        print("  cost_analysis:", {k: v for k, v in (cost or {}).items()
                                   if k in ("flops", "bytes accessed",
                                            "transcendentals")})
        rep = R.analyze(profile, M.TPU_V5E)
        print("  " + rep.one_liner())
        print(f"  collectives/dev: "
              f"{ {k: f'{v/1e9:.3f}GB' for k, v in profile.collective_bytes.items() if v} }"
              f" pod-crossing: {profile.pod_collective_bytes/1e9:.3f}GB")
        print(f"  peak mem/dev: {profile.peak_memory_bytes/1e9:.2f} GB"
              f"  compile: {compile_s:.1f}s")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = (f"{cfg.name}__{shape.name}__{mesh_label}__{variant}"
                 f"{('__' + tag) if tag else ''}.json")
        profile.save(os.path.join(out_dir, fname))
    return profile
