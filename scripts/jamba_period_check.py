#!/usr/bin/env python3
"""One period of AI21-Jamba2-Mini at its published widths on one four-chip
host, against the float32 reference in ``tests/jamba_ref.py``.

    python scripts/jamba_period_check.py --out benchmarks/out/jamba_period
    python scripts/jamba_period_check.py --aot     # compile for a described
                                                   # v5e:2x2, no chip needed
    python scripts/jamba_period_check.py --profile # Eq. 1 of the same steps
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python scripts/jamba_period_check.py --tiny   # rehearsal on the CPU

The program is ``get_config("jamba2-mini")`` cut to one period (8 blocks:
7 Mamba, 1 attention, 4 MoE, 4 dense FFNs) at the published widths, with
all 16 experts and the whole vocabulary, sharded by the repository's rules
(variant ``tp``) over a 1x4 ``(data, model)`` mesh: experts, heads, Mamba
channels and vocabulary on ``model``.  Its weights are random from the
seed, stored in bfloat16 (the period's 13.3 B parameters are 53 GB in
float32), with every norm scale, bias and skip weight moved off its
initial value.  It prefills 4 sequences of 4,096 tokens
(``transformer.prefill``) and decodes 64 more through the cache
(``transformer.decode_step``).  The reference computes each sequence's
4,160 positions at once, one block at a time, and the logits at positions
4,095 to 4,159 are compared.

The comparison that decides the check runs the program with float32
compute at matmul precision "highest", as the reference runs (see
``TOLERANCES``).  Controls run the same comparison one fidelity lower: the
router renormalised (``MoEConfig.renormalize``) and the Mamba scan in
bfloat16 (``jamba_ref.bf16_ssm_scan``); each tolerance must fail them.

Then the configuration as stated (bfloat16 compute): its logits against
the reference (held to ``BF16_LIMITS``), the prefill and decode step times
(host clock around work that ends in ``block_until_ready``), and from a
profiler trace of one prefill and of eight decode steps the share of
device time under each block kind's named scope (``mamba``, ``attention``,
``moe``, ``mlp``).  The readings go to ``<out>.json``; the last line of
standard output is a JSON summary.  Exit status 1 when a tolerance or a
limit fails.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "tests")]

import numpy as np  # noqa: E402

import jamba_ref  # noqa: E402

#: Relative error of one position's logits: ||program - reference||_2 over
#: ||reference||_2 across the vocabulary.  The comparison runs the program
#: with float32 compute at matmul precision "highest", as the reference
#: runs: at the configuration's bfloat16 compute the matmuls' rounding
#: (2.8% on the CPU at d_model 256 and 1,024 tokens) would hide a
#: bfloat16 scan (3.1% there), while in float32 the program reads 1.3e-6
#: and the bfloat16 scan 1.2e-2.  The limits lie between the program's chip
#: readings and the controls' (PERF.md gives both).
TOLERANCES = {
    # the prefill's last position and the median decode position: float32
    # rounding through 8 blocks, with room for the chip's own exp, softplus
    # and rsqrt; a bfloat16 scan or a renormalised router reads 1e-2 and up
    "prefill_rel_err": 1e-3,
    "decode_rel_err_median": 1e-3,
    # the worst of the 4 x 64 decode positions: room for a token whose
    # top-2 experts swap on a float32 near-tie of router probabilities
    "decode_rel_err_max": 5e-3,
}
KEYS = tuple(TOLERANCES)
#: The configuration as stated (bfloat16 compute) against the same
#: reference.  bfloat16 rounding of every matmul through the 8 blocks reads
#: 2.2e-2 at the prefill's last position and at the median decode position
#: (TPU v5e, seed 0); a cache or decode step at fault moves every
#: decode position, and a position whose logits share nothing with the
#: reference's reads about 1.  The worst decode position is a reading,
#: reported with the reference's router margin there (``router_margins``).
BF16_LIMITS = {"prefill_rel_err": 5e-2, "decode_rel_err_median": 5e-2}
CONTROLS = ("renormalised router", "bfloat16 scan")
SCOPES = ("mamba", "attention", "moe", "mlp")


def period_config():
    """The configuration run on the chip: one period at the published
    widths, its weights stored in bfloat16 (the period's 13.3 B parameters
    are 53 GB in float32, more than a four-chip host's 64 GB leaves room
    for); compute stays in bfloat16 as the configuration states."""
    from repro.configs import get_config

    cfg = get_config("jamba2-mini")
    return cfg.replace(n_layers=len(cfg.hybrid.pattern),
                       param_dtype="bfloat16")


def jax_highest():
    import jax

    return jax.default_matmul_precision("highest")


def build(cfg, mesh):
    """Shardings, and the jitted init / prefill / decode / reference
    functions of ``cfg`` on ``mesh``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed import ctx as CTX
    from repro.distributed import sharding as SH
    from repro.models import transformer as T

    sc = SH.ShardingConfig(variant="tp")
    key = jax.random.PRNGKey(0)
    shapes, axes = T.init_model(key, cfg, abstract=True)
    p_shard = SH.param_specs(shapes, axes, mesh, sc)
    repl = NamedSharding(mesh, P())

    def init(k):
        params, _ = T.init_model(k, cfg)
        leaves, tree = jax.tree.flatten(params)
        ax = jax.tree.leaves(axes, is_leaf=SH._is_axes_leaf)
        ks = jax.random.split(jax.random.fold_in(k, 1), len(leaves))
        # one number per channel (norm scales, biases, D): moved off init
        leaves = [t + 0.1 * jax.random.normal(kk, t.shape, t.dtype)
                  if sum(a != "layers" for a in a_) == 1 else t
                  for t, a_, kk in zip(leaves, ax, ks)]
        return jax.tree.unflatten(tree, leaves)

    def rules(kind):
        return CTX.use_rules(SH.activation_rules(mesh, sc, kind=kind))

    def prefill(params, cache, tokens):
        with rules("prefill"):
            return T.prefill(params, cfg, {"tokens": tokens,
                                           "labels": tokens}, cache)

    def decode(params, cache, tokens, index):
        with rules("decode"):
            return T.decode_step(params, cfg, cache, tokens, index)

    def ref_block(kind, n, fn=jamba_ref.block):
        def f(run, x, j):
            lw = jamba_ref.program_block(run, kind, n, 0, j)
            return fn(lw, kind, x, cfg.moe.top_k)
        return jax.jit(f, out_shardings=repl, static_argnums=2)

    fns = {
        "init": jax.jit(init, out_shardings=p_shard),
        "prefill": jax.jit(prefill),
        "decode": jax.jit(decode),
        "embed": jax.jit(lambda p, t: jamba_ref.embed(
            jamba_ref.program_outer(p), t), out_shardings=repl),
        "head": jax.jit(lambda p, x: jamba_ref.head(
            jamba_ref.program_outer(p), x), out_shardings=repl),
        "ref_block": {(kind, n): ref_block(kind, n) for _, kind, n in
                      jamba_ref.program_runs(cfg.hybrid.pattern)},
        "ref_margin": {(kind, n): ref_block(kind, n, jamba_ref.router_margin)
                       for _, kind, n in
                       jamba_ref.program_runs(cfg.hybrid.pattern)
                       if kind.endswith("+moe")},
    }
    return fns, shapes, p_shard, sc


def cache_for(cfg, mesh, sc, batch, max_len, abstract=False):
    from repro.distributed import sharding as SH
    from repro.models import transformer as T

    cache, axes = T.init_cache(cfg, batch, max_len, abstract=True)
    shard = SH.param_specs(cache, axes, mesh, sc, fsdp=False)
    return cache, shard


def aot(cfg, batch, seq, steps, reference=True):
    """Compile every program for a described v5e:2x2 and print what the
    chip's compiler says of its memory."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    fns, shapes, p_shard, sc = build(cfg, mesh)
    sds = lambda t, s: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=s)
    params = jax.tree.map(sds, shapes, p_shard)
    cache, c_shard = cache_for(cfg, mesh, sc, batch, seq + steps)
    cache = jax.tree.map(sds, cache, c_shard)
    from jax.sharding import NamedSharding, PartitionSpec as P
    tok_s = NamedSharding(mesh, P())
    toks = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=tok_s)
    one = jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=tok_s)
    idx = jax.ShapeDtypeStruct((), jnp.int32, sharding=tok_s)
    x = jax.ShapeDtypeStruct((seq + steps, cfg.d_model), jnp.float32,
                             sharding=tok_s)
    out = {}
    with jax.set_mesh(mesh):
        progs = {
            "init": (fns["init"], (jax.ShapeDtypeStruct(
                (2,), jnp.uint32, sharding=tok_s),)),
            "prefill": (fns["prefill"], (params, cache, toks)),
            "decode": (fns["decode"], (params, cache, one, idx)),
        }
        runs = {(kind, n): key for key, kind, n in
                jamba_ref.program_runs(cfg.hybrid.pattern)}
        for prog in ("ref_block", "ref_margin") if reference else ():
            for (kind, n), f in fns[prog].items():
                progs[f"{prog}[{kind} x{n}]"] = (
                    f, (params["groups"][runs[kind, n]], x, 0))
        for name, (f, args) in progs.items():
            t0 = time.time()
            m = f.lower(*args).compile().memory_analysis()
            gb = lambda b: round(b / 1e9, 3)
            out[name] = {
                "compile_s": round(time.time() - t0, 1),
                "arguments_gb": gb(m.argument_size_in_bytes),
                "outputs_gb": gb(m.output_size_in_bytes),
                "temporaries_gb": gb(m.temp_size_in_bytes),
                "aliased_gb": gb(m.alias_size_in_bytes)}
            print(name, out[name], flush=True)
    return out


def rel_err(got, want):
    """(positions,) relative L2 error over the vocabulary."""
    d = np.linalg.norm((got - want).astype(np.float64), axis=-1)
    return d / np.linalg.norm(want.astype(np.float64), axis=-1)


def run_program(fns, params, cache0, tokens, seq, steps):
    """Prefill then decode; logits (B, steps + 1, V) at positions
    seq - 1 ... seq + steps - 1."""
    import jax.numpy as jnp

    cache, lg = fns["prefill"](params, cache0, tokens[:, :seq])
    out = [np.asarray(lg[:, 0], np.float32)]
    for i in range(steps):
        cache, lg = fns["decode"](params, cache, tokens[:, seq + i:seq + i + 1],
                                  jnp.int32(seq + i))
        out.append(np.asarray(lg[:, 0], np.float32))
    return np.stack(out, axis=1)


def readings(got, want):
    e = rel_err(got, want)            # (B, steps + 1)
    worst = np.unravel_index(np.argmax(e[:, 1:]), e[:, 1:].shape)
    return {"prefill_rel_err": float(e[:, 0].max()),
            "decode_rel_err_median": float(np.median(e[:, 1:])),
            "decode_rel_err_max": float(e[:, 1:].max()),
            "decode_worst": [int(worst[0]), int(worst[1]) + 1]}


def reference(fns, params, cfg, tokens, seq):
    """Reference logits at positions seq-1 ... of each sequence, and at each
    the smallest router margin over the period's MoE blocks."""
    import jax.numpy as jnp

    out, margins = [], []
    for t in np.asarray(tokens):
        x = fns["embed"](params, jnp.asarray(t))
        m = np.inf
        for key, kind, n in jamba_ref.program_runs(cfg.hybrid.pattern):
            for j in range(n):
                run = params["groups"][key]
                if kind.endswith("+moe"):
                    m = np.minimum(m, np.asarray(
                        fns["ref_margin"][kind, n](run, x, j))[seq - 1:])
                x = fns["ref_block"][kind, n](run, x, j)
        out.append(np.asarray(fns["head"](params, x[seq - 1:]), np.float32))
        margins.append(m)
    return np.stack(out), np.stack(margins)


def router_margins(margins, worst):
    """The reference's router margin at a worst decode position (b, step),
    beside the median over decode positions and the share of them with a
    smaller margin: how near a tie of experts that position's routing is."""
    m = margins[worst[0], worst[1]]
    return {"at_worst": float(m),
            "median": float(np.median(margins[:, 1:])),
            "share_below": float(np.mean(margins[:, 1:] < m))}


def timed(f, n):
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        f()
        ts.append(time.perf_counter() - t0)
    return ts


_INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"', re.M)


def op_scopes(hlo_text):
    """Instruction name -> the block kind's named scope in its op_name.
    XLA rewrites ``lax.ragged_dot`` into ops named ``ragged-dot-*`` that
    lose the scope; the MoE's expert matmuls are the only ragged dots, so
    they count under ``moe``."""
    out = {}
    for name, op in _INSTR.findall(hlo_text):
        scope = "moe" if op.startswith("ragged-dot") else next(
            (s for s in SCOPES if f"/{s}/" in op), None)
        if scope:
            out[name] = scope
    return out


def scope_shares(trace_file, hlo_text):
    """Share of the device's synchronous op time under each block kind's
    named scope: each op event on a TPU plane's "XLA Ops" line is placed by
    the op_name of its instruction in the compiled program's HLO.  Async
    ``*-start`` / ``*-done`` events are left out: they run beside the
    compute they overlap."""
    import jax

    scopes = op_scopes(hlo_text)
    pd = jax.profiler.ProfileData.from_file(trace_file)
    total, by = 0.0, {s: 0.0 for s in SCOPES + ("other",)}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if "XLA Ops" not in line.name:
                continue
            for ev in line.events:
                name = ev.name.split(" = ")[0].strip().lstrip("%")
                if re.search(r"-(start|done)(\.\d+)?$", name):
                    continue
                total += ev.duration_ns
                by[scopes.get(name, "other")] += ev.duration_ns
    return {k: v / total for k, v in by.items()} if total else None


def run_phase(cfg, mesh, sc, params, tokens, seq, steps, note, out,
              want=None):
    """The configuration as stated (bfloat16 compute): its logits against
    the reference when ``want`` is given (a reading, no tolerance), step
    times, and the scope shares of a traced prefill and of eight traced
    decode steps (each trace kept beside ``out``)."""
    import shutil

    import jax
    import jax.numpy as jnp

    fns, _, _, _ = build(cfg, mesh)
    cache0, c_shard = cache_for(cfg, mesh, sc, batch=tokens.shape[0],
                                max_len=seq + steps)
    cache0 = jax.jit(lambda: jax.tree.map(jnp.zeros_like, cache0),
                     out_shardings=c_shard)()
    prompt, nxt, at = tokens[:, :seq], tokens[:, seq:seq + 1], jnp.int32(seq)
    # one cache sharding in and out of both programs, so that each compiled
    # program takes the other's cache
    prefill = jax.jit(fns["prefill"], out_shardings=(c_shard, None)).lower(
        params, cache0, prompt).compile()
    decode = jax.jit(fns["decode"], out_shardings=(c_shard, None)).lower(
        params, cache0, nxt, at).compile()
    cache, _ = prefill(params, cache0, prompt)
    if want is not None:
        fns["prefill"], fns["decode"] = prefill, \
            lambda p, c, t, i: decode(p, c, t, i)
        note("bf16_compute", readings(
            run_program(fns, params, cache0, tokens, seq, steps), want))

    def pf():
        c, lg = prefill(params, cache0, prompt)
        jax.block_until_ready(lg)

    def dc():
        nonlocal cache
        cache, lg = decode(params, cache, nxt, at)
        jax.block_until_ready(lg)

    pf(), dc()
    note("prefill_step_s", timed(pf, 5))
    note("decode_step_s", timed(dc, 32))
    for name, fn, prog in (("prefill", pf, prefill),
                           ("decode", lambda: timed(dc, 8), decode)):
        with tempfile.TemporaryDirectory() as tdir:
            jax.profiler.start_trace(tdir)
            fn()
            jax.profiler.stop_trace()
            try:
                trace = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                                  recursive=True)[0]
                if out:
                    shutil.copy(trace, f"{out}_{name}.xplane.pb")
                    with open(f"{out}_{name}.hlo.txt", "w") as f:
                        f.write(prog.as_text())
                note(f"scope_shares_{name}",
                     scope_shares(trace, prog.as_text()))
            except Exception as exc:  # a reading, not a check
                note(f"scope_shares_{name}_error", repr(exc))


def step_profiles(cfg, batch, seq, steps):
    """Eq. 1 of the period's prefill and decode steps on the 1x4 mesh, from
    the profiles the dry-run extraction gives (``launch.extract.run_cell``,
    compiled here on four host devices)."""
    from repro.configs.shapes import ShapeSpec
    from repro.core import timing
    from repro.core.machine import TPU_V5E
    from repro.launch import extract as EX
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 4), ("data", "model"))
    out = {}
    for shape in (ShapeSpec("period_prefill", seq, batch, "prefill"),
                  ShapeSpec("period_decode", seq + steps, batch, "decode")):
        prof = EX.run_cell(cfg, shape, mesh, "host1x4", "tp", None,
                           multi_pod=False, verbose=False, calibrate=True)
        t = timing.subsystem_times(prof, TPU_V5E)
        out[shape.kind] = {
            "eq1_s": timing.step_time(prof, TPU_V5E),
            "compute_s": t.compute, "memory_s": t.memory,
            "interconnect_s": t.interconnect,
            "flops": prof.flops, "hbm_bytes": prof.hbm_bytes,
            "collective_bytes": prof.collective_bytes}
        print(shape.kind, out[shape.kind], flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="benchmarks/out/jamba_period")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--profile", action="store_true",
                    help="Eq. 1 of the period's steps from the dry-run "
                         "extraction on four host devices")
    ap.add_argument("--tiny", action="store_true",
                    help="the SMOKE widths at a short length (rehearsal)")
    ap.add_argument("--controls", nargs="*", default=list(CONTROLS),
                    choices=CONTROLS, help="controls to run (default all)")
    ap.add_argument("--trace-only", action="store_true",
                    help="only the step times and traced scope shares of "
                         "the configuration as stated")
    args = ap.parse_args(argv)

    from repro.configs import get_config

    if args.tiny:
        cfg = get_config("jamba2-mini", smoke=True)
        batch, seq, steps = 4, 64, 8
    else:
        cfg = period_config()
        batch, seq, steps = 4, 4096, 64
    check = cfg.replace(compute_dtype="float32")
    if args.aot:
        with jax_highest():
            out = {"check": aot(check, batch, seq, steps)}
        out["run"] = aot(cfg, batch, seq, steps, reference=False)
        print(json.dumps(out))
        return 0
    if args.profile:
        print(json.dumps(step_profiles(cfg, batch, seq, steps)))
        return 0

    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_mesh
    from repro.models import layers as L

    devices = jax.devices()
    if len(devices) < 4:
        print(f"jamba_period_check: needs 4 devices, found {len(devices)}",
              file=sys.stderr)
        return 2
    mesh = make_mesh((1, 4), ("data", "model"))
    result = {"device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices)},
              "batch": batch, "seq": seq, "steps": steps,
              "layers": cfg.n_layers, "tolerances": TOLERANCES,
              "bf16_limits": BF16_LIMITS}
    t0 = time.perf_counter()

    def note(name, value):
        result[name] = value
        print(name, value, flush=True)

    with jax.set_mesh(mesh):
        fns, _, _, sc = build(check, mesh)
        params = fns["init"](jax.random.PRNGKey(args.seed))
        if args.trace_only:
            tokens = jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                                        (batch, seq + steps), 0,
                                        cfg.vocab_size)
            run_phase(cfg, mesh, sc, params, tokens, seq, steps, note,
                      args.out)
            if args.out:
                with open(args.out + ".json", "w") as f:
                    json.dump(result, f, indent=1)
            return 0
        cache0, c_shard = cache_for(check, mesh, sc, batch, seq + steps)
        zeros = jax.jit(lambda: jax.tree.map(jnp.zeros_like, cache0),
                        out_shardings=c_shard)
        tokens = jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                                    (batch, seq + steps), 0, cfg.vocab_size)
        t1 = time.perf_counter()
        want, margins = reference(fns, params, check, tokens, seq)
        note("reference_s", time.perf_counter() - t1)
        controls = {
            "renormalised router": (check.replace(moe=dataclasses.replace(
                check.moe, renormalize=True)), None),
            "bfloat16 scan": (check, jamba_ref.bf16_ssm_scan),
        }
        result["controls"] = {}
        for name in [None] + args.controls:
            ccfg, scan = controls[name] if name else (check, None)
            keep = L._ssm_scan
            if scan is not None:
                L._ssm_scan = scan
            try:
                cfns = build(ccfg, mesh)[0] if name else fns
                with jax_highest():
                    r = readings(run_program(cfns, params, zeros(), tokens,
                                             seq, steps), want)
            except Exception as exc:   # keep the readings of what follows
                r = {"error": repr(exc)[:2000]}
            finally:
                L._ssm_scan = keep
            if name:
                result["controls"][name] = r
            else:
                result["program"] = r
            print("program" if name is None else f"control {name}", r,
                  flush=True)
        del zeros, cache0

        try:
            run_phase(cfg, mesh, sc, params, tokens, seq, steps, note,
                      args.out, want)
        except Exception as exc:   # the readings so far are still written
            note("run_error", repr(exc)[:2000])

    if "decode_worst" in result.get("bf16_compute", {}):
        result["router_margins"] = {
            name: router_margins(margins, r["decode_worst"])
            for name, r in [("bf16_compute", result["bf16_compute"])]
            + list(result["controls"].items()) if "decode_worst" in r}
        print("router_margins", result["router_margins"], flush=True)
    ok = all(result["program"].get(k, np.inf) <= TOLERANCES[k]
             for k in KEYS) and all(
        result.get("bf16_compute", {}).get(k, np.inf) <= BF16_LIMITS[k]
        for k in BF16_LIMITS)
    result["ok"] = ok
    result["controls_fail_every_tolerance"] = all(
        r.get(k, -np.inf) > TOLERANCES[k]
        for r in result["controls"].values()
        for k in KEYS) if result["controls"] else None
    result["total_s"] = time.perf_counter() - t0
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out + ".json", "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps({k: result.get(k) for k in (
        "ok", "controls_fail_every_tolerance", "program", "bf16_compute",
        "device")}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
