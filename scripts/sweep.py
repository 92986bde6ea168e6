"""Design-space sweep CLI -- score machine populations against profiles.

Generates a machine-variant population (grid or low-discrepancy random) from
``repro.core.sweep.ParamSpace``, scores every (app x variant) cell with the
batched congruence engine, and dumps the best-fit variants + Pareto front
(aggregate congruence vs. area proxy) as JSON and/or markdown.

  PYTHONPATH=src:. python scripts/sweep.py --num 2048 --out sweep
  PYTHONPATH=src:. python scripts/sweep.py --mode grid --num 1024 \
      --format md --timing-model overlap
  PYTHONPATH=src:. python scripts/sweep.py --num 100000 --backend jax
  PYTHONPATH=src:. python scripts/sweep.py --num 100000 --backend pallas
  PYTHONPATH=src:. python scripts/sweep.py --num 1000000 --shards 8 \
      --backend jax --format md
  PYTHONPATH=src:. python scripts/sweep.py --num 10000000 --stream \
      --shards 64 --backend pallas --checkpoint-dir /tmp/megasweep --resume

Profiles come from ``benchmarks/artifacts/*.json`` (the dry-run outputs)
when present, else the synthetic trio -- same policy as the benchmark
harness.
"""

import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))

from benchmarks import common  # noqa: E402
from repro.core.kernels_xp import validate_backend_arg as validate_backend  # noqa: E402
from repro.core.machine import TPU_V5E, VARIANTS  # noqa: E402
from repro.core.sweep import ParamSpace, run_sweep, shard_sweep  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mesh", default="pod16x16",
                    help="artifact mesh filter ('' = all meshes)")
    ap.add_argument("--suite", default=None, metavar="SUITE",
                    help="score a model-zoo suite instead of the dry-run "
                         "artifacts: zoo | zoo-smoke, with an optional "
                         ":scenario (train | serve-prefill | serve-decode), "
                         "e.g. --suite zoo:train.  zoo-smoke extracts on a "
                         "cache miss; zoo requires the cache built by "
                         "`python -m repro.core.model_zoo`; generated "
                         "suites gen:<count>[:seed=S][:mode=halton|rng] "
                         "are accepted too")
    ap.add_argument("--gen", type=int, default=None, metavar="N",
                    help="score N generated stress workloads "
                         "(shorthand for --suite gen:N; AppSpace.default "
                         "sampled by Halton indices, seed 0)")
    ap.add_argument("--mode", choices=("random", "grid"), default="random")
    ap.add_argument("--num", type=int, default=1024,
                    help="population size (grid rounds up per-dim)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--span", type=float, default=4.0,
                    help="sweep each rate this many x below/above nominal")
    ap.add_argument("--max-links", type=int, default=8)
    ap.add_argument("--beta", type=float, default=None,
                    help="explicit target step time (s); default: per-app "
                         "ideal-compute beta against the baseline variant")
    ap.add_argument("--timing-model", choices=("serial", "overlap"),
                    default="serial")
    ap.add_argument("--backend", default=None,
                    help="kernel backend (default: $REPRO_SWEEP_BACKEND, "
                         "then numpy); 'jax' jits + device-places the "
                         "batched kernels, 'pallas' runs the fused TPU "
                         "kernel (interpret mode where jax runs on the CPU); any "
                         "register_backend() name is accepted")
    ap.add_argument("--shards", type=int, default=0, metavar="S",
                    help="score the population in S shards (shard_sweep): "
                         "mesh-sharded statistics + per-shard Pareto "
                         "pre-filter, for populations that outgrow one "
                         "device (0 = single-device run_sweep)")
    ap.add_argument("--stream", action="store_true",
                    help="regenerate each shard's variants on the fly "
                         "(PopulationStream): never materializes the full "
                         "population, so --num is bounded by patience, not "
                         "RAM; implies sharding (default shard count keeps "
                         "chunks ~64k variants)")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="write resumable per-shard checkpoints to DIR "
                         "(repro.checkpoint.store; atomic renames)")
    ap.add_argument("--resume", action="store_true",
                    help="with --checkpoint-dir: skip shards already "
                         "completed by a previous (killed) run; results "
                         "are byte-identical to an uninterrupted sweep")
    ap.add_argument("--abort-after-shard", type=int, default=None,
                    metavar="S", help="exit(3) after shard S completes "
                         "(deterministic kill hook for the CI resume "
                         "round-trip smoke)")
    ap.add_argument("--no-named", action="store_true",
                    help="do not prepend baseline/denser/densest")
    ap.add_argument("--top", type=int, default=16)
    ap.add_argument("--format", choices=("json", "md", "both"), default="both")
    ap.add_argument("--out", default=None,
                    help="output path stem (default: stdout); writes "
                         "<out>.json / <out>.md per --format")
    args = ap.parse_args(argv)
    if args.num < 1:
        ap.error("--num must be >= 1")
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint-dir")
    validate_backend(ap, args.backend)
    if args.gen is not None:
        if args.suite:
            ap.error("--gen and --suite are mutually exclusive")
        if args.gen < 1:
            ap.error("--gen must be >= 1")
        args.suite = f"gen:{args.gen}"

    if args.suite:
        from repro.core.model_zoo import resolve_suite, validate_suite_name
        try:
            validate_suite_name(args.suite)
        except ValueError as exc:
            ap.error(str(exc))
        profiles, synthetic = resolve_suite(args.suite), False
        print(f"suite {args.suite}: {len(profiles)} profiles",
              file=sys.stderr)
    else:
        profiles, synthetic = common.profiles_or_synthetic(args.mesh)
    space = ParamSpace.default(nominal=TPU_V5E, span=args.span,
                               max_links=args.max_links)
    sweep_kwargs = dict(
        space=space,
        n=args.num,
        mode=args.mode,
        seed=args.seed,
        include_named=() if args.no_named else VARIANTS,
        beta=args.beta,
        timing_model=args.timing_model,
        backend=args.backend,
    )
    if args.shards > 0 or args.stream or args.checkpoint_dir:
        progress = None
        if args.abort_after_shard is not None:
            class _Abort(Exception):
                pass

            def progress(s, num_shards, lo, hi):
                print(f"shard {s + 1}/{num_shards} done [{lo}, {hi})",
                      file=sys.stderr)
                if s >= args.abort_after_shard:
                    raise _Abort
        try:
            # keep_top must cover --top: each shard keeps its local top-k,
            # so a smaller keep would silently prune global ranks out of
            # the report.
            sharded = shard_sweep(
                profiles,
                num_shards=args.shards if args.shards > 0 else None,
                keep_top=max(16, args.top), stream=args.stream,
                checkpoint_dir=args.checkpoint_dir, resume=args.resume,
                progress=progress, **sweep_kwargs)
        except _Abort if args.abort_after_shard is not None else ():
            print(f"aborted after shard {args.abort_after_shard} "
                  f"(checkpoint in {args.checkpoint_dir})", file=sys.stderr)
            return 3
        result = sharded.result
        resumed = (f", {sharded.resumed_shards} shards resumed"
                   if sharded.resumed_shards else "")
        print(f"shard-swept {len(result.profiles)} apps x "
              f"{sharded.num_variants} variants in {sharded.num_shards} "
              f"shards ({sharded.mesh_axis}, {result.backend} backend"
              f"{', streamed' if sharded.streamed else ''}{resumed}"
              f"{', SYNTHETIC profiles' if synthetic else ''}); "
              f"{len(result.machines)} Pareto candidates kept; front: "
              f"{len(sharded.pareto_front())} variants "
              f"(3-D: {len(sharded.pareto_front_3d())})",
              file=sys.stderr)
        blob_source = sharded
    else:
        result = run_sweep(profiles, **sweep_kwargs)
        print(f"swept {len(result.profiles)} apps x {len(result.machines)} "
              f"variants on the {result.backend} backend"
              f"{' (SYNTHETIC profiles)' if synthetic else ''}; "
              f"pareto front: {len(result.pareto_front())} variants "
              f"(3-D: {len(result.pareto_front_3d())})",
              file=sys.stderr)
        blob_source = result

    blob = json.dumps(blob_source.to_json(top_k=args.top), indent=1,
                      sort_keys=True)
    md = blob_source.markdown(top_k=args.top)
    if args.out is None:
        if args.format in ("json", "both"):
            print(blob)
        if args.format in ("md", "both"):
            print(md)
    else:
        if args.format in ("json", "both"):
            with open(args.out + ".json", "w") as f:
                f.write(blob + "\n")
        if args.format in ("md", "both"):
            with open(args.out + ".md", "w") as f:
                f.write(md + "\n")
        print(f"wrote {args.out}.{{json,md}}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
