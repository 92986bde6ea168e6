"""Production training launcher.

Wires the full stack: arch config -> mesh + sharding variant -> sharded
train state -> fault-tolerant Trainer (async checkpoints, restart, straggler
monitor) -> step-indexed data pipeline.  On a real fleet each host runs this
with JAX_COORDINATOR/process-env set and jax.distributed.initialize picks up
the pod topology; on CPU (this container) it runs the same code path on the
local device with the smoke config.

  PYTHONPATH=src python -m repro.launch.train --arch chatglm3-6b --smoke \
      --steps 50 --seq-len 64 --batch 4
"""

from __future__ import annotations

import argparse
import os
import sys

import jax

from repro import configs as C
from repro.data.pipeline import DataConfig
from repro.distributed import ctx as CTX
from repro.distributed import sharding as SH
from repro.launch import mesh as MESH
from repro.optim import adamw
from repro.training.step import init_state
from repro.training.trainer import Trainer, TrainerConfig


def maybe_init_distributed() -> None:
    """Multi-host init from standard env (no-op single-process)."""
    if os.environ.get("JAX_COORDINATOR_ADDRESS"):
        jax.distributed.initialize(
            coordinator_address=os.environ["JAX_COORDINATOR_ADDRESS"],
            num_processes=int(os.environ.get("JAX_NUM_PROCESSES", "1")),
            process_id=int(os.environ.get("JAX_PROCESS_ID", "0")),
        )


def pick_mesh(args):
    n = len(jax.devices())
    if args.mesh == "pod":
        return MESH.make_production_mesh(multi_pod=False), False
    if args.mesh == "multipod":
        return MESH.make_production_mesh(multi_pod=True), True
    # auto: largest (data, model) grid that fits the device count
    model = 1
    for m in (16, 8, 4, 2, 1):
        if n % m == 0 and m <= n:
            model = m
            break
    return MESH.make_mesh((n // model, model), ("data", "model")), False


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="chatglm3-6b", choices=C.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--mesh", choices=("auto", "pod", "multipod"),
                    default="auto")
    ap.add_argument("--variant", choices=SH.SHARDING_VARIANTS, default="zero1")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--peak-lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args(argv)

    maybe_init_distributed()
    cfg = C.get_config(args.arch, smoke=args.smoke)
    mesh, multi_pod = pick_mesh(args)
    sc = SH.ShardingConfig(variant=args.variant, multi_pod=multi_pod)
    oc = adamw.OptimizerConfig(peak_lr=args.peak_lr,
                               warmup_steps=max(args.steps // 10, 1),
                               total_steps=args.steps)

    # sharded state template for Trainer restore/placement
    state_t, axes = init_state(jax.random.PRNGKey(0), cfg, oc, abstract=True)
    shardings = {
        "params": SH.param_specs(state_t["params"], axes, mesh, sc),
        "opt": {
            "m": SH.opt_state_specs(state_t["opt"]["m"], axes, mesh, sc),
            "v": SH.opt_state_specs(state_t["opt"]["v"], axes, mesh, sc),
            "step": SH.scalar_spec(mesh),
        },
    }
    tc = TrainerConfig(total_steps=args.steps,
                       checkpoint_every=args.ckpt_every,
                       checkpoint_dir=args.ckpt_dir, accum=args.accum)
    dc = DataConfig(seq_len=args.seq_len, global_batch=args.batch,
                    host_index=jax.process_index(),
                    host_count=jax.process_count())

    use_shardings = shardings if mesh.size > 1 else None
    trainer = Trainer(cfg, tc, dc, oc, shardings=use_shardings)
    with jax.set_mesh(mesh), CTX.use_rules(
            SH.activation_rules(mesh, sc, kind="train")):
        out = trainer.run()
    losses = [m["loss"] for m in out["metrics"]]
    print(f"done: {out['steps']} steps, loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}, {out['restarts']} restarts, "
          f"{out['straggler_events']} stragglers")
    return 0


if __name__ == "__main__":
    sys.exit(main())
