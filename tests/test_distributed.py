"""Sharding rules + multi-device integration (8 fake devices, subprocess).

The in-process tests exercise pure rule logic (no devices); the subprocess
tests set XLA_FLAGS for 8 host devices and run real sharded compiles,
an end-to-end sharded train step, elastic checkpoint resharding (8 -> 4
device mesh), and a mini dry-run with profile extraction.
"""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


# --------------------------------------------------------------------------- #
# rule logic (no devices needed beyond the default one)
# --------------------------------------------------------------------------- #


def test_spec_rules():
    out = run_sub("""
        from repro.distributed import sharding as SH
        from repro.launch import mesh as MESH
        from jax.sharding import PartitionSpec as P
        mesh = MESH.make_mesh((2, 4), ("data", "model"))
        sc = SH.ShardingConfig(variant="tp")
        # mlp dim sharded on model
        s = SH.spec_for_tensor((64, 128), ("embed", "mlp"), mesh, sc)
        assert s == P(None, "model"), s
        # kv_heads=2 not divisible by model=4 -> head_dim fallback
        s = SH.spec_for_tensor((64, 2, 16), ("embed", "kv_heads", "head_dim"),
                               mesh, sc)
        assert s == P(None, None, "model"), s
        # kv_heads divisible -> sharded, head_dim left alone
        s = SH.spec_for_tensor((64, 4, 16), ("embed", "kv_heads", "head_dim"),
                               mesh, sc)
        assert s == P(None, "model", None), s
        # batch axis across data
        s = SH.spec_for_tensor((8, 128), ("batch", None), mesh, sc)
        assert s == P("data", None), s
        # batch not divisible -> replicated
        s = SH.spec_for_tensor((3, 128), ("batch", None), mesh, sc)
        assert s == P(None, None), s
        # fsdp shards the biggest replicated dim over data
        s = SH.spec_for_tensor((64, 128), ("embed", "mlp"), mesh,
                               SH.ShardingConfig(variant="fsdp"),
                               fsdp_this=True)
        assert s == P("data", "model"), s
        print("RULES-OK")
    """)
    assert "RULES-OK" in out


def test_sharded_train_step_runs():
    """End-to-end numerically-executed sharded train step on 8 devices."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.distributed import sharding as SH, ctx as CTX
        from repro.launch import mesh as MESH
        from repro.optim import adamw
        from repro.training.step import init_state, make_train_step
        from repro.data.pipeline import DataConfig, SyntheticLM

        mesh = MESH.make_mesh((2, 4), ("data", "model"))
        cfg = get_config("chatglm3-6b", smoke=True).replace(
            d_model=64, n_heads=4, n_kv_heads=2, d_ff=128)
        oc = adamw.OptimizerConfig(warmup_steps=1, total_steps=10)
        sc = SH.ShardingConfig(variant="zero1")
        state, axes = init_state(jax.random.PRNGKey(0), cfg, oc)
        p_sh = SH.param_specs(state["params"], axes, mesh, sc)
        o_sh = {"m": SH.opt_state_specs(state["opt"]["m"], axes, mesh, sc),
                "v": SH.opt_state_specs(state["opt"]["v"], axes, mesh, sc),
                "step": SH.scalar_spec(mesh)}
        st_sh = {"params": p_sh, "opt": o_sh}
        state = jax.tree.map(jax.device_put, state, st_sh)
        data = SyntheticLM(cfg, DataConfig(seq_len=32, global_batch=4))
        batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
        step = jax.jit(make_train_step(cfg, oc), donate_argnums=0)
        with jax.set_mesh(mesh), CTX.use_rules(
                SH.activation_rules(mesh, sc, kind="train")):
            state, metrics = step(state, batch)
            l1 = float(metrics["loss"])
            state, metrics = step(state, batch)
            l2 = float(metrics["loss"])
        assert np.isfinite(l1) and np.isfinite(l2)
        assert l2 < l1  # same batch twice -> loss drops
        print("TRAIN-OK", l1, l2)
    """)
    assert "TRAIN-OK" in out


def test_sharded_matches_single_device():
    """Sharded loss == unsharded loss (same params, same batch)."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.distributed import sharding as SH, ctx as CTX
        from repro.launch import mesh as MESH
        from repro.models import transformer as T
        from repro.data.pipeline import DataConfig, SyntheticLM

        cfg = get_config("qwen3-32b", smoke=True).replace(compute_dtype="float32")
        params, axes = T.init_model(jax.random.PRNGKey(0), cfg)
        data = SyntheticLM(cfg, DataConfig(seq_len=32, global_batch=4))
        batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
        base, _ = T.loss_fn(params, cfg, batch)

        mesh = MESH.make_mesh((2, 4), ("data", "model"))
        sc = SH.ShardingConfig(variant="tp")
        p_sh = SH.param_specs(params, axes, mesh, sc)
        params_sh = jax.tree.map(jax.device_put, params, p_sh)
        with jax.set_mesh(mesh), CTX.use_rules(
                SH.activation_rules(mesh, sc, kind="train")):
            sharded, _ = jax.jit(lambda p, b: T.loss_fn(p, cfg, b))(params_sh, batch)
        assert abs(float(base) - float(sharded)) < 1e-3, (base, sharded)
        print("MATCH-OK", float(base), float(sharded))
    """)
    assert "MATCH-OK" in out


def test_elastic_checkpoint_reshard():
    """Save on an 8-device (2,4) mesh; restore onto a 4-device (2,2) mesh."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.checkpoint import store
        import tempfile, os

        devs = jax.devices()
        mesh8 = Mesh(np.array(devs).reshape(2, 4), ("data", "model"))
        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        sh8 = {"w": NamedSharding(mesh8, P("data", "model"))}
        tree = jax.tree.map(jax.device_put, tree, sh8)
        d = tempfile.mkdtemp()
        store.save(d, 5, tree)

        mesh4 = Mesh(np.array(devs[:4]).reshape(2, 2), ("data", "model"))
        sh4 = {"w": NamedSharding(mesh4, P("data", "model"))}
        restored, extra = store.restore(d, tree, shardings=sh4)
        assert extra["step"] == 5
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(64).reshape(8, 8))
        assert restored["w"].sharding.mesh.devices.size == 4
        print("ELASTIC-OK")
    """)
    assert "ELASTIC-OK" in out


def test_mini_dryrun_profile_extraction():
    """Mini dry-run: multi-pod mesh compile + profile + congruence report."""
    out = run_sub("""
        import jax
        from repro import configs as C
        from repro.configs.shapes import ShapeSpec
        from repro.core import TPU_V5E, profile_congruence, analyze
        from repro.distributed import sharding as SH, ctx as CTX
        from repro.launch import mesh as MESH
        from repro.launch.specs import input_specs
        from repro.core import costs as CO

        mesh = MESH.make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = C.get_config("grok-1-314b", smoke=True)
        shape = ShapeSpec("t", 32, 4, "train")
        sc = SH.ShardingConfig(variant="fsdp", multi_pod=True)
        cell = input_specs(cfg, shape, mesh, sc)
        with jax.set_mesh(mesh), CTX.use_rules(
                SH.activation_rules(mesh, sc, kind="train")):
            compiled = jax.jit(cell.step_fn, in_shardings=cell.in_shardings,
                               out_shardings=cell.out_shardings,
                               donate_argnums=cell.donate_argnums
                               ).lower(*cell.args).compile()
        prof = CO.profile_from_compiled(
            "mini", compiled, num_devices=8, model_flops=1e9, tokens=128,
            devices_per_pod=4)
        assert prof.flops > 0 and prof.total_collective_bytes > 0
        rep = profile_congruence(prof, TPU_V5E)
        assert set(rep.scores) == {"ICS", "HRCS", "LBCS"}
        rl = analyze(prof, TPU_V5E)
        assert rl.dominant in ("compute", "memory", "interconnect")
        print("DRYRUN-OK", rep.dominant, rl.dominant)
    """)
    assert "DRYRUN-OK" in out
