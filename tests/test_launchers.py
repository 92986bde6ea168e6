"""Launcher entry points run end-to-end on CPU (smoke scale)."""

import shutil

from repro.launch import serve as serve_launch
from repro.launch import train as train_launch


def test_train_launcher(tmp_path):
    shutil.rmtree("/tmp/repro_launch_train_test", ignore_errors=True)
    rc = train_launch.main([
        "--arch", "chatglm3-6b", "--smoke", "--steps", "6",
        "--seq-len", "32", "--batch", "2",
        "--ckpt-dir", str(tmp_path), "--ckpt-every", "3",
    ])
    assert rc == 0
    from repro.checkpoint import store
    assert store.latest_step(str(tmp_path)) == 6


def test_serve_launcher():
    rc = serve_launch.main([
        "--arch", "falcon-mamba-7b", "--smoke", "--requests", "2",
        "--slots", "2", "--new-tokens", "3", "--max-len", "32",
    ])
    assert rc == 0


def test_serve_codesign_launcher(capsys):
    from repro.launch import serve_codesign

    rc = serve_codesign.main(["--smoke", "--suites", "2", "--apps", "2"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "mega-sweep shard" in out and "frontier+warm" in out

    # bad flags die at parse time through the one validation path
    import pytest
    with pytest.raises(SystemExit):
        serve_codesign.main(["--smoke", "--backend", "cuda"])
    with pytest.raises(SystemExit):
        serve_codesign.main(["--smoke", "--budgets", "-1"])


def test_serve_codesign_fails_when_a_job_fails(monkeypatch, capsys):
    """A request that does not finish ``done`` makes the launcher exit
    non-zero and name the job, even though the other jobs rendered."""
    from repro.launch import serve_codesign
    from repro.serving.codesign_service import CodesignService

    def broken(self, job):
        raise RuntimeError("injected frontier failure")

    monkeypatch.setattr(CodesignService, "_run_frontier", broken)
    rc = serve_codesign.main(["--smoke", "--suites", "2", "--apps", "1"])
    assert rc == 1
    err = capsys.readouterr().err
    assert "frontier did not finish" in err and "injected" in err


def _cache_probe(tmp_path, env_dir):
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "path = enable_compile_cache()\n"
        "assert jax.config.jax_compilation_cache_dir == path, path\n"
        f"if {env_dir is not None}:\n"
        "    jax.jit(lambda x: x * 3 + 1)(jnp.ones(4)).block_until_ready()\n"
        "print(path)\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=tmp_path, timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1], root


def test_compile_cache_follows_env_var(tmp_path):
    """With $JAX_COMPILATION_CACHE_DIR set, compiled programs land there."""
    cache = tmp_path / "cache"
    path, _ = _cache_probe(tmp_path, cache)
    assert path == str(cache)
    assert any(p.name.startswith("jit_") for p in cache.iterdir())


def test_compile_cache_defaults_into_checkout(tmp_path):
    """Without it, the cache sits at a fixed, gitignored checkout path --
    never one built from a temp name, a pid or the time."""
    import os

    path, root = _cache_probe(tmp_path, None)
    assert path == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
