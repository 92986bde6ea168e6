"""Per-assigned-architecture smoke tests (deliverable f).

Each of the 11 architectures instantiates its REDUCED same-family config and
runs one forward + one train step on CPU, asserting output shapes + no NaNs.
The FULL configs are exercised only via the dry-run (no allocation).
"""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, REGISTRY, SHAPES, applicable, get_config
from repro.models import transformer as T
from repro.models.config import Family
from repro.optim import adamw
from repro.training.step import init_state, make_train_step

KEY = jax.random.PRNGKey(0)


def make_batch(cfg, B=2, S=16):
    toks = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    if cfg.family == Family.AUDIO:
        batch["frames"] = jnp.ones((B, cfg.encoder_seq_len, cfg.d_model),
                                   jnp.float32)
    if cfg.family == Family.VLM:
        batch["patches"] = jnp.ones((B, cfg.n_vision_tokens, cfg.d_model),
                                    jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward(arch):
    cfg = get_config(arch, smoke=True)
    params, _ = T.init_model(KEY, cfg)
    B, S = 2, 16
    batch = make_batch(cfg, B, S)
    hidden, aux = T.forward(params, cfg, batch)
    assert hidden.shape == (B, S, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(hidden)))


@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = get_config(arch, smoke=True)
    oc = adamw.OptimizerConfig(warmup_steps=1, total_steps=10)
    state, _ = init_state(KEY, cfg, oc)
    step = make_train_step(cfg, oc)
    batch = make_batch(cfg)
    new_state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    assert int(new_state["opt"]["step"]) == 1
    # params actually moved
    moved = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))),
        state["params"], new_state["params"])
    assert max(jax.tree.leaves(moved)) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_decode_step(arch):
    cfg = get_config(arch, smoke=True)
    params, _ = T.init_model(KEY, cfg)
    B, S = 2, 8
    cache, _ = T.init_cache(cfg, B, S)
    tok = jnp.zeros((B, 1), jnp.int32)
    cache, logits = T.decode_step(params, cfg, cache, tok, jnp.int32(0))
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_full_configs_match_assignment():
    """Exact published numbers from the assignment block."""
    c = REGISTRY["chatglm3-6b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (28, 4096, 32, 2, 13696, 65024)
    c = REGISTRY["qwen3-32b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (64, 5120, 64, 8, 25600, 151936)
    assert c.qk_norm
    c = REGISTRY["qwen1.5-4b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (40, 2560, 20, 20, 6912, 151936)
    assert c.qkv_bias
    c = REGISTRY["deepseek-67b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (95, 8192, 64, 8, 22016, 102400)
    c = REGISTRY["whisper-medium"]
    assert (c.n_layers, c.d_model, c.n_heads, c.d_ff, c.vocab_size) == (
        24, 1024, 16, 4096, 51865)
    c = REGISTRY["recurrentgemma-9b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (38, 4096, 16, 1, 12288, 256000)
    assert c.hybrid.pattern == ("rec", "rec", "att")
    c = REGISTRY["grok-1-314b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads,
            c.vocab_size) == (64, 6144, 48, 8, 131072)
    assert (c.moe.n_experts, c.moe.top_k, c.moe.d_ff_expert) == (8, 2, 32768)
    c = REGISTRY["qwen2-moe-a2.7b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.vocab_size) == (
        24, 2048, 16, 151936)
    assert (c.moe.n_experts, c.moe.top_k, c.moe.n_shared_experts) == (60, 4, 4)
    c = REGISTRY["paligemma-3b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (18, 2048, 8, 1, 16384, 257216)
    assert c.n_vision_tokens == 256
    c = REGISTRY["falcon-mamba-7b"]
    assert (c.n_layers, c.d_model, c.vocab_size) == (64, 4096, 65024)
    assert c.ssm.state_dim == 16


def test_cell_applicability():
    """long_500k runs exactly for the sub-quadratic archs."""
    long = SHAPES["long_500k"]
    runnable = {a for a in ARCH_IDS if applicable(REGISTRY[a], long)[0]}
    assert runnable == {"falcon-mamba-7b", "recurrentgemma-9b"}
    for a in ARCH_IDS - runnable if isinstance(ARCH_IDS, set) else \
            set(ARCH_IDS) - runnable:
        ok, reason = applicable(REGISTRY[a], long)
        assert not ok and "full-attention" in reason
