"""Jamba2-Mini's hybrid period against its float32 reference (``jamba_ref``)
at SMOKE size on the CPU, with seeded random weights.

The program computes in float32 here (``compute_dtype``), as the reference
does; what is left between them is the order of float32 sums (chunked
scans, fused einsums), a few 1e-7 of the logits' scale.  Each tolerance is
written beside its check with the reason for it, and each is tight enough
that the router renormalised, or the Mamba scan in bfloat16, fails it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

import jamba_ref
from repro.configs import get_config
from repro.models import layers as L
from repro.models import transformer as T

SMOKE = get_config("jamba2-mini", smoke=True).replace(compute_dtype="float32")

#: Largest |logit difference| over the largest |logit|.  Float32 on both
#: sides reads about 1e-6 (the order of sums differs); a renormalised
#: router or a bfloat16 Mamba scan reads 1e-2 and more.
TOL = 1e-4


def _params(cfg, seed=0):
    """Seeded weights, with every norm scale and bias moved off its init
    value so that each enters the comparison."""
    params, _ = T.init_model(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    leaves = [t + 0.1 * jax.random.normal(k, t.shape, t.dtype)
              if t.ndim == 1 or (t.ndim == 2 and t.shape[0] == 1) else t
              for t, k in zip(leaves, keys)]
    return jax.tree.unflatten(tree, leaves)


def _tokens(cfg, B=2, S=16, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0,
                              cfg.vocab_size)


_ref_forward = jax.jit(jamba_ref.forward, static_argnums=(1, 3))


def _reference(params, cfg, tokens):
    w, kinds = jamba_ref.from_program(params, cfg.hybrid.pattern)
    return jnp.stack([_ref_forward(w, kinds, t, cfg.moe.top_k)
                      for t in tokens])


def _err(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _program_logits(params, cfg, tokens):
    hidden, _ = T.forward(params, cfg, {"tokens": tokens, "labels": tokens})
    return L.unembed_apply(params["embed"], cfg, hidden)


def test_forward_matches_reference():
    params = _params(SMOKE)
    tokens = _tokens(SMOKE)
    err = _err(_program_logits(params, SMOKE, tokens),
               _reference(params, SMOKE, tokens))
    assert err < TOL, err


def test_prefill_then_decode_matches_reference_forward():
    """Prefill 12 tokens, then decode 4 through the mixed cache (Mamba conv
    and SSM state beside the attention block's KV cache); every logit is
    compared with the reference's full forward over all 16 tokens."""
    params = _params(SMOKE)
    tokens = _tokens(SMOKE)
    B, S, P = tokens.shape[0], tokens.shape[1], 12
    cache, _ = T.init_cache(SMOKE, B, S)
    cache, first = T.prefill(params, SMOKE,
                             {"tokens": tokens[:, :P],
                              "labels": tokens[:, :P]}, cache)
    got = [first[:, 0]]
    for i in range(P, S):
        cache, lg = T.decode_step(params, SMOKE, cache, tokens[:, i:i + 1],
                                  jnp.int32(i))
        got.append(lg[:, 0])
    want = _reference(params, SMOKE, tokens)[:, P - 1:]
    err = _err(jnp.stack(got, axis=1), want)
    assert err < TOL, err


@pytest.mark.parametrize("fault", ["renormalised router", "bfloat16 scan"])
def test_tolerance_catches_lower_fidelity(fault, monkeypatch):
    """The controls: the same comparison fails by far with either fault."""
    params = _params(SMOKE)
    tokens = _tokens(SMOKE, S=64)
    cfg = SMOKE
    if fault == "renormalised router":
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, renormalize=True))
    else:
        monkeypatch.setattr(L, "_ssm_scan", jamba_ref.bf16_ssm_scan)
    err = _err(_program_logits(params, cfg, tokens),
               _reference(params, SMOKE, tokens))
    assert err > 10 * TOL, err


def test_param_counts_match_initialised_tree():
    """Exact: ``param_counts`` leaves out only the final norm."""
    for cfg in (SMOKE, get_config("recurrentgemma-9b", smoke=True)):
        params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
        size = sum(p.size for p in jax.tree.leaves(params))
        total, active = cfg.param_counts()
        assert total == size - cfg.d_model, (cfg.name, total, size)
    # active: the two chosen experts of each MoE block, of four
    total, active = SMOKE.param_counts()
    n_moe = sum(k.endswith("+moe") for k in SMOKE.hybrid.pattern)
    expert = 3 * SMOKE.d_model * SMOKE.moe.d_ff_expert
    assert total - active == n_moe * 2 * expert


def test_published_sizes():
    cfg = get_config("jamba2-mini")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim_, cfg.d_ff, cfg.vocab_size) == (
        32, 4096, 32, 8, 128, 14336, 65536)
    assert (cfg.ssm.state_dim, cfg.ssm.conv_width, cfg.ssm.expand,
            cfg.ssm.dt_rank) == (16, 4, 2, 256)
    assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.d_ff_expert,
            cfg.moe.renormalize) == (16, 2, 14336, False)
    pattern = cfg.hybrid.pattern
    assert [i for i, k in enumerate(pattern) if k.startswith("att")] == [4]
    assert [i for i, k in enumerate(pattern) if k.endswith("+moe")] == [
        1, 3, 5, 7]
    assert not cfg.tie_embeddings and cfg.rope_style == "none"
    total, active = cfg.param_counts()
    assert abs(total / 51.6e9 - 1) < 5e-3, total
    assert abs(active / 12.1e9 - 1) < 5e-3, active
    assert not cfg.supports_long_context


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_hybrid_probes_equal_unrolled_full_depth(kind):
    """The costs calibrated from probes at one and two periods plus the
    tail equal those of the whole stack compiled unrolled: 17 blocks, two
    periods and one Mamba block, on a 1x1 mesh.  Tolerance 1e-6 relative:
    the probes are the same HLO, block for block, and cost_analysis sums
    per op."""
    from repro.configs.shapes import ShapeSpec
    from repro.distributed import sharding as SH
    from repro.launch import extract as EX
    from repro.launch.mesh import make_mesh

    cfg = get_config("jamba2-mini", smoke=True).replace(n_layers=17)
    shape = ShapeSpec(f"probe_{kind}", 32, 2, kind)
    mesh = make_mesh((1, 1), ("data", "model"))
    sc = SH.ShardingConfig(variant="tp")
    cal = EX.calibrate_costs(cfg, shape, mesh, "host1x1", sc,
                             multi_pod=False, verbose=False)
    full = EX._probe_costs(cfg, shape, mesh, sc, cfg.n_layers)
    cal["flops"] -= EX._analytic_scan_flops(cfg, shape, mesh.size)
    for key in ("flops", "dot_flops"):
        cal[key] += cal["routed_excess_flops"]
    for key in ("flops", "bytes", "hbm", "transc", "dot_flops"):
        assert cal[key] == pytest.approx(full[key], rel=1e-6), key


@pytest.mark.parametrize("kind", ["prefill", "train"])
def test_routed_expert_flops_counted_at_expected_rows(kind):
    """The calibration's excess over the routed experts' expected work is
    (n_experts - 1) times that work: every routed row through one expert,
    three matmuls of 2 * d_model * d_ff_expert FLOPs each, forward (and the
    backward's two for train) in each MoE block.  Exact up to float64
    rounding: the probes count dots per op."""
    from repro.configs.shapes import ShapeSpec
    from repro.distributed import sharding as SH
    from repro.launch import extract as EX
    from repro.launch.mesh import make_mesh

    cfg = get_config("jamba2-mini", smoke=True).replace(n_layers=16)
    m = cfg.moe
    shape = ShapeSpec(f"probe_{kind}", 32, 2, kind)
    mesh = make_mesh((1, 1), ("data", "model"))
    sc = SH.ShardingConfig(variant="tp")
    period = len(cfg.hybrid.pattern)
    excess = EX._routed_excess(cfg, shape, mesh, sc, period,
                               EX._probe_costs(cfg, shape, mesh, sc, period))
    passes = 3 if kind == "train" else 1
    expected = (passes * shape.global_batch * shape.seq_len * m.top_k
                * EX._n_moe_blocks(cfg, cfg.n_layers)
                * 3 * 2 * cfg.d_model * m.d_ff_expert)
    assert excess == pytest.approx((m.n_experts - 1) * expected, rel=1e-9)
