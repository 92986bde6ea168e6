"""Model stacks for all assigned families.

Public API:
  init_model(key, cfg, abstract=False)       -> (params, axes)
  forward(params, cfg, batch)                -> (hidden, aux_loss)
  loss_fn(params, cfg, batch)                -> (loss, metrics)
  init_cache(cfg, batch, max_len, abstract)  -> (cache, axes)
  prefill(params, cfg, batch, cache)         -> (cache, logits_last)
  decode_step(params, cfg, cache, tokens, index) -> (cache, logits)

``batch`` is a dict: {"tokens": (B,S) int32, "labels": (B,S) int32, and for
stub-frontend families "frames": (B,F,D) / "patches": (B,P,D)}.

Layers are stacked along a leading "layers" axis and iterated with
``lax.scan`` (keeps HLO size O(1) in depth); remat policy per config.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.ctx import constrain
from repro.models import layers as L
from repro.models.config import Family, ModelConfig

Params = Dict[str, Any]


# --------------------------------------------------------------------------- #
# init helpers
# --------------------------------------------------------------------------- #


def _stack_init(init_fn: Callable, key, n: int, abstract: bool):
    """vmap an (params, axes) init over n layers; prepend 'layers' to axes."""
    keys = jax.random.split(key, n)
    cap: Dict[str, Any] = {}

    def wrapped(k):
        p, a = init_fn(k)
        cap["axes"] = a
        return p

    if abstract:
        params = jax.eval_shape(jax.vmap(wrapped), keys)
    else:
        params = jax.vmap(wrapped)(keys)
    axes = jax.tree.map(
        lambda _, a: ("layers",) + tuple(a), params, cap["axes"]
    )
    return params, axes


def _maybe(key, init_fn, abstract: bool):
    if abstract:
        cap = {}

        def wrapped(k):
            p, a = init_fn(k)
            cap["axes"] = a
            return p

        params = jax.eval_shape(wrapped, key)
        return params, cap["axes"]
    return init_fn(key)


def _remat(cfg: ModelConfig, fn: Callable) -> Callable:
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)


# --------------------------------------------------------------------------- #
# per-family block definitions
# --------------------------------------------------------------------------- #


def _dense_block_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    attn_p, attn_a = L.attn_init(ks[0], cfg)
    mlp_p, mlp_a = L.mlp_init(ks[1], cfg)
    n1p, n1a = L.norm_init(cfg)
    n2p, n2a = L.norm_init(cfg)
    return (
        {"attn": attn_p, "mlp": mlp_p, "ln1": n1p, "ln2": n2p},
        {"attn": attn_a, "mlp": mlp_a, "ln1": n1a, "ln2": n2a},
    )


def _dense_block_apply(bp, cfg, x, *, rope, mask, q_pos=None, k_pos=None,
                       cache=None, index=None):
    h, new_kv = L.attn_apply(
        bp["attn"], cfg, L.norm_apply(bp["ln1"], cfg, x),
        rope=rope, mask=mask, q_pos=q_pos, k_pos=k_pos,
        cache=cache, cache_index=index,
    )
    x = constrain(x + h, "acts")
    y = L.mlp_apply(bp["mlp"], cfg, L.norm_apply(bp["ln2"], cfg, x))
    return constrain(x + y, "acts"), new_kv, 0.0


def _moe_block_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    attn_p, attn_a = L.attn_init(ks[0], cfg)
    moe_p, moe_a = L.moe_init(ks[1], cfg)
    n1p, n1a = L.norm_init(cfg)
    n2p, n2a = L.norm_init(cfg)
    return (
        {"attn": attn_p, "moe": moe_p, "ln1": n1p, "ln2": n2p},
        {"attn": attn_a, "moe": moe_a, "ln1": n1a, "ln2": n2a},
    )


def _moe_block_apply(bp, cfg, x, *, rope, mask, q_pos=None, k_pos=None,
                     cache=None, index=None):
    h, new_kv = L.attn_apply(
        bp["attn"], cfg, L.norm_apply(bp["ln1"], cfg, x),
        rope=rope, mask=mask, q_pos=q_pos, k_pos=k_pos,
        cache=cache, cache_index=index,
    )
    x = constrain(x + h, "acts")
    y, aux = L.moe_apply(bp["moe"], cfg, L.norm_apply(bp["ln2"], cfg, x))
    return constrain(x + y, "acts"), new_kv, aux


def _ssm_block_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    mp, ma = L.mamba_init(ks[0], cfg)
    np_, na = L.norm_init(cfg)
    return {"mamba": mp, "ln": np_}, {"mamba": ma, "ln": na}


def _ssm_block_apply(bp, cfg, x, *, state=None):
    h, new_state = L.mamba_apply(
        bp["mamba"], cfg, L.norm_apply(bp["ln"], cfg, x),
        state=state, scan_chunk=cfg.ssm.scan_chunk,
    )
    return constrain(x + h, "acts"), new_state, 0.0


#: Param key of each hybrid mixer, and its named scope.
_MIXER_KEY = {"rec": "rec", "att": "attn", "mamba": "mamba"}
_MIXER_SCOPE = {"rec": "rglru", "att": "attention", "mamba": "mamba"}


def _hybrid_block_init(key, cfg: ModelConfig, kind: str):
    """One hybrid block: ``kind`` is "<mixer>" or "<mixer>+moe"."""
    h = cfg.hybrid
    mixer = h.mixer(kind)
    ks = jax.random.split(key, 4)
    mixer_init = {"rec": L.rglru_init, "att": L.attn_init,
                  "mamba": L.mamba_init}[mixer]
    xp, xa = mixer_init(ks[0], cfg)
    ffn = "moe" if h.is_moe(kind) else "mlp"
    fp, fa = (L.moe_init if ffn == "moe" else L.mlp_init)(ks[1], cfg)
    n1p, n1a = L.norm_init(cfg)
    n2p, n2a = L.norm_init(cfg)
    return (
        {_MIXER_KEY[mixer]: xp, ffn: fp, "ln1": n1p, "ln2": n2p},
        {_MIXER_KEY[mixer]: xa, ffn: fa, "ln1": n1a, "ln2": n2a},
    )


@dataclasses.dataclass(frozen=True)
class _AttnCtx:
    """What a hybrid attention block needs besides its params and state.

    ``index`` is None for a full sequence (forward, or prefill when a cache
    is given) and the cache slot to write for a decode step."""

    rope: Any
    mask: Any
    q_pos: jax.Array
    k_pos: jax.Array
    index: Any = None


def _hybrid_block_apply(bp, cfg: ModelConfig, kind: str, x, state,
                        ctx: _AttnCtx):
    """Pre-norm residual block -> (x, new_state, aux).  ``state`` is the
    mixer's cache entry (None without a cache)."""
    h = cfg.hybrid
    mixer = h.mixer(kind)
    xn = L.norm_apply(bp["ln1"], cfg, x)
    with jax.named_scope(_MIXER_SCOPE[mixer]):
        if mixer == "rec":
            y, new_state = L.rglru_apply(bp["rec"], cfg, xn, state=state)
        elif mixer == "mamba":
            y, new_state = L.mamba_apply(bp["mamba"], cfg, xn, state=state,
                                         scan_chunk=cfg.ssm.scan_chunk)
        elif ctx.index is not None:   # decode: attend over the cache
            y, new_state = L.attn_apply(
                bp["attn"], cfg, xn, rope=ctx.rope, mask=ctx.mask,
                q_pos=ctx.q_pos, k_pos=ctx.k_pos, cache=state,
                cache_index=ctx.index)
        else:
            y, _ = L.attn_apply(bp["attn"], cfg, xn, rope=ctx.rope,
                                mask=ctx.mask, q_pos=ctx.q_pos,
                                k_pos=ctx.q_pos)
            new_state = None if state is None else _ring_write(
                bp["attn"], cfg, xn, state, ctx.rope)
    x = constrain(x + y, "acts")
    xn = L.norm_apply(bp["ln2"], cfg, x)
    if h.is_moe(kind):
        with jax.named_scope("moe"):
            y, aux = L.moe_apply(bp["moe"], cfg, xn)
    else:
        with jax.named_scope("mlp"):
            y, aux = L.mlp_apply(bp["mlp"], cfg, xn), 0.0
    return constrain(x + y, "acts"), new_state, aux


def _ring_write(p, cfg: ModelConfig, xn, kv, rope):
    """Prefill: write the last W positions' k/v into a (B, W) cache, slot
    ``position % W`` (a ring when W is the attention window)."""
    cd = L.dtype_of(cfg.compute_dtype)
    k = jnp.einsum("bsd,dhk->bshk", xn.astype(cd), p["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", xn.astype(cd), p["wv"].astype(cd))
    if rope is not None:
        k = L.apply_rope(k, *rope, cfg.rope_style)
    W, S = kv["k"].shape[1], k.shape[1]
    take = min(W, S)
    slots = (S - take + jnp.arange(take)) % W
    return {"k": kv["k"].at[:, slots].set(k[:, -take:].astype(kv["k"].dtype)),
            "v": kv["v"].at[:, slots].set(v[:, -take:].astype(kv["v"].dtype))}


def _xattn_block_init(key, cfg: ModelConfig):
    """Whisper decoder block: self-attn + cross-attn + mlp."""
    ks = jax.random.split(key, 5)
    self_p, self_a = L.attn_init(ks[0], cfg)
    cross_p, cross_a = L.attn_init(ks[1], cfg)
    mlp_p, mlp_a = L.mlp_init(ks[2], cfg)
    norms = [L.norm_init(cfg) for _ in range(3)]
    return (
        {"self": self_p, "cross": cross_p, "mlp": mlp_p,
         "ln1": norms[0][0], "ln2": norms[1][0], "ln3": norms[2][0]},
        {"self": self_a, "cross": cross_a, "mlp": mlp_a,
         "ln1": norms[0][1], "ln2": norms[1][1], "ln3": norms[2][1]},
    )


def _xattn_block_apply(bp, cfg, x, *, mask, q_pos=None, k_pos=None,
                       enc_out=None, cache=None, index=None):
    self_cache = cache["self"] if cache is not None else None
    h, new_self = L.attn_apply(
        bp["self"], cfg, L.norm_apply(bp["ln1"], cfg, x),
        mask=mask, q_pos=q_pos, k_pos=k_pos,
        cache=self_cache, cache_index=index,
    )
    x = constrain(x + h, "acts")
    cross_cache = cache["cross"] if cache is not None else None
    h, _ = L.attn_apply(
        bp["cross"], cfg, L.norm_apply(bp["ln2"], cfg, x),
        kv_x=enc_out, cache=cross_cache,
        static_cache=cross_cache is not None,
    )
    x = constrain(x + h, "acts")
    y = L.mlp_apply(bp["mlp"], cfg, L.norm_apply(bp["ln3"], cfg, x))
    return constrain(x + y, "acts"), new_self, 0.0


# --------------------------------------------------------------------------- #
# model init
# --------------------------------------------------------------------------- #


_BLOCK_INIT = {
    Family.DENSE: _dense_block_init,
    Family.VLM: _dense_block_init,
    Family.MOE: _moe_block_init,
    Family.SSM: _ssm_block_init,
}


def hybrid_layout(cfg: ModelConfig) -> Tuple[int, int]:
    """(n_groups, n_tail) of the hybrid stack: whole periods of the
    pattern, then the first ``n_tail`` blocks of one more, which must all
    be of one kind."""
    pattern = cfg.hybrid.pattern
    n_groups, n_tail = divmod(cfg.n_layers, len(pattern))
    if len(set(pattern[:n_tail])) > 1:
        raise ValueError(f"{cfg.name}: the {n_tail} blocks past the last "
                         f"whole period mix kinds {pattern[:n_tail]}")
    return n_groups, n_tail


def _hybrid_runs(pattern) -> Tuple[Tuple[str, str, int], ...]:
    """(key, kind, length) of each run of equal consecutive blocks in the
    period.  A run is held under its kind when the kind forms one run, else
    under ``<kind>.<r>`` for its r-th run."""
    runs = []
    for kind in pattern:
        if runs and runs[-1][1] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, kind, 1])
    n_runs = collections.Counter(kind for _, kind, _ in runs)
    seen = collections.Counter()
    for run in runs:
        if n_runs[run[1]] > 1:
            run[0] = f"{run[1]}.{seen[run[1]]}"
        seen[run[1]] += 1
    return tuple(map(tuple, runs))


def _hybrid_group_init(key, cfg: ModelConfig):
    """One period: each run's blocks stacked (a run of one block is held
    unstacked), so that no block is ever sliced out of a stack."""
    runs = _hybrid_runs(cfg.hybrid.pattern)
    params, axes = {}, {}
    for k, (name, kind, n) in zip(jax.random.split(key, len(runs)), runs):
        init = functools.partial(_hybrid_block_init, cfg=cfg, kind=kind)
        if n == 1:
            params[name], axes[name] = init(k)
        else:
            params[name], axes[name] = _stack_init(init, k, n, abstract=False)
    return params, axes


def init_model(key, cfg: ModelConfig, abstract: bool = False):
    ks = jax.random.split(key, 8)
    params: Params = {}
    axes: Params = {}

    p, a = _maybe(ks[0], lambda k: L.embed_init(k, cfg), abstract)
    params["embed"], axes["embed"] = p, a
    p, a = _maybe(ks[1], lambda k: L.norm_init(cfg), abstract)
    params["final_norm"], axes["final_norm"] = p, a

    if cfg.family in (Family.DENSE, Family.VLM, Family.MOE, Family.SSM):
        init_fn = functools.partial(_BLOCK_INIT[cfg.family], cfg=cfg)
        params["layers"], axes["layers"] = _stack_init(
            lambda k: init_fn(k), ks[2], cfg.n_layers, abstract
        )
    elif cfg.family == Family.HYBRID:
        n_groups, n_tail = hybrid_layout(cfg)
        params["groups"], axes["groups"] = _stack_init(
            lambda k: _hybrid_group_init(k, cfg), ks[2], n_groups, abstract
        )
        if n_tail:
            kind = cfg.hybrid.pattern[0]
            params["tail"], axes["tail"] = _stack_init(
                lambda k: _hybrid_block_init(k, cfg, kind), ks[3], n_tail,
                abstract
            )
    elif cfg.family == Family.AUDIO:
        params["enc_layers"], axes["enc_layers"] = _stack_init(
            lambda k: _dense_block_init(k, cfg.replace(rope_style="none")),
            ks[2], cfg.n_encoder_layers, abstract,
        )
        params["dec_layers"], axes["dec_layers"] = _stack_init(
            lambda k: _xattn_block_init(k, cfg), ks[3], cfg.n_layers, abstract
        )
        p, a = _maybe(ks[4], lambda k: L.norm_init(cfg), abstract)
        params["enc_norm"], axes["enc_norm"] = p, a

        def pos_init(k):
            enc = L._init_dense(k, (cfg.encoder_seq_len, cfg.d_model),
                                L.dtype_of(cfg.param_dtype), scale=0.02)
            return enc, ("positions", "embed")

        p, a = _maybe(ks[5], pos_init, abstract)
        params["enc_pos"], axes["enc_pos"] = p, a

        if cfg.decoder_pos_len:
            def dpos_init(k):
                dec = L._init_dense(k, (cfg.decoder_pos_len, cfg.d_model),
                                    L.dtype_of(cfg.param_dtype), scale=0.02)
                return dec, ("positions", "embed")

            p, a = _maybe(ks[6], dpos_init, abstract)
            params["dec_pos"], axes["dec_pos"] = p, a
    else:  # pragma: no cover
        raise ValueError(cfg.family)

    return params, axes


# --------------------------------------------------------------------------- #
# forward (train / full-sequence)
# --------------------------------------------------------------------------- #


def _rope_for(cfg: ModelConfig, positions: jax.Array):
    if cfg.rope_style == "none":
        return None
    return L.rope_tables(positions, L.rotary_dim_of(cfg), cfg.rope_theta)


def _scan_blocks(cfg: ModelConfig, stacked, x, body):
    """scan over stacked layer params; body(bp, x) -> (x, aux)."""

    def f(carry, bp):
        xx, aux = carry
        xx, aux_d = body(bp, xx)
        return (xx, aux + aux_d), None

    f = _remat(cfg, f)
    if cfg.scan_layers:
        (x, aux), _ = lax.scan(f, (x, 0.0), stacked)
    else:
        carry = (x, 0.0)
        n = jax.tree.leaves(stacked)[0].shape[0]
        for i in range(n):
            bp = jax.tree.map(lambda t: t[i], stacked)
            carry, _ = f(carry, bp)
        x, aux = carry
    return x, aux



def _scan_or_unroll(cfg: ModelConfig, body, carry, xs):
    """lax.scan when cfg.scan_layers, else an unrolled python loop (used by
    the dry-run cost probes -- XLA's cost_analysis counts while-loop bodies
    once, so probes compile unrolled at reduced depth)."""
    if cfg.scan_layers:
        return lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        carry, y = body(carry, jax.tree.map(lambda t: t[i], xs))
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *x: jnp.stack(x), *ys)
    else:
        ys = None
    return carry, ys


def _scan_blocks_cached(cfg: ModelConfig, stacked, cache, x, body):
    """scan over (stacked params, stacked cache); body -> (x, new_c, aux)."""

    def f(carry, xs):
        xx, aux = carry
        bp, c = xs
        xx, new_c, aux_d = body(bp, xx, c)
        return (xx, aux + aux_d), new_c

    f = _remat(cfg, f)
    (x, aux), new_cache = _scan_or_unroll(cfg, f, (x, 0.0), (stacked, cache))
    return x, aux, new_cache


def _hybrid_group_apply(gp, cfg: ModelConfig, x, cache, ctx: _AttnCtx):
    """One period of blocks in pattern order -> (x, new_cache, aux); a run
    of equal blocks is a scan over its stack."""
    inner = cfg.replace(remat="none")
    aux, new_cache = 0.0, {}
    for name, kind, n in _hybrid_runs(cfg.hybrid.pattern):
        c = None if cache is None else cache[name]
        if n == 1:
            x, new_cache[name], a = _hybrid_block_apply(gp[name], cfg, kind,
                                                        x, c, ctx)
        else:
            body = functools.partial(
                lambda bp, xx, cc, kind: _hybrid_block_apply(
                    bp, cfg, kind, xx, cc, ctx), kind=kind)
            x, a, new_cache[name] = _scan_blocks_cached(inner, gp[name], c,
                                                        x, body)
        aux = aux + a
    return x, None if cache is None else new_cache, aux


def _hybrid_stack(params, cfg: ModelConfig, x, cache, ctx: _AttnCtx):
    """The whole hybrid stack: a scan over periods, then the tail.
    Returns (x, aux, new_cache); the cache is None without one."""
    body = lambda gp, xx, c: _hybrid_group_apply(gp, cfg, xx, c, ctx)
    x, aux, groups = _scan_blocks_cached(
        cfg, params["groups"], None if cache is None else cache["groups"],
        x, body)
    new_cache = None if cache is None else {"groups": groups}
    if "tail" in params:
        kind = cfg.hybrid.pattern[0]
        body = lambda bp, xx, c: _hybrid_block_apply(bp, cfg, kind, xx, c, ctx)
        x, tail_aux, tail = _scan_blocks_cached(
            cfg, params["tail"], None if cache is None else cache["tail"],
            x, body)
        aux = aux + tail_aux
        if cache is not None:
            new_cache["tail"] = tail
    return x, aux, new_cache


def _decode_ctx(cfg: ModelConfig, cache, rope, positions, index) -> _AttnCtx:
    """Key positions and the cache slot of a hybrid decode step: a ring of
    the last W positions under a window, else one slot per position."""
    mask = L.MaskSpec(causal=True, window=cfg.attn_window)
    att = [name for name, kind, _ in _hybrid_runs(cfg.hybrid.pattern)
           if cfg.hybrid.mixer(kind) == "att"]
    if not att:
        return _AttnCtx(rope, mask, positions, None, index)
    B = positions.shape[0]
    W = cache["groups"][att[0]]["k"].shape[-3]
    k_pos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[None], (B, W))
    if cfg.attn_window and W <= cfg.attn_window:
        # slot i holds the latest position p <= index with p % W == i
        return _AttnCtx(rope, mask, positions,
                        positions - ((positions - k_pos) % W), index % W)
    return _AttnCtx(rope, mask, positions, k_pos, index)


def forward(
    params: Params,
    cfg: ModelConfig,
    batch: Dict[str, jax.Array],
    cache=None,
):
    """Full-sequence forward -> (hidden (B,S,D), aux_loss[, new_cache]).

    With ``cache`` (prefill mode) the per-layer k/v / recurrent states are
    written in the same pass (single-pass prefill; no recompute)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = L.embed_apply(params["embed"], cfg, tokens)
    x = constrain(x, "acts")
    prefix_len = 0

    if cfg.family == Family.VLM:
        patches = batch["patches"].astype(x.dtype)  # SigLIP stub embeddings
        x = jnp.concatenate([patches, x], axis=1)
        prefix_len = patches.shape[1]
        S = x.shape[1]

    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    rope = _rope_for(cfg, positions)
    q_pos = positions
    new_cache = None

    if cfg.family in (Family.DENSE, Family.VLM, Family.MOE):
        block = (_moe_block_apply if cfg.family == Family.MOE
                 else _dense_block_apply)
        mask = L.MaskSpec(causal=True, window=cfg.attn_window,
                          prefix_len=prefix_len)
        if cache is not None:
            S_cache = cache["k"].shape[2]
            k_pos = jnp.broadcast_to(
                jnp.arange(S_cache, dtype=jnp.int32)[None], (B, S_cache))
            body = lambda bp, xx, c: block(bp, cfg, xx, rope=rope, mask=mask,
                                           q_pos=q_pos, k_pos=k_pos,
                                           cache=c, index=0)
            x, aux, new_cache = _scan_blocks_cached(
                cfg, params["layers"], cache, x, body)
        else:
            body = lambda bp, xx: block(bp, cfg, xx, rope=rope, mask=mask,
                                        q_pos=q_pos, k_pos=q_pos)[::2]
            x, aux = _scan_blocks(cfg, params["layers"], x, body)

    elif cfg.family == Family.SSM:
        if cache is not None:
            body = lambda bp, xx, c: _ssm_block_apply(bp, cfg, xx, state=c)
            x, aux, new_cache = _scan_blocks_cached(
                cfg, params["layers"], cache, x, body)
        else:
            body = lambda bp, xx: _ssm_block_apply(bp, cfg, xx)[::2]
            x, aux = _scan_blocks(cfg, params["layers"], x, body)

    elif cfg.family == Family.HYBRID:
        ctx = _AttnCtx(rope, L.MaskSpec(causal=True, window=cfg.attn_window),
                       q_pos, q_pos)
        x, aux, new_cache = _hybrid_stack(params, cfg, x, cache, ctx)

    elif cfg.family == Family.AUDIO:
        if "dec_pos" in params:
            x = x + params["dec_pos"].astype(x.dtype)[None, :S]
        enc = encode(params, cfg, batch["frames"])
        mask = L.MaskSpec(causal=True)
        if cache is not None:
            S_cache = cache["self"]["k"].shape[2]
            k_pos = jnp.broadcast_to(
                jnp.arange(S_cache, dtype=jnp.int32)[None], (B, S_cache))
            body = lambda bp, xx, c: (
                lambda r: (r[0], {"self": r[1], "cross": c["cross"]}, r[2])
            )(_xattn_block_apply(bp, cfg, xx, mask=mask, q_pos=q_pos,
                                 k_pos=k_pos, enc_out=enc, cache=c, index=0))
            x, aux, new_cache = _scan_blocks_cached(
                cfg, params["dec_layers"], cache, x, body)
        else:
            body = lambda bp, xx: _xattn_block_apply(
                bp, cfg, xx, mask=mask, q_pos=q_pos, k_pos=q_pos,
                enc_out=enc)[::2]
            x, aux = _scan_blocks(cfg, params["dec_layers"], x, body)
    else:  # pragma: no cover
        raise ValueError(cfg.family)

    x = L.norm_apply(params["final_norm"], cfg, x)
    if cfg.family == Family.VLM:
        x = x[:, prefix_len:]  # loss only over text positions
    if cache is not None:
        return x, aux, new_cache
    return x, aux


def encode(params: Params, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """Whisper encoder over stub frame embeddings (B, F, D)."""
    x = frames.astype(L.dtype_of(cfg.compute_dtype))
    x = x + params["enc_pos"].astype(x.dtype)[None, : x.shape[1]]
    B, F = x.shape[0], x.shape[1]
    mask = L.MaskSpec(everything=True)
    enc_cfg = cfg.replace(rope_style="none")
    body = lambda bp, xx: _dense_block_apply(
        bp, enc_cfg, xx, rope=None, mask=mask)[::2]
    x, _ = _scan_blocks(cfg, params["enc_layers"], x, body)
    return L.norm_apply(params["enc_norm"], cfg, x)


# --------------------------------------------------------------------------- #
# loss
# --------------------------------------------------------------------------- #


def _xent(params, cfg, hidden, labels):
    """Mean token cross-entropy; optionally chunked over sequence."""
    cd = L.dtype_of(cfg.compute_dtype)

    def chunk_loss(h_chunk, y_chunk):
        logits = L.unembed_apply(params["embed"], cfg, h_chunk)
        logits = constrain(logits, "logits").astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, y_chunk[..., None].astype(jnp.int32), axis=-1
        )[..., 0]
        correct = jnp.argmax(logits, axis=-1) == y_chunk
        return jnp.sum(lse - picked), jnp.sum(correct)

    B, S, _ = hidden.shape
    if cfg.logits_chunk and S % cfg.logits_chunk == 0 and S > cfg.logits_chunk:
        n = S // cfg.logits_chunk
        hs = hidden.reshape(B, n, cfg.logits_chunk, -1).swapaxes(0, 1)
        ys = labels.reshape(B, n, cfg.logits_chunk).swapaxes(0, 1)

        def f(acc, xs):
            h, y = xs
            ls, cs = jax.checkpoint(chunk_loss)(h, y)
            return (acc[0] + ls, acc[1] + cs), None

        (loss_sum, correct), _ = lax.scan(f, (jnp.float32(0.0), jnp.float32(0.0)), (hs, ys))
    else:
        loss_sum, correct = chunk_loss(hidden, labels)

    denom = jnp.float32(B * S)
    return loss_sum / denom, correct / denom


def loss_fn(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array]):
    hidden, aux = forward(params, cfg, batch)
    loss, acc = _xent(params, cfg, hidden, batch["labels"])
    total = loss + aux
    return total, {"loss": loss, "aux_loss": aux, "accuracy": acc}


# --------------------------------------------------------------------------- #
# caches + decode
# --------------------------------------------------------------------------- #


def _kv_cache_shape(cfg, n_layers, B, S):
    return {
        "k": jnp.zeros((n_layers, B, S, cfg.n_kv_heads, cfg.head_dim_),
                       L.dtype_of(cfg.compute_dtype)),
        "v": jnp.zeros((n_layers, B, S, cfg.n_kv_heads, cfg.head_dim_),
                       L.dtype_of(cfg.compute_dtype)),
    }


_KV_AXES = {"k": ("layers", "batch", None, "kv_heads", "head_dim"),
            "v": ("layers", "batch", None, "kv_heads", "head_dim")}


def _hybrid_state(cfg: ModelConfig, kind: str, B: int, W: int, lead):
    """(cache, axes) of one hybrid block kind, stacked over ``lead``."""
    h = cfg.hybrid
    cd = L.dtype_of(cfg.compute_dtype)
    extra = ("layers",) + (None,) * (len(lead) - 1)
    mixer = h.mixer(kind)
    if mixer == "att":
        shape = lead + (B, W, cfg.n_kv_heads, cfg.head_dim_)
        return ({"k": jnp.zeros(shape, cd), "v": jnp.zeros(shape, cd)},
                {k: extra + a[1:] for k, a in _KV_AXES.items()})
    if mixer == "rec":
        w = h.lru_width or cfg.d_model
        return ({"conv": jnp.zeros(lead + (B, h.conv_width - 1, w), cd),
                 "lru": jnp.zeros(lead + (B, w), jnp.float32)},
                {"conv": extra + ("batch", None, "mlp"),
                 "lru": extra + ("batch", "mlp")})
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    return ({"conv": jnp.zeros(lead + (B, s.conv_width - 1, d_in), cd),
             "ssm": jnp.zeros(lead + (B, d_in, s.state_dim), jnp.float32)},
            {"conv": extra + ("batch", None, "mlp"),
             "ssm": extra + ("batch", "mlp", "state")})


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int,
               abstract: bool = False):
    """Decode cache + logical axes.  max_len = full context length."""
    B = batch_size

    def build():
        if cfg.family in (Family.DENSE, Family.VLM, Family.MOE):
            S = max_len + (cfg.n_vision_tokens if cfg.family == Family.VLM else 0)
            if cfg.attn_window:
                S = min(S, cfg.attn_window)
            return _kv_cache_shape(cfg, cfg.n_layers, B, S), dict(_KV_AXES)
        if cfg.family == Family.SSM:
            s = cfg.ssm
            d_in = s.expand * cfg.d_model
            cache = {
                "conv": jnp.zeros((cfg.n_layers, B, s.conv_width - 1, d_in),
                                  L.dtype_of(cfg.compute_dtype)),
                "ssm": jnp.zeros((cfg.n_layers, B, d_in, s.state_dim), jnp.float32),
            }
            ax = {"conv": ("layers", "batch", None, "mlp"),
                  "ssm": ("layers", "batch", "mlp", "state")}
            return cache, ax
        if cfg.family == Family.HYBRID:
            h = cfg.hybrid
            n_groups, n_tail = hybrid_layout(cfg)
            W = min(max_len, cfg.attn_window or max_len)
            groups = {name: _hybrid_state(cfg, kind, B, W, (n_groups,)
                                          if n == 1 else (n_groups, n))
                      for name, kind, n in _hybrid_runs(h.pattern)}
            cache = {"groups": {k: v[0] for k, v in groups.items()}}
            ax = {"groups": {k: v[1] for k, v in groups.items()}}
            if n_tail:
                cache["tail"], ax["tail"] = _hybrid_state(
                    cfg, h.pattern[0], B, W, (n_tail,))
            return cache, ax
        if cfg.family == Family.AUDIO:
            cache = {
                "self": _kv_cache_shape(cfg, cfg.n_layers, B, max_len),
                "cross": _kv_cache_shape(cfg, cfg.n_layers, B, cfg.encoder_seq_len),
            }
            ax = {"self": dict(_KV_AXES), "cross": dict(_KV_AXES)}
            return cache, ax
        raise ValueError(cfg.family)  # pragma: no cover

    if abstract:
        cap = {}

        def w():
            c, a = build()
            cap["a"] = a
            return c

        cache = jax.eval_shape(w)
        return cache, cap["a"]
    return build()


def decode_step(params: Params, cfg: ModelConfig, cache,
                tokens: jax.Array, index: jax.Array):
    """One-token decode.  tokens: (B, 1); index: position of the new token in
    the context -- a scalar shared by all rows, or a (B,) vector of per-row
    positions (continuous batching with staggered admissions).
    Returns (new_cache, logits (B, 1, V))."""
    B = tokens.shape[0]
    x = L.embed_apply(params["embed"], cfg, tokens)
    index = jnp.asarray(index, jnp.int32)
    if cfg.family == Family.VLM:
        index = index + cfg.n_vision_tokens  # cache slots are absolute
    if index.ndim:
        positions = jnp.reshape(index, (B, 1))
    else:
        positions = jnp.full((B, 1), index, jnp.int32)
    rope = _rope_for(cfg, positions)
    q_pos = positions

    if cfg.family in (Family.DENSE, Family.VLM, Family.MOE):
        S_cache = cache["k"].shape[2]
        if cfg.attn_window and S_cache <= cfg.attn_window:
            # ring-buffer slots; all slots <= index are valid within window
            slot = index % S_cache
            k_pos = jnp.broadcast_to(jnp.arange(S_cache, dtype=jnp.int32)[None],
                                     (B, S_cache))
            # slot i holds position: latest p <= index with p % S == i
            # (positions broadcasts (B, 1) against (B, S) for both index kinds)
            k_pos = positions - ((positions - k_pos) % S_cache)
            write_index = slot
        else:
            k_pos = jnp.broadcast_to(jnp.arange(S_cache, dtype=jnp.int32)[None],
                                     (B, S_cache))
            write_index = index
        mask = L.MaskSpec(causal=True, window=cfg.attn_window)

        block = (_moe_block_apply if cfg.family == Family.MOE
                 else _dense_block_apply)

        def f(xx, xs):
            bp, c = xs
            xx, new_kv, _ = block(bp, cfg, xx, rope=rope, mask=mask,
                                  q_pos=q_pos, k_pos=k_pos,
                                  cache=c, index=write_index)
            return xx, new_kv

        x, new_cache = _scan_or_unroll(cfg, f, x, (params["layers"], cache))

    elif cfg.family == Family.SSM:
        def f(xx, xs):
            bp, c = xs
            xx, new_state, _ = _ssm_block_apply(bp, cfg, xx, state=c)
            return xx, new_state

        x, new_cache = _scan_or_unroll(cfg, f, x, (params["layers"], cache))

    elif cfg.family == Family.HYBRID:
        x, _, new_cache = _hybrid_stack(
            params, cfg, x, cache, _decode_ctx(cfg, cache, rope, positions, index))

    elif cfg.family == Family.AUDIO:
        if "dec_pos" in params:
            if index.ndim:
                x = x + jnp.take(params["dec_pos"], index,
                                 axis=0)[:, None].astype(x.dtype)
            else:
                x = x + lax.dynamic_slice_in_dim(
                    params["dec_pos"], index, 1, axis=0).astype(x.dtype)[None]
        S_cache = cache["self"]["k"].shape[2]
        k_pos = jnp.broadcast_to(jnp.arange(S_cache, dtype=jnp.int32)[None],
                                 (B, S_cache))
        mask = L.MaskSpec(causal=True)

        def f(xx, xs):
            bp, c = xs
            xx, new_self, _ = _xattn_block_apply(
                bp, cfg, xx, mask=mask, q_pos=q_pos, k_pos=k_pos,
                cache=c, index=index)
            return xx, {"self": new_self, "cross": c["cross"]}

        x, new_cache = _scan_or_unroll(cfg, f, x, (params["dec_layers"], cache))
    else:  # pragma: no cover
        raise ValueError(cfg.family)

    x = L.norm_apply(params["final_norm"], cfg, x)
    logits = L.unembed_apply(params["embed"], cfg, x)
    return new_cache, logits


def prefill(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array], cache):
    """Run the full prompt, fill the cache, return (cache, last-token logits).

    Single-pass: cache writes happen inside the same forward (no recompute).
    Decode equivalence is asserted in tests.
    """
    tokens = batch["tokens"]
    B, S = tokens.shape

    if cfg.family == Family.AUDIO:
        enc = encode(params, cfg, batch["frames"])
        # precompute per-layer cross k/v into the cache
        cd = L.dtype_of(cfg.compute_dtype)

        def xkv(carry, bp):
            k = jnp.einsum("bsd,dhk->bshk", enc.astype(cd), bp["cross"]["wk"].astype(cd))
            v = jnp.einsum("bsd,dhk->bshk", enc.astype(cd), bp["cross"]["wv"].astype(cd))
            return carry, {"k": k, "v": v}

        _, cross = _scan_or_unroll(cfg, xkv, 0, params["dec_layers"])
        cache = dict(cache)
        cache["cross"] = cross

    hidden, _, new_cache = forward(params, cfg, batch, cache=cache)
    logits = L.unembed_apply(params["embed"], cfg, hidden[:, -1:])
    return new_cache, logits
