"""Neural net layers (pure JAX, no framework deps).

Every layer is an (init, apply) pair.  ``*_init`` returns ``(params, axes)``
where ``axes`` mirrors the param pytree with tuples of *logical* axis names
("embed", "heads", "mlp", "experts", "vocab", ...).  The distributed layer
maps logical axes onto mesh axes per sharding variant, so model code never
mentions physical meshes.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.config import ModelConfig

Params = Dict[str, Any]
Axes = Dict[str, Any]


def dtype_of(name: str):
    return jnp.dtype(name)


def _init_dense(key, shape, dtype, scale: Optional[float] = None):
    if scale is None:
        scale = 1.0 / math.sqrt(shape[0])
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# --------------------------------------------------------------------------- #
# Norms
# --------------------------------------------------------------------------- #


def norm_init(cfg: ModelConfig, dim: Optional[int] = None) -> Tuple[Params, Axes]:
    dim = dim or cfg.d_model
    dt = dtype_of(cfg.param_dtype)
    if cfg.norm == "layernorm":
        p = {"scale": jnp.ones((dim,), dt), "bias": jnp.zeros((dim,), dt)}
        a = {"scale": ("embed",), "bias": ("embed",)}
    else:
        p = {"scale": jnp.ones((dim,), dt)}
        a = {"scale": ("embed",)}
    return p, a


def norm_apply(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * lax.rsqrt(ms + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_head_norm(scale: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    """RMS norm with a learned scale over the trailing dim (qwen3's per-head
    qk_norm; Jamba's dt, B and C norms)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------- #
# Rotary embeddings ("full" neox-style, "half" = partial/interleaved a la GLM)
# --------------------------------------------------------------------------- #


def rope_tables(
    positions: jax.Array, rotary_dim: int, theta: float
) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables: (..., seq, rotary_dim//2), f32."""
    half = rotary_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(
    x: jax.Array, cos: jax.Array, sin: jax.Array, style: str
) -> jax.Array:
    """x: (B, S, H, hd).  "full": rotate all dims (paired halves).
    "half": chatglm-style 2d rotary -- rotate only the first half of head_dim,
    interleaved pairing; the second half passes through."""
    if style == "none":
        return x
    hd = x.shape[-1]
    if style == "half":
        rot, keep = jnp.split(x, 2, axis=-1)
        xr = rot.astype(jnp.float32).reshape(*rot.shape[:-1], -1, 2)
        x1, x2 = xr[..., 0], xr[..., 1]
        c = cos[:, :, None, :]
        s = sin[:, :, None, :]
        o1 = x1 * c - x2 * s
        o2 = x2 * c + x1 * s
        out = jnp.stack([o1, o2], axis=-1).reshape(rot.shape)
        return jnp.concatenate([out.astype(x.dtype), keep], axis=-1)
    # full, neox pairing (first half with second half)
    half = hd // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    o1 = x1 * c - x2 * s
    o2 = x2 * c + x1 * s
    return jnp.concatenate([o1, o2], axis=-1).astype(x.dtype)


def rotary_dim_of(cfg: ModelConfig) -> int:
    return cfg.head_dim_ // 2 if cfg.rope_style == "half" else cfg.head_dim_


# --------------------------------------------------------------------------- #
# Embedding / unembedding
# --------------------------------------------------------------------------- #


def embed_init(key, cfg: ModelConfig) -> Tuple[Params, Axes]:
    dt = dtype_of(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    p: Params = {"tok": _init_dense(k1, (cfg.vocab_size, cfg.d_model), dt, scale=1.0)}
    a: Axes = {"tok": ("vocab", "embed")}
    if not cfg.tie_embeddings:
        p["unembed"] = _init_dense(k2, (cfg.d_model, cfg.vocab_size), dt)
        a["unembed"] = ("embed", "vocab")
    return p, a


def embed_apply(p: Params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    x = p["tok"].astype(dtype_of(cfg.compute_dtype))[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def unembed_apply(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    cd = dtype_of(cfg.compute_dtype)
    w = p["unembed"] if not cfg.tie_embeddings else p["tok"].T
    return jnp.einsum("bsd,dv->bsv", x.astype(cd), w.astype(cd))


# --------------------------------------------------------------------------- #
# Attention (GQA; causal / sliding-window / prefix-LM; self or cross; cached)
# --------------------------------------------------------------------------- #


def attn_init(key, cfg: ModelConfig) -> Tuple[Params, Axes]:
    dt = dtype_of(cfg.param_dtype)
    d, hd = cfg.d_model, cfg.head_dim_
    ks = jax.random.split(key, 6)
    p: Params = {
        "wq": _init_dense(ks[0], (d, cfg.n_heads, hd), dt),
        "wk": _init_dense(ks[1], (d, cfg.n_kv_heads, hd), dt),
        "wv": _init_dense(ks[2], (d, cfg.n_kv_heads, hd), dt),
        "wo": _init_dense(ks[3], (cfg.n_heads, hd, d), dt, scale=1.0 / math.sqrt(cfg.q_dim)),
    }
    a: Axes = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads, hd), dt)
        p["bk"] = jnp.zeros((cfg.n_kv_heads, hd), dt)
        p["bv"] = jnp.zeros((cfg.n_kv_heads, hd), dt)
        a["bq"] = ("heads", "head_dim")
        a["bk"] = ("kv_heads", "head_dim")
        a["bv"] = ("kv_heads", "head_dim")
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
        a["q_norm"] = ("head_dim",)
        a["k_norm"] = ("head_dim",)
    return p, a


@jax.tree_util.register_static
class MaskSpec:
    """Attention-mask description; the (S_q, S_k) boolean mask itself is
    built lazily per q-chunk inside attention (a full 32k x 32k mask is 1 GB
    per device -- never materialize it)."""

    def __init__(self, *, causal: bool = True, window: Optional[int] = None,
                 prefix_len: int = 0, everything: bool = False):
        self.causal = causal
        self.window = window
        self.prefix_len = prefix_len
        self.everything = everything  # True -> no masking at all

    def build(self, q_pos: jax.Array, k_pos: jax.Array) -> Optional[jax.Array]:
        """(B, S_q) x (B, S_k) -> (B, S_q, S_k) bool, or None if unmasked."""
        if self.everything:
            return None
        dq = q_pos[..., :, None]
        dk = k_pos[..., None, :]
        mask = jnp.ones(jnp.broadcast_shapes(dq.shape, dk.shape), bool)
        if self.causal:
            m = dk <= dq
            if self.prefix_len:
                m = m | (dk < self.prefix_len)
            mask = mask & m
        if self.window is not None:
            mask = mask & (dq - dk < self.window)
        return mask


def _attn_mask(q_pos, k_pos, *, causal, window, prefix_len: int = 0):
    """Compatibility helper: materialized mask (small shapes only)."""
    return MaskSpec(causal=causal, window=window, prefix_len=prefix_len).build(
        q_pos, k_pos)


def _sdpa(q, k, v, mask, cfg: ModelConfig):
    """Masked softmax attention core.  q: (B,Sq,K,G,hd); k,v: (B,T,K,hd);
    mask: (B,Sq,T) bool or None."""
    cd = q.dtype
    B, Sq, K, G, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k) * scale
    scores = scores.astype(jnp.float32)
    if cfg.attn_logit_softcap:
        cap = cfg.attn_logit_softcap
        scores = cap * jnp.tanh(scores / cap)
    if mask is not None:
        big_neg = jnp.asarray(-1e30, jnp.float32)
        scores = jnp.where(mask[:, None, None, :, :], scores, big_neg)
    probs = jax.nn.softmax(scores, axis=-1).astype(cd)
    return jnp.einsum("bkgst,btkd->bskgd", probs, v)


def attn_apply(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    rope: Optional[Tuple[jax.Array, jax.Array]] = None,
    mask: Optional[MaskSpec] = None,
    q_pos: Optional[jax.Array] = None,
    k_pos: Optional[jax.Array] = None,
    kv_x: Optional[jax.Array] = None,
    cache: Optional[Dict[str, jax.Array]] = None,
    cache_index: Optional[jax.Array] = None,
    kv_rope: Optional[Tuple[jax.Array, jax.Array]] = None,
    static_cache: bool = False,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Self- or cross-attention.

    x: (B, S, D).  ``mask`` is a MaskSpec evaluated lazily against
    (q_pos, k_pos) -- per q-chunk when ``cfg.attn_q_chunk`` divides S, so the
    full (S, T) mask / score matrices are never materialized at long context.
    With ``cache`` (dict of k/v (B, S_max, K, hd)) and ``cache_index``:
    decode mode -- writes new k/v at cache_index and attends over the cache.
    ``kv_x`` switches to cross-attention.
    """
    cd = dtype_of(cfg.compute_dtype)
    B, S, _ = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    G = H // K

    q = jnp.einsum("bsd,dhk->bshk", x.astype(cd), p["wq"].astype(cd))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cd)

    if cache is not None and (static_cache or kv_x is not None):
        # cross-attention with precomputed encoder k/v (whisper decode)
        k, v = cache["k"].astype(cd), cache["v"].astype(cd)
    else:
        src = kv_x if kv_x is not None else x
        k = jnp.einsum("bsd,dhk->bshk", src.astype(cd), p["wk"].astype(cd))
        v = jnp.einsum("bsd,dhk->bshk", src.astype(cd), p["wv"].astype(cd))
        if cfg.qkv_bias:
            k = k + p["bk"].astype(cd)
            v = v + p["bv"].astype(cd)

    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm"], q, cfg.norm_eps)
        if not (cache is not None and (static_cache or kv_x is not None)):
            k = rms_head_norm(p["k_norm"], k, cfg.norm_eps)

    if rope is not None:
        cos_q, sin_q = rope
        q = apply_rope(q, cos_q, sin_q, cfg.rope_style)
        if kv_x is None and not static_cache:
            cos_k, sin_k = kv_rope if kv_rope is not None else rope
            k = apply_rope(k, cos_k, sin_k, cfg.rope_style)

    new_cache = None
    if cache is not None and kv_x is None and not static_cache:
        # decode/prefill-with-cache: insert k,v at cache_index
        assert cache_index is not None
        idx = jnp.asarray(cache_index)
        if idx.ndim:
            # per-row positions (continuous batching: one index per slot);
            # decode-only, so S == 1 and each row writes its own cache slot
            rows = jnp.arange(B)
            k_cache = cache["k"].at[rows, idx].set(
                k[:, 0].astype(cache["k"].dtype))
            v_cache = cache["v"].at[rows, idx].set(
                v[:, 0].astype(cache["v"].dtype))
        else:
            k_cache = lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, cache_index, 0, 0)
            )
            v_cache = lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, cache_index, 0, 0)
            )
        new_cache = {"k": k_cache, "v": v_cache}
        k, v = k_cache.astype(cd), v_cache.astype(cd)

    T = k.shape[1]
    if q_pos is None:
        q_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if k_pos is None:
        k_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    if mask is None:
        mask = MaskSpec(everything=True)

    # Pallas flash-attention path (TPU target; interpret-mode on CPU).
    # Covers self-attention without prefix-LM masking; q_pos must be the
    # plain 0..S-1 range (full-sequence forward).
    if (cfg.attn_impl == "pallas" and kv_x is None and new_cache is None
            and cache is None and not mask.everything
            and mask.prefix_len == 0 and mask.causal):
        from repro.kernels import ops as kops

        def _blk(n: int, pref: int = 128) -> int:
            for b in (pref, 64, 32, 16, 8, 4, 2, 1):
                if n % b == 0:
                    return b
            return 1

        ctx = kops.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True, window=mask.window,
            block_q=_blk(S), block_kv=_blk(T),
        ).transpose(0, 2, 1, 3)
        out = jnp.einsum("bshk,hkd->bsd", ctx.reshape(B, S, H, hd),
                         p["wo"].astype(cd))
        return out, new_cache

    qg = q.reshape(B, S, K, G, hd)
    qc = cfg.attn_q_chunk
    if qc and S > qc and S % qc == 0:
        # blockwise attention: scan over q chunks; scores stay (B,qc,T)
        n_chunks = S // qc
        q_chunks = qg.reshape(B, n_chunks, qc, K, G, hd).swapaxes(0, 1)
        qpos_chunks = q_pos.reshape(B, n_chunks, qc).swapaxes(0, 1)

        def chunk(carry, inp):
            q_c, qp_c = inp
            m = mask.build(qp_c, k_pos)
            ctx_c = _sdpa(q_c, k, v, m, cfg)
            return carry, ctx_c

        _, ctx = lax.scan(chunk, 0, (q_chunks, qpos_chunks))
        ctx = ctx.swapaxes(0, 1).reshape(B, S, H, hd)
    else:
        ctx = _sdpa(qg, k, v, mask.build(q_pos, k_pos), cfg).reshape(B, S, H, hd)

    out = jnp.einsum("bshk,hkd->bsd", ctx, p["wo"].astype(cd))
    return out, new_cache


# --------------------------------------------------------------------------- #
# MLPs
# --------------------------------------------------------------------------- #


def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Tuple[Params, Axes]:
    dt = dtype_of(cfg.param_dtype)
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp in ("swiglu", "geglu"):
        p = {
            "w_gate": _init_dense(ks[0], (d, f), dt),
            "w_up": _init_dense(ks[1], (d, f), dt),
            "w_down": _init_dense(ks[2], (f, d), dt),
        }
        a = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")}
    else:  # plain gelu (whisper)
        p = {
            "w_up": _init_dense(ks[0], (d, f), dt),
            "b_up": jnp.zeros((f,), dt),
            "w_down": _init_dense(ks[1], (f, d), dt),
            "b_down": jnp.zeros((d,), dt),
        }
        a = {"w_up": ("embed", "mlp"), "b_up": ("mlp",),
             "w_down": ("mlp", "embed"), "b_down": ("embed",)}
    return p, a


def mlp_apply(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    cd = dtype_of(cfg.compute_dtype)
    x = x.astype(cd)
    if cfg.mlp in ("swiglu", "geglu"):
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(cd))
        up = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(cd))
        act = jax.nn.silu(gate) if cfg.mlp == "swiglu" else jax.nn.gelu(gate, approximate=True)
        return jnp.einsum("bsf,fd->bsd", act * up, p["w_down"].astype(cd))
    h = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(cd)) + p["b_up"].astype(cd)
    h = jax.nn.gelu(h, approximate=True)
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(cd)) + p["b_down"].astype(cd)


# --------------------------------------------------------------------------- #
# Mixture of Experts
# --------------------------------------------------------------------------- #


def moe_init(key, cfg: ModelConfig) -> Tuple[Params, Axes]:
    m = cfg.moe
    assert m is not None
    dt = dtype_of(cfg.param_dtype)
    d, f, E = cfg.d_model, m.d_ff_expert, m.n_experts
    ks = jax.random.split(key, 6)
    p: Params = {
        "router": _init_dense(ks[0], (d, E), dt),
        "w_gate": _init_dense(ks[1], (E, d, f), dt),
        "w_up": _init_dense(ks[2], (E, d, f), dt),
        "w_down": _init_dense(ks[3], (E, f, d), dt, scale=1.0 / math.sqrt(f)),
    }
    a: Axes = {
        "router": ("embed", None),
        "w_gate": ("experts", "embed", "mlp"),
        "w_up": ("experts", "embed", "mlp"),
        "w_down": ("experts", "mlp", "embed"),
    }
    if m.n_shared_experts:
        fs = m.d_ff_shared * m.n_shared_experts
        p["shared"] = {
            "w_gate": _init_dense(ks[4], (d, fs), dt),
            "w_up": _init_dense(jax.random.fold_in(ks[4], 1), (d, fs), dt),
            "w_down": _init_dense(jax.random.fold_in(ks[4], 2), (fs, d), dt),
        }
        p["shared_gate"] = _init_dense(ks[5], (d, 1), dt)
        a["shared"] = {
            "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed"),
        }
        a["shared_gate"] = ("embed", None)
    return p, a


def moe_apply(
    p: Params, cfg: ModelConfig, x: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Token-choice top-k MoE.  x: (B, S, D) -> (y, aux_loss).

    impl="gmm": sort tokens by expert and run grouped matmuls via
    ``lax.ragged_dot`` (the TPU megablox-style dataflow), expert-parallel
    under a mesh whose ``model`` axis divides the experts (``_moe_gmm``).
    impl="dense": run every expert on every token (tiny smoke tests only).
    """
    m = cfg.moe
    assert m is not None
    cd = dtype_of(cfg.compute_dtype)
    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D).astype(cd)
    E, k = m.n_experts, m.top_k

    logits = jnp.einsum("td,de->te", xt, p["router"].astype(cd)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = lax.top_k(probs, k)                     # (T, k)
    if m.renormalize:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    # load-balancing aux loss (Switch-style)
    density = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), axis=0)
    router_prob = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * router_prob) * E * m.aux_loss_weight

    act = jax.nn.silu if cfg.mlp == "swiglu" else functools.partial(
        jax.nn.gelu, approximate=True)

    if m.impl == "dense":
        # (T, E, f) -- every expert everywhere; only for tiny configs.
        h_g = jnp.einsum("td,edf->tef", xt, p["w_gate"].astype(cd))
        h_u = jnp.einsum("td,edf->tef", xt, p["w_up"].astype(cd))
        h = act(h_g) * h_u
        y_all = jnp.einsum("tef,efd->ted", h, p["w_down"].astype(cd))
        combine = jnp.zeros((T, E), cd).at[jnp.arange(T)[:, None], idx].add(
            gates.astype(cd))
        y = jnp.einsum("ted,te->td", y_all, combine)
    elif m.impl == "capacity":
        y = _moe_capacity(p, cfg, xt, gates, idx, act)
    else:
        y = _moe_gmm(p, xt, gates, idx, act)

    if m.n_shared_experts:
        sh = p["shared"]
        g = jnp.einsum("td,df->tf", xt, sh["w_gate"].astype(cd))
        u = jnp.einsum("td,df->tf", xt, sh["w_up"].astype(cd))
        ys = jnp.einsum("tf,fd->td", act(g) * u, sh["w_down"].astype(cd))
        sg = jax.nn.sigmoid(
            jnp.einsum("td,do->to", xt, p["shared_gate"].astype(cd)).astype(jnp.float32)
        ).astype(cd)
        y = y + ys * sg

    return y.reshape(B, S, D), aux


def _gmm_rows(xt, gates, idx, w_gate, w_up, w_down, act, e0=0):
    """Grouped matmuls (``lax.ragged_dot``) over the experts held here,
    ``e0`` onward: each (token, choice) routed to one of them is a row,
    sorted by expert; the rows of experts held elsewhere sort last and add
    nothing.  -> (T, D), the part of the layer these experts give."""
    T, D = xt.shape
    n_held, k = w_gate.shape[0], idx.shape[1]
    cd = xt.dtype
    local = idx.reshape(-1) - e0                          # (T*k,)
    held = (local >= 0) & (local < n_held)
    order = jnp.argsort(jnp.where(held, local, n_held))   # stable
    token_of = order // k
    xs = jnp.take(xt, token_of, axis=0)                   # (T*k, D) grouped
    group_sizes = jnp.bincount(jnp.where(held, local, n_held),
                               length=n_held + 1)[:n_held].astype(jnp.int32)
    h_g = lax.ragged_dot(xs, w_gate.astype(cd), group_sizes)
    h_u = lax.ragged_dot(xs, w_up.astype(cd), group_sizes)
    out = lax.ragged_dot(act(h_g) * h_u, w_down.astype(cd), group_sizes)
    w = jnp.take(jnp.where(held, gates.reshape(-1), 0.0), order, axis=0)
    out = jnp.where(jnp.take(held, order)[:, None], out * w.astype(cd)[:, None],
                    0)
    return jnp.zeros((T, D), cd).at[token_of].add(out)


def _moe_gmm(p: Params, xt: jax.Array, gates: jax.Array, idx: jax.Array,
             act) -> jax.Array:
    """Token-choice MoE on grouped matmuls.

    Under a launcher's mesh whose ``model`` axis divides the experts, the
    layer is expert-parallel: each device holds its experts' weights,
    routes its data shard's tokens over all experts, computes what its own
    experts give, and one psum over ``model`` adds the parts (the
    partitioner would otherwise gather every expert's weights onto every
    device).  Elsewhere every expert is local."""
    from repro.distributed import ctx as _ctx

    E = p["w_gate"].shape[0]
    shmap = _ctx.shmap_info()
    if shmap is not None:
        dp_axes, tp_axis, mesh = shmap
        n_dp = math.prod(mesh.shape[a] for a in dp_axes)
        if E % mesh.shape[tp_axis] == 0 and xt.shape[0] % n_dp == 0:
            from jax.sharding import PartitionSpec as P

            dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]

            def kernel(x, g, i, w1, w2, w3):
                e0 = lax.axis_index(tp_axis) * w1.shape[0]
                return lax.psum(_gmm_rows(x, g, i, w1, w2, w3, act, e0),
                                tp_axis)

            tokens, experts = P(dp, None), P(tp_axis, None, None)
            return jax.shard_map(
                kernel, mesh=mesh,
                in_specs=(tokens, tokens, tokens, experts, experts, experts),
                out_specs=tokens, check_vma=False,
            )(xt, gates, idx, p["w_gate"], p["w_up"], p["w_down"])
    return _gmm_rows(xt, gates, idx, p["w_gate"], p["w_up"], p["w_down"],
                     act)


def _moe_capacity(p: Params, cfg: ModelConfig, xt: jax.Array,
                  gates: jax.Array, idx: jax.Array, act) -> jax.Array:
    """Capacity-based MoE dispatch (GShard/Switch dataflow, TPU-shaped).

    Tokens are routed into a per-expert buffer of fixed capacity C via
    gather/scatter (linear cost, well-behaved VJPs), experts run as one
    batched dense einsum (E, C, d) x (E, d, f) -- no ragged primitives, so
    forward AND backward stay at ~active-expert FLOPs, unlike the XLA
    ragged_dot fallback whose VJP materializes dense (rows, f, E) tensors.
    Overflowing tokens are dropped (standard; exact when capacity_factor is
    generous).  Routing is computed per data-parallel group (ctx.dp_groups)
    so dispatch never crosses device boundaries.
    """
    from repro.distributed import ctx as _ctx

    m = cfg.moe
    cd = xt.dtype
    T, D = xt.shape
    E, k = m.n_experts, m.top_k
    G = _ctx.data_parallel_groups()
    if T % G != 0:
        G = 1
    Tg = T // G
    C = min(Tg * k, int(-(-Tg * k * m.capacity_factor // E)))

    xg = _ctx.constrain(xt.reshape(G, Tg, D), "moe_tokens")
    gg = gates.reshape(G, Tg, k).astype(cd)
    ig = idx.reshape(G, Tg, k)

    def one_group(x, gate, eidx, w_gate, w_up, w_down):
        flat_e = eidx.reshape(-1)                       # (Tg*k,)
        order = jnp.argsort(flat_e)
        sorted_e = flat_e[order]
        token_of = order // k
        counts = jnp.bincount(flat_e, length=E)
        starts = jnp.concatenate(
            [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
        slot = jnp.arange(Tg * k) - starts[sorted_e]    # rank within expert
        keep = slot < C
        # dropped rows scatter to row C (mode=drop discards them)
        scat_e = jnp.where(keep, sorted_e, E)
        scat_c = jnp.where(keep, slot, C)
        buf = jnp.zeros((E, C, D), cd).at[scat_e, scat_c].set(
            jnp.take(x, token_of, axis=0), mode="drop")
        h_g = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(cd))
        h_u = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(cd))
        h = act(h_g) * h_u
        out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(cd))
        # combine: gather each kept row back to its token, weighted
        rows = out[jnp.minimum(scat_e, E - 1), jnp.minimum(scat_c, C - 1)]
        w = jnp.take(gate.reshape(-1), order) * keep.astype(cd)
        y = jnp.zeros((Tg, D), cd).at[token_of].add(rows * w[:, None])
        return y

    shmap = _ctx.shmap_info()
    if shmap is not None:
        # Megatron-MoE dataflow under explicit shard_map: tokens sharded over
        # the data axes (one routing group per data shard, replicated across
        # the model axis), expert f-dim sharded over "model"; each device
        # computes its f-slice for its data-shard's tokens, combines LOCALLY
        # to token-sized partial outputs, and a single psum('model') per
        # layer reduces (Tg, D) -- k*capacity_factor x less interconnect
        # traffic than letting the partitioner all-reduce the (E, C, D)
        # expert buffers.
        dp_axes, tp_axis, mesh = shmap
        from jax.sharding import PartitionSpec as P

        dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]

        def kernel(x_blk, g_blk, i_blk, w1, w2, w3):
            y = one_group(x_blk[0], g_blk[0], i_blk[0], w1, w2, w3)
            y = jax.lax.psum(y, tp_axis)
            return y[None]

        y = jax.shard_map(
            kernel,
            mesh=mesh,
            in_specs=(P(dp, None, None), P(dp, None, None), P(dp, None, None),
                      P(None, None, tp_axis), P(None, None, tp_axis),
                      P(None, tp_axis, None)),
            out_specs=P(dp, None, None),
            check_vma=False,
        )(xg, gg, ig, p["w_gate"], p["w_up"], p["w_down"])
    else:
        y = jax.vmap(
            lambda x, g, i: one_group(x, g, i, p["w_gate"], p["w_up"],
                                      p["w_down"])
        )(xg, gg, ig)
    y = _ctx.constrain(y, "moe_tokens")
    return y.reshape(T, D)


# --------------------------------------------------------------------------- #
# RG-LRU recurrent block (RecurrentGemma / Griffin)
# --------------------------------------------------------------------------- #

_LRU_BLOCKS = 8      # block-diagonal gate structure
_LRU_C = 8.0


def rglru_init(key, cfg: ModelConfig) -> Tuple[Params, Axes]:
    h = cfg.hybrid
    assert h is not None
    dt = dtype_of(cfg.param_dtype)
    d = cfg.d_model
    w = h.lru_width or d
    wb = w // _LRU_BLOCKS
    ks = jax.random.split(key, 7)
    p: Params = {
        "w_x": _init_dense(ks[0], (d, w), dt),
        "w_y": _init_dense(ks[1], (d, w), dt),
        "conv_w": _init_dense(ks[2], (h.conv_width, w), dt, scale=0.1),
        "conv_b": jnp.zeros((w,), dt),
        "gate_a": _init_dense(ks[3], (_LRU_BLOCKS, wb, wb), dt),
        "gate_x": _init_dense(ks[4], (_LRU_BLOCKS, wb, wb), dt),
        "lambda": jnp.full((w,), 2.0, dt),  # softplus param for decay a
        "w_out": _init_dense(ks[5], (w, d), dt),
    }
    a: Axes = {
        "w_x": ("embed", "mlp"),
        "w_y": ("embed", "mlp"),
        "conv_w": ("conv", "mlp"),
        "conv_b": ("mlp",),
        "gate_a": (None, "mlp_block", "mlp_block"),
        "gate_x": (None, "mlp_block", "mlp_block"),
        "lambda": ("mlp",),
        "w_out": ("mlp", "embed"),
    }
    return p, a


def causal_conv1d(
    x: jax.Array, w: jax.Array, b: Optional[jax.Array],
    state: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv over (B, S, C).  w: (width, C).

    Returns (y, new_state) with state = last (width-1) inputs for decode.
    """
    width = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = jnp.zeros_like(x)
    for i in range(width):
        y = y + xp[:, i: i + x.shape[1], :] * w[i].astype(x.dtype)
    if b is not None:
        y = y + b.astype(x.dtype)
    new_state = xp[:, -(width - 1):, :] if width > 1 else state
    return y, new_state


def _lru_scan(a: jax.Array, bx: jax.Array, h0: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """h_t = a_t * h_{t-1} + bx_t over axis 1.  a, bx: (B, S, W) f32."""
    from repro.distributed import ctx as _ctx

    # keep the channel dim sharded through the scan (replicated carries make
    # the partitioner all-gather every step's inputs -- see _ssm_scan)
    h0 = _ctx.constrain(h0, "lru_state")

    def step(h, inp):
        at, bt = inp
        h = at * h + bt
        return h, h

    hT, ys = lax.scan(step, h0,
                      (_ctx.constrain(a.swapaxes(0, 1), "lru_seq"),
                       _ctx.constrain(bx.swapaxes(0, 1), "lru_seq")))
    return ys.swapaxes(0, 1), hT


def rglru_apply(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    state: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Recurrent block: [x->conv->RG-LRU] gated by GeLU(y-branch)."""
    h = cfg.hybrid
    assert h is not None
    cd = dtype_of(cfg.compute_dtype)
    x = x.astype(cd)
    B, S, _ = x.shape
    w = p["w_x"].shape[1]
    wb = w // _LRU_BLOCKS

    xb = jnp.einsum("bsd,dw->bsw", x, p["w_x"].astype(cd))
    yb = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", x, p["w_y"].astype(cd)), approximate=True)

    conv_state = state["conv"] if state is not None else None
    xb, new_conv = causal_conv1d(xb, p["conv_w"], p["conv_b"], conv_state)

    # block-diagonal gates
    xg = xb.reshape(B, S, _LRU_BLOCKS, wb)
    r = jax.nn.sigmoid(jnp.einsum(
        "bshw,hwe->bshe", xg.astype(jnp.float32), p["gate_a"].astype(jnp.float32)
    ).reshape(B, S, w))
    i = jax.nn.sigmoid(jnp.einsum(
        "bshw,hwe->bshe", xg.astype(jnp.float32), p["gate_x"].astype(jnp.float32)
    ).reshape(B, S, w))

    log_a = -_LRU_C * jax.nn.softplus(p["lambda"].astype(jnp.float32)) * r
    a = jnp.exp(log_a)
    gated = i * xb.astype(jnp.float32)
    bx = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-8)) * gated

    h0 = state["lru"] if state is not None else jnp.zeros((B, w), jnp.float32)
    ys, hT = _lru_scan(a, bx, h0)

    out = (ys.astype(cd) * yb)
    out = jnp.einsum("bsw,wd->bsd", out, p["w_out"].astype(cd))
    new_state = {"conv": new_conv, "lru": hT} if state is not None else None
    return out, new_state


# --------------------------------------------------------------------------- #
# Mamba-1 block (falcon-mamba; Jamba's adds learned RMSNorms on dt, B and C)
# --------------------------------------------------------------------------- #


def _dbc_norm(cfg: ModelConfig) -> bool:
    """A hybrid's Mamba blocks carry the learned dt/B/C norms."""
    return cfg.hybrid is not None


def mamba_init(key, cfg: ModelConfig) -> Tuple[Params, Axes]:
    s = cfg.ssm
    assert s is not None
    dt = dtype_of(cfg.param_dtype)
    d = cfg.d_model
    d_in = s.expand * d
    dt_rank = s.dt_rank or -(-d // 16)
    n = s.state_dim
    ks = jax.random.split(key, 7)
    p: Params = {
        "w_in": _init_dense(ks[0], (d, 2 * d_in), dt),
        "conv_w": _init_dense(ks[1], (s.conv_width, d_in), dt, scale=0.5),
        "conv_b": jnp.zeros((d_in,), dt),
        "w_xdbc": _init_dense(ks[2], (d_in, dt_rank + 2 * n), dt),
        "w_dt": _init_dense(ks[3], (dt_rank, d_in), dt),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.clip(jax.random.uniform(ks[4], (d_in,)) * 0.1 + 0.001, 1e-4)
        )).astype(dt),
        "A_log": jnp.log(jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32), (d_in, 1))
                         ).astype(dt),
        "D": jnp.ones((d_in,), dt),
        "w_out": _init_dense(ks[5], (d_in, d), dt),
    }
    a: Axes = {
        "w_in": ("embed", "mlp"),
        "conv_w": ("conv", "mlp"),
        "conv_b": ("mlp",),
        "w_xdbc": ("mlp", None),
        "w_dt": (None, "mlp"),
        "dt_bias": ("mlp",),
        "A_log": ("mlp", "state"),
        "D": ("mlp",),
        "w_out": ("mlp", "embed"),
    }
    if _dbc_norm(cfg):
        p["dt_norm"] = jnp.ones((dt_rank,), dt)
        p["b_norm"] = jnp.ones((n,), dt)
        p["c_norm"] = jnp.ones((n,), dt)
        a["dt_norm"] = (None,)
        a["b_norm"] = ("state",)
        a["c_norm"] = ("state",)
    return p, a


def _ssm_scan(
    xi: jax.Array,        # (B, S, Din)  post-conv/silu activations
    dt_in: jax.Array,     # (B, S, R)    low-rank dt projection input
    Bm: jax.Array,        # (B, S, N)
    Cm: jax.Array,        # (B, S, N)
    w_dt: jax.Array,      # (R, Din)
    dt_bias: jax.Array,   # (Din,)
    A: jax.Array,         # (Din, N), negative
    h0: jax.Array,        # (B, Din, N)
    chunk: int = 256,
) -> Tuple[jax.Array, jax.Array]:
    """Selective-scan core -> (y: (B,S,Din), hT).

    The (B,S,Din,N) discretized dA/dBx tensors are NEVER materialized for the
    full sequence: each chunk computes its own slice inside a rematerialized
    scan body, so live memory is one chunk's worth (the same blocking the
    Pallas kernel uses on TPU)."""
    B, S, Din = xi.shape
    N = A.shape[1]
    if S % chunk != 0:
        chunk = S  # fall back to single chunk for odd sizes (decode, tests)
    n_chunks = S // chunk

    def chunk_step(h, inp):
        xi_c, dtin_c, B_c, C_c = inp  # leading dim = chunk, batch second
        dt_c = jax.nn.softplus(
            jnp.einsum("tbr,rd->tbd", dtin_c.astype(jnp.float32),
                       w_dt.astype(jnp.float32))
            + dt_bias.astype(jnp.float32))           # (chunk, B, Din)
        dA_c = jnp.exp(dt_c[..., None] * A[None, None])  # (chunk,B,Din,N)
        dBx_c = (dt_c * xi_c.astype(jnp.float32))[..., None] \
            * B_c.astype(jnp.float32)[:, :, None, :]

        def step(hh, t):
            dA_t, dBx_t, C_t = t
            hh = dA_t * hh + dBx_t
            y_t = jnp.einsum("bdn,bn->bd", hh, C_t)
            return hh, y_t

        h, ys = lax.scan(step, h, (dA_c, dBx_c, C_c.astype(jnp.float32)))
        return h, ys

    from repro.distributed import ctx as _ctx

    to_chunks = lambda x: x.swapaxes(0, 1).reshape(
        n_chunks, chunk, B, *x.shape[2:])
    # Shard the channel dim of the recurrence across the model axis: the
    # scan carry h0 defaults to replicated, which otherwise makes the
    # partitioner all-gather every chunk's (chunk, B, Din, N) inputs.
    h0 = _ctx.constrain(h0, "ssm_state")
    xs = (_ctx.constrain(to_chunks(xi), "ssm_chunks_d"),
          to_chunks(dt_in), to_chunks(Bm), to_chunks(Cm))
    hT, ys = lax.scan(jax.checkpoint(chunk_step), h0, xs)
    y = ys.reshape(S, B, Din).swapaxes(0, 1)
    return y, hT


def mamba_apply(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    state: Optional[Dict[str, jax.Array]] = None,
    scan_chunk: int = 256,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    s = cfg.ssm
    assert s is not None
    cd = dtype_of(cfg.compute_dtype)
    x = x.astype(cd)
    B, S, _ = x.shape
    d_in = p["conv_b"].shape[0]
    n = s.state_dim
    dt_rank = p["w_dt"].shape[0]

    xz = jnp.einsum("bsd,de->bse", x, p["w_in"].astype(cd))
    xi, z = jnp.split(xz, 2, axis=-1)

    conv_state = state["conv"] if state is not None else None
    xi, new_conv = causal_conv1d(xi, p["conv_w"], p["conv_b"], conv_state)
    xi = jax.nn.silu(xi)

    dbc = jnp.einsum("bse,en->bsn", xi, p["w_xdbc"].astype(cd))
    dt_in, Bm, Cm = jnp.split(dbc, [dt_rank, dt_rank + n], axis=-1)
    if _dbc_norm(cfg):
        dt_in = rms_head_norm(p["dt_norm"], dt_in, cfg.norm_eps)
        Bm = rms_head_norm(p["b_norm"], Bm, cfg.norm_eps)
        Cm = rms_head_norm(p["c_norm"], Cm, cfg.norm_eps)

    A = -jnp.exp(p["A_log"].astype(jnp.float32))          # (Din, N)
    h0 = state["ssm"] if state is not None else jnp.zeros((B, d_in, n), jnp.float32)
    y, hT = _ssm_scan(xi, dt_in, Bm, Cm, p["w_dt"], p["dt_bias"], A, h0,
                      chunk=scan_chunk)
    y = y + p["D"].astype(jnp.float32) * xi.astype(jnp.float32)
    y = y.astype(cd) * jax.nn.silu(z)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"].astype(cd))
    new_state = {"conv": new_conv, "ssm": hT} if state is not None else None
    return out, new_state
