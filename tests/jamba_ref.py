"""A plain float32 forward of AI21-Jamba2-Mini, written from the published
layer equations (transformers' ``modeling_jamba``), as the reference the
program's hybrid stack is compared with.

Straight ``jax.numpy`` in float32 under ``jax.default_matmul_precision(
"highest")``: no cache, no kernels, no scan over layers, nothing imported
from ``repro``.  Each block is pre-norm residual, ``x += mixer(norm(x))``
then ``x += ffn(norm(x))``, and a final norm precedes the untied head:

- Mamba mixer: ``(x, z) = in_proj(h)``; ``x = SiLU(causal_conv4(x) + b)``;
  ``(dt, B, C) = x_proj(x)``, each through a learned RMSNorm;
  ``delta = softplus(dt W_dt + b_dt)``; ``h_t = exp(delta_t A) h_{t-1} +
  delta_t B_t x_t`` with ``A = -exp(A_log)``; ``y_t = C_t . h_t + D x_t``;
  ``out_proj(y * SiLU(z))``.
- Attention: causal softmax GQA, no positional encoding.
- MoE: softmax over the router logits, the top-k experts weighted by their
  probabilities as they are (not renormalised); SwiGLU experts.  Every
  expert is computed on every token and weighted by zero where it was not
  chosen, which is the same sum.
- Dense FFN: SwiGLU.

Departures from the published model: weights are random from a seed; the
router's load-balance loss is left out (it does not change the logits);
the depth is whatever the weights hold.

Weights come as ``{"embed", "unembed", "final_norm", "layers"}``, one dict
per block in order (``from_program`` and ``program_block`` unpack the
program's stacked tree).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6
F32 = jnp.float32


def rms_norm(x, w, eps=EPS):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def attention(w, h):
    """Causal GQA over (S, D); w: wq (D, H, hd), wk/wv (D, K, hd), wo (H, hd, D)."""
    S = h.shape[0]
    H, K, hd = w["wq"].shape[1], w["wk"].shape[1], w["wq"].shape[2]
    q = jnp.einsum("sd,dhk->hsk", h, w["wq"])
    k = jnp.einsum("sd,dhk->hsk", h, w["wk"])
    v = jnp.einsum("sd,dhk->hsk", h, w["wv"])
    causal = jnp.tril(jnp.ones((S, S), bool))
    heads = []
    for j in range(H):               # query head j reads KV head j // (H/K)
        kv = j // (H // K)
        s = q[j] @ k[kv].T / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        heads.append(p @ v[kv])
    return jnp.einsum("hsk,hkd->sd", jnp.stack(heads), w["wo"])


def mamba(w, h):
    """Mamba-1 mixer over (S, D)."""
    d_in = w["D"].shape[0]
    r, n = w["dt_norm"].shape[0], w["b_norm"].shape[0]
    xz = h @ w["w_in"]
    x, z = xz[:, :d_in], xz[:, d_in:]
    width = w["conv_w"].shape[0]
    xp = jnp.concatenate([jnp.zeros((width - 1, d_in), F32), x])
    x = sum(xp[i:i + h.shape[0]] * w["conv_w"][i] for i in range(width))
    x = jax.nn.silu(x + w["conv_b"])
    dbc = x @ w["w_xdbc"]
    dt = rms_norm(dbc[:, :r], w["dt_norm"])
    B = rms_norm(dbc[:, r:r + n], w["b_norm"])
    C = rms_norm(dbc[:, r + n:], w["c_norm"])
    delta = jax.nn.softplus(dt @ w["w_dt"] + w["dt_bias"])      # (S, d_in)
    A = -jnp.exp(w["A_log"])                                     # (d_in, n)

    def step(state, t):
        d_t, x_t, B_t, C_t = t
        state = jnp.exp(d_t[:, None] * A) * state \
            + (d_t * x_t)[:, None] * B_t[None, :]
        return state, state @ C_t

    _, y = jax.lax.scan(step, jnp.zeros((d_in, n), F32), (delta, x, B, C))
    y = y + w["D"] * x
    return (y * jax.nn.silu(z)) @ w["w_out"]


def swiglu(w, h):
    return (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def moe(w, h, top_k):
    """Top-k of a softmax router, gates not renormalised."""
    probs = jax.nn.softmax(h @ w["router"], axis=-1)            # (S, E)
    kth = jnp.sort(probs, axis=-1)[:, -top_k][:, None]
    gates = jnp.where(probs >= kth, probs, 0.0)
    up = jax.nn.silu(jnp.einsum("sd,edf->sef", h, w["w_gate"])) \
        * jnp.einsum("sd,edf->sef", h, w["w_up"])
    return jnp.einsum("sef,efd,se->sd", up, w["w_down"], gates)


def _mixed(lw, kind, x):
    """The block's residual after its mixer, and the FFN's normed input."""
    h = rms_norm(x, lw["ln1"])
    x = x + (attention(lw["mixer"], h) if kind.startswith("att")
             else mamba(lw["mixer"], h))
    return x, rms_norm(x, lw["ln2"])


def block(lw, kind, x, top_k=2):
    """One pre-norm residual block over (S, D); ``kind`` as in the
    program's pattern ("mamba", "att", with "+moe" for an MoE FFN)."""
    with jax.default_matmul_precision("highest"):
        x, h = _mixed(lw, kind, x)
        return x + (moe(lw["ffn"], h, top_k) if kind.endswith("+moe")
                    else swiglu(lw["ffn"], h))


def router_margin(lw, kind, x, top_k=2):
    """(S,) gap between the k-th and the (k+1)-th router probability of an
    MoE block, at each position of its input ``x``: how near the block's
    choice of experts is to a tie."""
    with jax.default_matmul_precision("highest"):
        _, h = _mixed(lw, kind, x)
        p = jnp.sort(jax.nn.softmax(h @ lw["ffn"]["router"], axis=-1), -1)
        return p[:, -top_k] - p[:, -top_k - 1]


def embed(w, tokens):
    return w["embed"][tokens].astype(F32)


def head(w, x):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, w["final_norm"]) @ w["unembed"]


def forward(w, kinds, tokens, top_k=2):
    """Logits (S, V) of one sequence of token ids (S,)."""
    x = embed(w, tokens)
    for lw, kind in zip(w["layers"], kinds):
        x = block(lw, kind, x, top_k)
    return head(w, x)


def program_runs(pattern):
    """(key, kind, length) of each run of equal consecutive blocks in the
    program's period, under the key the program holds it by: the kind when
    the kind forms one run, else ``<kind>.<r>`` for its r-th run."""
    runs = []
    for kind in pattern:
        if runs and runs[-1][1] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, kind, 1])
    n_runs = {k: sum(r[1] == k for r in runs) for k in pattern}
    seen = {}
    for run in runs:
        if n_runs[run[1]] > 1:
            run[0] = f"{run[1]}.{seen.get(run[1], 0)}"
        seen[run[1]] = seen.get(run[1], 0) + 1
    return tuple(map(tuple, runs))


def program_block(run, kind, n, g, j):
    """Block ``j`` of a run of ``n`` blocks of ``kind`` in period ``g``, where
    ``run`` is ``params["groups"][key]`` (periods stacked; a run's blocks
    stacked when it has more than one), as this module's block dict in
    float32."""
    bp = jax.tree.map(lambda t: (t[g] if n == 1 else t[g][j]).astype(F32),
                      run)
    return {"ln1": bp["ln1"]["scale"], "ln2": bp["ln2"]["scale"],
            "mixer": bp["attn"] if kind.startswith("att") else bp["mamba"],
            "ffn": bp["moe"] if kind.endswith("+moe") else bp["mlp"]}


def program_outer(params):
    """The program's embedding, head and final norm, in float32."""
    return {"embed": params["embed"]["tok"].astype(F32),
            "unembed": params["embed"]["unembed"].astype(F32),
            "final_norm": params["final_norm"]["scale"].astype(F32)}


def from_program(params, pattern):
    """The program's parameter tree as this module's weights.  Returns
    ``(weights, kinds)``."""
    groups = params["groups"]
    n_groups = jax.tree.leaves(groups)[0].shape[0]
    layers, kinds = [], []
    for g in range(n_groups):
        for key, kind, n in program_runs(pattern):
            for j in range(n):
                layers.append(program_block(groups[key], kind, n, g, j))
                kinds.append(kind)
    return dict(program_outer(params), layers=layers), tuple(kinds)


def bf16_ssm_scan(xi, dt_in, Bm, Cm, w_dt, dt_bias, A, h0, chunk=256):
    """The control: the program's selective scan (the signature of
    ``repro.models.layers._ssm_scan``) computed in bfloat16, one precision
    below the float32 the configuration states for it.  A comparison that
    this passes is too loose."""
    bf = jnp.bfloat16
    lo = lambda t: t.astype(bf)
    delta = jax.nn.softplus(lo(dt_in) @ lo(w_dt) + lo(dt_bias))

    def step(state, t):
        d_t, x_t, B_t, C_t = t
        state = jnp.exp(d_t[..., None] * lo(A)) * state \
            + (d_t * x_t)[..., None] * B_t[:, None, :]
        return state, jnp.einsum("bdn,bn->bd", state, C_t)

    seq = lambda t: lo(t).swapaxes(0, 1)
    hT, ys = jax.lax.scan(step, lo(h0), (seq(delta), seq(xi), seq(Bm),
                                          seq(Cm)))
    return ys.swapaxes(0, 1).astype(F32), hT.astype(F32)
