"""AI21-Jamba2-Mini [hybrid]: 32L d_model=4096, 32 query heads over 8 KV
heads (head 128), vocab 65536, untied embeddings, RMSNorm eps 1e-6; 52 B
parameters, 12 B active.  [hf:ai21labs/AI21-Jamba2-Mini config.json]

One period of 8 blocks holds every kind of block in its published ratio:
the mixer is softmax GQA attention without positional encoding where
``i % 8 == 4`` (``attn_layer_period`` 8, ``attn_layer_offset`` 4) and
Mamba-1 elsewhere (``d_state`` 16, ``d_conv`` 4 with a bias, ``expand`` 2,
``dt_rank`` 256, learned RMSNorms on dt, B and C, no projection biases);
the FFN is a 16-expert top-2 SwiGLU MoE of width 14336 in odd blocks
(``expert_layer_period`` 2, offset 1), whose two gates are the softmax
probabilities as they are (not renormalised), and a dense SwiGLU of the
same width in even blocks.

Sizes the config does not give, set here: the width of one expert is
``intermediate_size`` (the catalog reads it so); the router's load-balance
loss weight is 0.001 (``router_aux_loss_coef`` of transformers'
``JambaConfig``).

On the chip (``scripts/jamba_period_check.py``): depth 32 -> 8, one whole
period, at the published widths with all 16 experts and the whole
vocabulary, sharded over one four-chip host (a 1x4 mesh, experts and
heads on its ``model`` axis); the 24 blocks left out would be three more
such periods further down a pipeline.
"""

from repro.models.config import (
    Family,
    HybridConfig,
    ModelConfig,
    MoEConfig,
    SSMConfig,
)


def period(n: int = 8, attn_offset: int = 4, expert_offset: int = 1):
    """Jamba's block pattern: attention at ``attn_offset``, Mamba
    elsewhere; an MoE FFN in every other block from ``expert_offset``."""
    return tuple(("att" if i == attn_offset else "mamba")
                 + ("+moe" if i % 2 == expert_offset else "")
                 for i in range(n))


CONFIG = ModelConfig(
    name="jamba2-mini",
    family=Family.HYBRID,
    n_layers=32,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_ff=14336,
    vocab_size=65536,
    rope_style="none",
    norm_eps=1e-6,
    mlp="swiglu",
    moe=MoEConfig(n_experts=16, top_k=2, d_ff_expert=14336,
                  aux_loss_weight=0.001, renormalize=False),
    ssm=SSMConfig(state_dim=16, conv_width=4, expand=2, dt_rank=256),
    hybrid=HybridConfig(pattern=period(), conv_width=4),
    logits_chunk=1024,
    attn_q_chunk=1024,
)

SMOKE = CONFIG.replace(
    name="jamba2-mini-smoke", n_layers=8, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=96, vocab_size=256, remat="none", logits_chunk=0,
    attn_q_chunk=0,
    moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=96,
                  aux_loss_weight=0.001, renormalize=False),
    ssm=SSMConfig(state_dim=4, conv_width=4, expand=2, dt_rank=8),
)
