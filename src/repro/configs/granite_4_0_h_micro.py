"""granite-4.0-h-micro [hybrid] — Mamba-2 and GQA attention 9:1, dense SwiGLU.

40L d_model=2048 32H (GQA kv=8, head 64, no positional encoding) d_ff=8192
vocab=100352, tied embeddings (ibm-granite/granite-4.0-h-micro config.json).
``layer_types`` puts attention at 5, 15, 25 and 35: a period of 10 layers,
five Mamba-2 mixers, one attention, four Mamba-2.  Mamba-2: 64 heads of 64
(expand 2), d_state 128, one group, conv 4, SSD chunk 256.  muP-style
multipliers: embedding x12, attention scale 1/64, residual x0.22, logits /8;
RMSNorm (scale w) with eps 1e-5.
"""

from repro.configs.base import LayerSpec, ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    d_ff=8192,
    vocab_size=100352,
    head_dim=64,
    pattern=tuple(LayerSpec(kind="attn" if p == 5 else "mamba2") for p in range(10)),
    rope="none",
    ssm_state_dim=128,
    ssm_expand=2,
    ssm_conv_width=4,
    ssm_heads=64,
    ssm_head_dim=64,
    ssm_groups=1,
    ssm_chunk=256,
    norm="rmsnorm_plain",
    norm_eps=1e-5,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_divisor=8.0,
    attention_scale=0.015625,
    act="swiglu",
    tie_embeddings=True,
    notes="hybrid Mamba-2 (SSD) + GQA; attention KV cache at 1/10 of layers",
)
