"""AI21-Jamba2-3B [hf ai21labs/AI21-Jamba2-3B config.json; layer
equations arXiv:2403.19887]: 28L d2560, Mamba-1 mixers (d_state 16,
d_conv 4 with bias, expand 2, dt_rank 160, RMSNorm on dt/B/C) with
attention at layers 7 and 21 (period 14, offset 7): 20H, one KV head,
head_dim 128, no positional encoding; a dense SwiGLU MLP (d_ff 8192) on
every layer (``num_experts: 1``: no MoE); vocab 65536, tied embeddings,
RMSNorm eps 1e-6."""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="jamba2-3b", family="hybrid",
    num_layers=28, d_model=2560, num_heads=20, num_kv_heads=1,
    d_ff=8192, vocab_size=65536, head_dim=128,
    norm_eps=1e-6, tie_embeddings=True, num_experts=0,
    ssm_kind="mamba", attn_every=14, attn_offset=7,
    ssm_state=16, ssm_conv=4, ssm_expand=2,
)


def reduced() -> ModelConfig:
    """Two periods of 4 layers, attention at sub-layer 2 of each, for
    CPU tests.  ``d_model`` 64 keeps the Mamba dt_rank (d/16) at 4; the
    vocabulary stays published, so that a scored window's tokens have
    the published resolution (65536 bins)."""
    import dataclasses
    return dataclasses.replace(
        CONFIG, num_layers=8, d_model=64, num_heads=4, num_kv_heads=1,
        head_dim=16, d_ff=128, attn_every=4, attn_offset=2, ssm_state=8)
