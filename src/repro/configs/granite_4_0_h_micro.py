"""granite-4.0-h-micro: 40L d_model=2048, a Mamba-2/attention hybrid whose
layer types repeat a period of ten (five Mamba-2, one attention, four
Mamba-2): 36 Mamba-2 layers (64 heads of 64, state 128, one group, conv 4,
chunk 256) and 4 GQA layers (32 heads over 8 KV heads of 64, no positions),
a gated SiLU MLP of 8192 in every layer, vocab 100352, tied
[hf:ibm-granite/granite-4.0-h-micro config.json]."""

import dataclasses

from repro.models.config import ATTN, MAMBA2, MLP, ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    vocab=100352,
    d_model=2048,
    n_layers=40,
    d_ff=8192,
    n_heads=32,
    n_kv_heads=8,
    layer_pattern=(MAMBA2,) * 5 + (ATTN,) + (MAMBA2,) * 4,
    ffn_pattern=(MLP,),
    partial_rotary=0.0,              # position_embedding_type: nope
    ssm_heads=64,
    ssm_head_dim=64,
    ssm_groups=1,
    ssm_state=128,
    ssm_conv=4,
    ssm_chunk=256,
    norm_eps=1e-5,
    tie_embeddings=True,
)


def reduced() -> ModelConfig:
    """Two periods at small widths; a chunk of 8, so a 16-token prompt
    crosses a chunk boundary."""
    return dataclasses.replace(
        CONFIG, vocab=512, d_model=64, n_layers=20, d_ff=128, n_heads=4,
        n_kv_heads=2, ssm_heads=4, ssm_head_dim=16, ssm_state=16,
        ssm_chunk=8)
