"""granite-4.0-h-micro [seq_hybrid]: 40L d2048, v100352 tied, NoPE.

Each layer holds one mixer, then a SwiGLU MLP (8192): Mamba-2 in 36 layers
(64 heads x 64, d_state 128, 1 group, conv 4 with bias, chunk 256) and GQA
attention (32 heads, 8 KV heads of 64, no position embedding, score scale
0.015625) at layers 5, 15, 25 and 35.  muP multipliers: embeddings x12,
both residual branches x0.22, logits / 8.
[hf:ibm-granite/granite-4.0-h-micro config.json, model_type granitemoehybrid]
"""
from repro.configs.base import ArchConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ArchConfig(
    name="granite-4.0-h-micro", family="seq_hybrid",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8,
    d_ff=8192, vocab=100352, head_dim=64, tie_embeddings=True,
    rope_theta=10000.0, norm_eps=1e-5,
    ssm_state=128, ssm_head_dim=64, ssm_expand=2, ssm_groups=1,
    conv_kernel=4, ssm_chunk=256, conv_bias=True,
    layer_types=_PERIOD * 4,
    attention_multiplier=0.015625, embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=8.0,
)
