"""granite-4.0-h-small [granite_hybrid] — hf:ibm-granite (32B-A9B).

40 layers in four periods of ``m m m m m A m m m m``: Mamba2 mixers and NoPE
GQA attention, each layer followed by 72 experts top-10 and one shared
SwiGLU expert; Granite's embedding, residual, attention and logit scalars.
"""
import dataclasses
from repro.configs.base import ModelConfig

PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-small", family="granite_hybrid",
    num_layers=40, d_model=4096, num_heads=32, num_kv_heads=8,
    d_ff=768, vocab_size=100352, head_dim=128,
    mlp_activation="swiglu", num_experts=72, experts_per_token=10,
    shared_expert_ff=1536, layer_types=PERIOD * 4,
    ssm_state=128, ssm_head_dim=64, ssm_expand=2, ssm_groups=1,
    ssm_conv_width=4, ssm_chunk=256,
    use_rope=False, embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.0078125, logits_scaling=16.0,
    tie_embeddings=True, scan_layers=False,
)

SMOKE_CONFIG = dataclasses.replace(
    CONFIG, name="granite-4.0-h-small-smoke",
    num_layers=3, layer_types=("mamba", "attention", "mamba"),
    d_model=64, num_heads=4, num_kv_heads=2, head_dim=16, d_ff=32,
    vocab_size=512, num_experts=8, experts_per_token=2, shared_expert_ff=48,
    ssm_state=16, ssm_head_dim=16, ssm_chunk=16, attention_multiplier=0.0625,
)
