"""granite-moe-3b-a800m [moe] — 40 experts top-8.

[hf:ibm-granite/granite-3.0-3b-a800m-base; hf] 32 layers, d 1536, 24q/8kv
heads of 64, 40 routed experts of width 512 (gated SiLU), 8 per token,
vocab 49155, tied embeddings.
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="granite-moe-3b-a800m",
    family="moe",
    num_layers=32,
    d_model=1536,
    num_heads=24,
    num_kv_heads=8,
    head_dim=64,
    d_ff=512,  # per-expert hidden size
    vocab_size=49155,
    num_experts=40,
    experts_per_token=8,
    activation="silu",
    gated_mlp=True,
    tie_embeddings=True,
    source="hf:ibm-granite/granite-3.0-3b-a800m-base",
)
