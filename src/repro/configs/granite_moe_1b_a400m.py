"""Granite-3.0-1B-A400M — MoE 32 experts top-8, dropless
[hf:ibm-granite/granite-3.0-1b-a400m-base].  Granite's scalars and
``rms_norm_eps`` as its config.json gives them; ``output_router_logits``
is false there, so the training loss has no load-balancing term."""
from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="granite-moe-1b-a400m", family="moe",
    num_layers=24, d_model=1024, num_heads=16, num_kv_heads=8,
    d_ff=512, vocab_size=49155,
    num_experts=32, top_k=8,
    rope_theta=1e4, mlp="swiglu", tie_embeddings=True, norm_eps=1e-6,
    embedding_multiplier=12.0, attention_multiplier=0.015625,
    residual_multiplier=0.22, logits_scaling=6.0,
)
