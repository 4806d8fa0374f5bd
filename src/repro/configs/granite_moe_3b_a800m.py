"""Granite-3.0-3B-A800M — MoE 40 experts top-8, dropless
[hf:ibm-granite/granite-3.0-3b-a800m-base].  Granite's scalars and
``rms_norm_eps`` as for the 1B-A400M (its config.json's values; no copy of
either file is here to check them against); ``output_router_logits`` is
false, so the training loss has no load-balancing term."""
from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="granite-moe-3b-a800m", family="moe",
    num_layers=32, d_model=1536, num_heads=24, num_kv_heads=8,
    d_ff=512, vocab_size=49155,
    num_experts=40, top_k=8,
    rope_theta=1e4, mlp="swiglu", tie_embeddings=True, norm_eps=1e-6,
    embedding_multiplier=12.0, attention_multiplier=0.015625,
    residual_multiplier=0.22, logits_scaling=6.0,
)
