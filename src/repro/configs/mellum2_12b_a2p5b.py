"""Mellum2-12B-A2.5B [hf:JetBrains/Mellum2-12B-A2.5B-Instruct] — 64-expert
top-8 MoE on every layer, three sliding-window (1024) layers to one full
layer, YaRN on the full layers."""
from .base import LpsaConfig, ModelConfig, MoeConfig, YarnConfig

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b", family="moe",
    n_layers=28, d_model=2304, n_heads=32, n_kv_heads=4, head_dim=128,
    d_ff=896,  # = expert intermediate dim (every layer's FFN is MoE)
    vocab=98_304,
    layer_pattern=("local", "local", "local", "attn"), window=1024,
    moe=MoeConfig(n_experts=64, top_k=8, d_expert=896, n_shared=0),
    rope_theta=500_000.0,
    rope_yarn=YarnConfig(factor=16.0, original_max=8192, beta_fast=32.0,
                         beta_slow=1.0, attention_factor=1.2772588722239782),
    tie_embeddings=False,
    lpsa=LpsaConfig(sink=0, window=1024, chunk=256),
    lpsa_on_globals=False,
)
