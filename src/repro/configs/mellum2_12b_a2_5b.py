"""mellum2-12b-a2.5b [moe] — 28L d_model=2304 32H (GQA kv=4, head_dim 128)
vocab=98304, every MLP sparse: 64 SwiGLU experts of width 896, top-8,
renormalised.  Three sliding-window layers (1024) then one full layer,
repeated; RoPE theta 500,000 on both, YaRN (factor 16 over 8,192
positions, beta 32/1, attention factor 1.2773) on the full layers only.
RMSNorm eps 1e-6, untied embeddings, no attention bias.
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json]

The model card's multi-token-prediction head is not in ``config.json``
and is left out: the objective is next-token.
"""

from repro.configs.base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    num_layers=28,
    d_model=2304,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=896,                  # moe_intermediate_size (every layer sparse)
    vocab_size=98304,
    mlp_type="swiglu",
    sliding_window=1024,
    global_every=4,            # layer_types: S S S F, repeated
    rope_theta=500_000.0,
    rope_yarn_factor=16.0,
    rope_yarn_original_max=8192,
    rope_yarn_beta_fast=32.0,
    rope_yarn_beta_slow=1.0,
    rope_yarn_attention_factor=1.2772588722239782,
    num_experts=64,
    num_experts_per_tok=8,
    tie_embeddings=False,
    source="https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
           "blob/main/config.json",
))
