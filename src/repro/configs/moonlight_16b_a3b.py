"""moonlight-16b-a3b [moe] — 27L d_model=2048 16H, the DeepSeek-V3 block
(``model_type: deepseek_v3``) vocab=163840.

* Latent attention (MLA) with no query low-rank: ``q_proj`` 2048 -> 16 x
  (128 nope + 64 rope); ``kv_a_proj_with_mqa`` 2048 -> 512 latent + 64,
  the latent RMS-normed (``kv_a_layernorm``), the 64 one RoPE key every
  head shares; ``kv_b_proj`` 512 -> 16 x (128 k_nope + 128 v);
  ``o_proj`` 16 x 128 -> 2048.  Softmax scale 192**-0.5.  RoPE theta
  50,000 on adjacent pairs (``rope_interleave``), no ``rope_scaling``.
* Layer 0 dense (``first_k_dense_replace: 1``): SwiGLU of width 11,264.
* Layers 1-26: 64 routed SwiGLU experts of width 1,408, top-6, plus 2
  shared experts (one SwiGLU of width 2,816).  Sigmoid scores
  (``scoring_func: sigmoid``, ``topk_method: noaux_tc``): experts chosen
  on score + ``e_score_correction_bias``, weighted by the unbiased scores
  of the chosen, renormalised (``norm_topk_prob``) and times
  ``routed_scaling_factor`` 2.446; ``n_group`` = ``topk_group`` = 1.
* RMSNorm eps 1e-5, untied head, no MTP layer.
[hf:moonshotai/Moonlight-16B-A3B config.json]
"""

from repro.configs.base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,              # qk_nope_head_dim + qk_rope_head_dim
    d_ff=1408,                 # moe_intermediate_size
    vocab_size=163840,
    mlp_type="swiglu",
    rope_theta=50_000.0,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_interleave=True,
    num_experts=64,
    num_experts_per_tok=6,
    n_shared_experts=2,
    first_k_dense_replace=1,
    dense_d_ff=11264,          # intermediate_size
    router_scoring="sigmoid",
    router_bias=True,
    routed_scaling_factor=2.446,
    rms_norm_eps=1e-5,
    tie_embeddings=False,
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/"
           "config.json",
))
