"""DeepSeek-V2-Lite (arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite
``config.json``): every layer is multi-head latent attention (16 heads,
``kv_lora_rank`` 512, no query compression, 128 + 64 query/key dims, 128
value dims) with YaRN positions (factor 40 over 4,096); layer 0 has a dense
SwiGLU MLP of 10,944, layers 1..26 have 64 routed experts of 1,408 (top-6,
softmax scores, no renormalisation, scale 1) and 2 shared ones."""
from repro.configs.base import (LayerSpec, MLAConfig, ModelConfig, MoEConfig,
                                RopeScaling)

DEEPSEEK_V2_LITE = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=10944, vocab_size=102400, tie_embeddings=False,
    prefix=(LayerSpec("mla", "dense"),),
    pattern=(LayerSpec("mla", "moe"),),
    moe=MoEConfig(n_experts=64, top_k=6, n_shared=2, d_expert=1408,
                  renorm_topk=False),
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    rope_theta=10000.0,
    rope_scaling=RopeScaling(factor=40.0, original_max_position=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
)

CONFIG = DEEPSEEK_V2_LITE
