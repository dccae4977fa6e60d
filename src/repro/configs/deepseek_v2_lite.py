"""DeepSeek-V2-Lite — multi-head latent attention (no query compression,
YaRN rotary) and fine-grained MoE, 2 shared + 64 routed experts top-6
without top-k renormalisation, layer 0 dense
[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite config.json]."""
from . import ArchConfig, MLAConfig, MoEConfig, YarnScaling

CONFIG = ArchConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=1408, vocab_size=102400,
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                  first_k_dense=1, d_ff_dense=10944,
                  norm_topk_prob=False, routed_scaling_factor=1.0),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
    rope="rope", rope_theta=10000.0,
    rope_scaling=YarnScaling(factor=40.0, original_max_position=4096, beta_fast=32.0,
                             beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    embed_scale=False, norm="rmsnorm", act="silu", glu=True,
    notes="The published q_pe/k_pe columns are interleaved pairs; the program "
          "rotates halves (a fixed permutation of those columns).",
)

SMOKE = ArchConfig(
    name="deepseek-v2-lite-smoke", family="moe",
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=32, vocab_size=64,
    moe=MoEConfig(n_experts=8, top_k=2, d_expert=32, n_shared=2,
                  first_k_dense=1, d_ff_dense=96, norm_topk_prob=False),
    mla=MLAConfig(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=12),
    rope="rope", rope_theta=10000.0,
    rope_scaling=YarnScaling(factor=4.0, original_max_position=32, beta_fast=32.0,
                             beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    embed_scale=False, norm="rmsnorm", act="silu", glu=True,
)
