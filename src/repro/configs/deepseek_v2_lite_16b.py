"""deepseek-v2-lite-16b [moe]: MLA attention + fine-grained MoE, as the
published config.json (huggingface.co/deepseek-ai/DeepSeek-V2-Lite) states it.

27 layers, d_model 2048, 16 heads, vocab 102400, untied head, RMSNorm eps
1e-6. MLA without query compression (q_lora_rank null): kv_lora_rank 512,
qk_nope 128, decoupled rope dim 64, v_head 128; rope_theta 10000 with YaRN
scaling (factor 40 over 4096 original positions, beta_fast 32, beta_slow 1,
mscale = mscale_all_dim = 0.707, so the softmax scale gains mscale^2).
MoE: the first layer dense (d_ff 10944), then 26 layers of 64 routed experts
(width 1408, top-6 by greedy softmax gating, no renormalisation of the top-6
weights, routed_scaling_factor 1) plus 2 shared experts. "160 routed"
belongs to the full V2; V2-Lite has 64 (see DESIGN.md §3).
[arXiv:2405.04434; hf]
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b", family="moe",
    num_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=10944, vocab_size=102400,
    use_mla=True, kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
    n_experts=64, moe_top_k=6, n_shared_experts=2, expert_d_ff=1408,
    first_dense_layers=1, norm_topk_prob=False, routed_scaling_factor=1.0,
    rope_theta=10000.0, norm_eps=1e-6,
    rope_scaling={"type": "yarn", "factor": 40,
                  "original_max_position_embeddings": 4096,
                  "beta_fast": 32, "beta_slow": 1,
                  "mscale": 0.707, "mscale_all_dim": 0.707},
    activation="silu",
)

SMOKE = CONFIG.replace(
    num_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=128, vocab_size=256,
    kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
    n_experts=8, moe_top_k=2, n_shared_experts=1, expert_d_ff=32,
    dtype="float32",
)
