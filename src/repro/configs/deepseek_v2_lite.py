"""DeepSeek-V2-Lite [arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite].

27L d_model=2048 16H MLA (kv_lora=512, no q LoRA: a direct Q projection,
decoupled rope 64, nope head 128, v head 128), first layer dense
(d_ff=10944), then 26 MoE layers of 64 routed experts (width 1408) top-6
with softmax scoring and unnormalised top-k weights, 2 shared experts;
YaRN rope scaling (factor 40 over 4096 original positions, mscale =
mscale_all_dim = 0.707), rope_theta 10000, vocab=102400, untied head.

``CONFIG`` holds every published width and all 64 experts; an
expert-parallel share of it is the same config with ``num_experts``
(held) and ``first_expert`` set.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,           # MLA: heads share the compressed KV
    head_dim=128,              # qk_nope_head_dim
    d_ff=10944,                # the dense first layer's width
    d_ff_expert=1408,
    vocab_size=102400,
    num_experts=64,
    num_shared_experts=2,
    experts_per_token=6,
    norm_topk_prob=False,
    first_k_dense=1,
    use_mla=True,
    kv_lora_rank=512,
    q_lora_rank=0,
    rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10000.0,
    yarn_factor=40.0,
    yarn_original_max_position_embeddings=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    norm_eps=1e-6,
)

REDUCED = CONFIG.reduced()
