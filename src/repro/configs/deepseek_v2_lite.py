"""deepseek-v2-lite [moe] — multi-head latent attention (kv_lora_rank 512, no
q-LoRA, nope 128 + rope 64 per head, v 128) with YaRN rope (x40 over 4096);
2 shared + 64 routed top-6 experts at 1408, the top-6 weights not
renormalized; first layer dense (d_ff 10944). [arXiv:2405.04434; hf
deepseek-ai/DeepSeek-V2-Lite]"""
from repro.configs.base import LayerGroup, MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=1408,
    vocab=102400,
    dense_d_ff=10944,
    moe=MoEConfig(num_experts=64, top_k=6, num_shared_experts=2, d_expert=1408,
                  norm_topk=False),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128),
    rope_yarn=YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                         beta_fast=32.0, beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    # layer 0 dense MLP, remaining 26 MoE
    layer_groups=(LayerGroup("A", 1, moe_mask="0"), LayerGroup("A", 26, moe_mask="1")),
    source="arXiv:2405.04434; hf",
)
