"""deepseek-v2-lite: MLA attention, DeepSeekMoE with shared experts.

[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite] 27L d_model=2048 16H,
MLA (kv_lora_rank=512, no q LoRA, qk nope 128 + rope 64, v 128), layer 0
dense (d_ff=10944), 26 MoE layers of 64 routed experts (d_ff_expert=1408,
top-6 softmax, weights not renormalised) plus 2 shared, sequence-wise
balance loss (alpha 0.001), vocab=102400.  Dropless routing.  RoPE theta
1e4 without the YaRN context extension of the released checkpoint.
"""
from repro.configs.base import AttnConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="deepseek_v2_lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    d_ff=10944,
    vocab=102400,
    attn=AttnConfig(n_heads=16, n_kv_heads=16, head_dim=192,
                    rope_theta=10000.0, kv_lora_rank=512,
                    qk_nope_head_dim=128, qk_rope_head_dim=64,
                    v_head_dim=128),
    moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408,
                  capacity_factor=None, n_shared=2, norm_topk=False,
                  seq_aux=True, aux_alpha=0.001),
    first_k_dense=1,
    tie_embeddings=False,
    supports_long_context=False,  # full attention
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite",
)
