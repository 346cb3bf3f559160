"""moonlight-16b-a3b — DeepSeek-V3 block: latent attention (MLA), 64
sigmoid-routed experts (top-6) with 2 shared experts, one leading dense
layer.  [hf:moonshotai/Moonlight-16B-A3B config.json; arXiv:2412.19437 §2.1]
27L d_model=2048 16H (nope 128 + rope 64, v 128, kv latent 512)
d_ff(dense)=11264 d_expert=1408 vocab=163840, untied.
"""
from repro.configs.base import ArchConfig, AttnConfig, MLAConfig, MoEConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    family="mla_moe",
    n_layers=27,
    d_model=2048,
    d_ff=11264,
    vocab_size=163840,
    attn=AttnConfig(n_heads=16, n_kv_heads=16, head_dim=128,
                    rope_theta=50000.0,
                    mla=MLAConfig(kv_lora_rank=512, rope_dim=64,
                                  v_head_dim=128)),
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared_experts=2,
                  d_shared=2 * 1408, routed_scale=2.446),
    first_k_dense=1,
    norm_eps=1e-5,
    source="[hf:moonshotai/Moonlight-16B-A3B; arXiv:2412.19437]",
)
