"""kimi-linear-48b-a3b — Kimi Linear's hybrid block: Kimi Delta Attention
(gated delta-rule linear attention, per-channel decay, short convolutions)
on three layers of four, latent attention without rotation (NoPE) on the
fourth and the last; 256 sigmoid-routed experts (top-8) with one shared
expert after one leading dense layer.
[hf:moonshotai/Kimi-Linear-48B-A3B-Instruct config.json; arXiv:2510.26692 §3]
27L d_model=2304; KDA 32H x 128, conv 4; MLA 32H (nope 128 + 64 unrotated,
v 128, kv latent 512); d_ff(dense)=9216 d_expert=1024 vocab=163840, untied.
"""
from repro.configs.base import (ArchConfig, AttnConfig, KDAConfig, MLAConfig,
                                MoEConfig)

CONFIG = ArchConfig(
    name="kimi-linear-48b-a3b",
    family="kda_mla_moe",
    n_layers=27,
    d_model=2304,
    d_ff=9216,
    vocab_size=163840,
    attn=AttnConfig(n_heads=32, n_kv_heads=32, head_dim=128,
                    rope_theta=10000.0,
                    mla=MLAConfig(kv_lora_rank=512, rope_dim=64,
                                  v_head_dim=128, rope=False)),
    kda=KDAConfig(n_heads=32, head_dim=128, d_conv=4,
                  # published full_attn_layers 4, 8, ..., 24, 27 (1-based)
                  mla_layers=(3, 7, 11, 15, 19, 23, 26)),
    moe=MoEConfig(n_experts=256, top_k=8, d_expert=1024, n_shared_experts=1,
                  d_shared=1024, routed_scale=2.446),
    first_k_dense=1,
    norm_eps=1e-5,
    source="[hf:moonshotai/Kimi-Linear-48B-A3B-Instruct; arXiv:2510.26692]",
)
