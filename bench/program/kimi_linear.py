"""The program's configuration of Kimi Linear (Kimi-Linear-48B-A3B), built
from the numbers of a configuration file in ``bench/configs/``: the
``kda_mla_moe`` family, KDA on the layers of ``linear_attn_config`` and
latent attention without rotation on its ``full_attn_layers``, holding
``num_experts`` of the router's ``published.num_experts`` experts, those of
expert-parallel rank ``expert_parallel.rank``."""
from repro.configs.base import (ArchConfig, AttnConfig, KDAConfig, MLAConfig,
                                MoEConfig)

# what this program implements of the Kimi Linear configuration space
SUPPORTED = {"q_lora_rank": None, "num_expert_group": 1, "topk_group": 1,
             "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
             "moe_layer_freq": 1, "mla_use_nope": True,
             "num_nextn_predict_layers": 0}


def program_config(conf: dict) -> ArchConfig:
    for key, want in SUPPORTED.items():
        if conf[key] != want:
            raise ValueError(f"{key}={conf[key]!r}: only {want!r} is "
                             "implemented")
    lin = conf["linear_attn_config"]
    n = conf["num_hidden_layers"]
    mla = tuple(i - 1 for i in lin["full_attn_layers"] if i <= n)
    kda = tuple(i - 1 for i in lin["kda_layers"] if i <= n)
    if sorted(mla + kda) != list(range(n)):
        raise ValueError("every layer is either KDA or latent attention")
    held = conf["num_experts"]
    return ArchConfig(
        name=conf["name"], family="kda_mla_moe",
        n_layers=n, d_model=conf["hidden_size"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        attn=AttnConfig(n_heads=conf["num_attention_heads"],
                        n_kv_heads=conf["num_key_value_heads"],
                        head_dim=conf["qk_nope_head_dim"],
                        rope_theta=float(conf["rope_theta"]),
                        mla=MLAConfig(kv_lora_rank=conf["kv_lora_rank"],
                                      rope_dim=conf["qk_rope_head_dim"],
                                      v_head_dim=conf["v_head_dim"],
                                      rope=not conf["mla_use_nope"])),
        kda=KDAConfig(n_heads=lin["num_heads"], head_dim=lin["head_dim"],
                      d_conv=lin["short_conv_kernel_size"], mla_layers=mla),
        moe=MoEConfig(n_experts=conf["published"]["num_experts"],
                      top_k=conf["num_experts_per_token"],
                      d_expert=conf["moe_intermediate_size"],
                      n_shared_experts=conf["num_shared_experts"],
                      d_shared=(conf["num_shared_experts"]
                                * conf["moe_intermediate_size"]),
                      routed_scale=conf["routed_scaling_factor"],
                      n_held=held,
                      held_first=conf["expert_parallel"]["rank"] * held),
        first_k_dense=conf["first_k_dense_replace"],
        tie_embeddings=conf["tie_word_embeddings"],
        norm_eps=conf["rms_norm_eps"], act=conf["hidden_act"],
        source=conf["source"])
