"""The program's configuration of a DeepSeek-V3 decoder (Moonlight family),
built from the numbers of a configuration file in ``bench/configs/``: the
``mla_moe`` family, holding ``n_routed_experts`` of the router's
``published.n_routed_experts`` experts, those of expert-parallel rank
``expert_parallel.rank``."""
from repro.configs.base import ArchConfig, AttnConfig, MLAConfig, MoEConfig

# what this program implements of the DeepSeek-V3 configuration space
SUPPORTED = {"q_lora_rank": None, "n_group": 1, "topk_group": 1,
             "norm_topk_prob": True, "topk_method": "noaux_tc",
             "scoring_func": "sigmoid", "moe_layer_freq": 1}


def program_config(conf: dict) -> ArchConfig:
    for key, want in SUPPORTED.items():
        if conf[key] != want:
            raise ValueError(f"{key}={conf[key]!r}: only {want!r} is "
                             "implemented")
    held = conf["n_routed_experts"]
    return ArchConfig(
        name=conf["name"], family="mla_moe",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        attn=AttnConfig(n_heads=conf["num_attention_heads"],
                        n_kv_heads=conf["num_key_value_heads"],
                        head_dim=conf["qk_nope_head_dim"],
                        rope_theta=float(conf["rope_theta"]),
                        mla=MLAConfig(kv_lora_rank=conf["kv_lora_rank"],
                                      rope_dim=conf["qk_rope_head_dim"],
                                      v_head_dim=conf["v_head_dim"])),
        moe=MoEConfig(n_experts=conf["published"]["n_routed_experts"],
                      top_k=conf["num_experts_per_tok"],
                      d_expert=conf["moe_intermediate_size"],
                      n_shared_experts=conf["n_shared_experts"],
                      d_shared=(conf["n_shared_experts"]
                                * conf["moe_intermediate_size"]),
                      routed_scale=conf["routed_scaling_factor"],
                      n_held=held,
                      held_first=conf["expert_parallel"]["rank"] * held),
        first_k_dense=conf["first_k_dense_replace"],
        tie_embeddings=conf["tie_word_embeddings"],
        norm_eps=conf["rms_norm_eps"], act=conf["hidden_act"],
        source=conf["source"])
