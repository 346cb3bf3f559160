"""The program's configuration of a dense decoder (Qwen3 family), built from
the numbers of a configuration file in ``bench/configs/``."""
from repro.configs.base import ArchConfig, AttnConfig


def program_config(conf: dict) -> ArchConfig:
    return ArchConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        attn=AttnConfig(n_heads=conf["num_attention_heads"],
                        n_kv_heads=conf["num_key_value_heads"],
                        head_dim=conf["head_dim"], qk_norm=True,
                        rope_theta=float(conf["rope_theta"])),
        tie_embeddings=conf["tie_word_embeddings"],
        norm_eps=conf["rms_norm_eps"], act=conf["hidden_act"],
        source=conf["source"])
