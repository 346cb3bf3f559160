"""The program's configuration of a Mamba2 language model, built from the
numbers of a configuration file in ``bench/configs/``."""
from repro.configs.base import ArchConfig, SSMConfig


def program_config(conf: dict) -> ArchConfig:
    if conf["ngroups"] != 1 or conf["d_intermediate"] != 0:
        raise ValueError("the program's Mamba2 block has one B/C group and "
                         "no MLP")
    return ArchConfig(
        name=conf["name"], family="ssm", n_layers=conf["n_layer"],
        d_model=conf["d_model"], d_ff=0, vocab_size=conf["vocab_size"],
        ssm=SSMConfig(d_state=conf["d_state"], expand=conf["expand"],
                      head_dim=conf["headdim"], d_conv=conf["d_conv"],
                      chunk=conf["chunk_size"]),
        tie_embeddings=conf["tie_embeddings"],
        norm_eps=conf["norm_epsilon"], source=conf["source"])
