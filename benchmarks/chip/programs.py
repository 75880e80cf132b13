"""How a configuration file is handed to the program under test."""
from __future__ import annotations


def model_config(cfg: dict):
    """The program's `ModelConfig` for a decoder configuration file."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=cfg["attention_bias"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], dtype=cfg["torch_dtype"])
