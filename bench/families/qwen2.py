"""Qwen2-family decoders (dense attention with QKV bias) in the program."""
from __future__ import annotations

from repro.models.config import ModelConfig


def program_config(cfg: dict) -> ModelConfig:
    c = cfg["config"]
    return ModelConfig(
        name=cfg["name"], arch_type="dense",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        head_dim=c.get("head_dim",
                       c["hidden_size"] // c["num_attention_heads"]),
        ffn_act="swiglu", qkv_bias=True, layer_pattern=("attn",),
        rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), attn_shard="batch",
        param_dtype=cfg["precision"]["param_dtype"])
