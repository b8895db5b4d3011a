"""Mamba-2 (SSD) language models in the program."""
from __future__ import annotations

from repro.models.config import ModelConfig


def program_config(cfg: dict) -> ModelConfig:
    c = cfg["config"]
    mult = c.get("pad_vocab_size_multiple", 1)
    return ModelConfig(
        name=cfg["name"], arch_type="ssm", n_layers=c["n_layer"],
        d_model=c["d_model"], n_heads=c["expand"] * c["d_model"]
        // c["headdim"], n_kv_heads=c["expand"] * c["d_model"]
        // c["headdim"], d_ff=c["d_intermediate"],
        vocab_size=-(-c["vocab_size"] // mult) * mult,
        layer_pattern=("mamba",), ssm_state=c["d_state"],
        ssm_head_dim=c["headdim"], ssm_expand=c["expand"],
        ssm_conv=c["d_conv"], tie_embeddings=bool(c["tie_embeddings"]),
        attn_shard="batch", param_dtype=cfg["precision"]["param_dtype"])
