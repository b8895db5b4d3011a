"""DeepSeek-V3-family decoders in the program: latent attention (no
q-LoRA), leading dense layers, then the held share of a dropless expert
layer with sigmoid routing and shared experts."""
from __future__ import annotations

from repro.models.config import ModelConfig


def program_config(cfg: dict) -> ModelConfig:
    c = cfg["config"]
    if c["q_lora_rank"] is not None or c["n_group"] != 1 \
            or c["topk_group"] != 1 or c["moe_layer_freq"] != 1:
        raise ValueError(f"{cfg['name']}: q-LoRA, grouped routing and "
                         f"sparse expert layers are not built")
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    return ModelConfig(
        name=cfg["name"], arch_type="moe",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=nope + rope,
        ffn_act="swiglu", layer_pattern=("mla",),
        dense_lead=c["first_k_dense_replace"],
        moe_experts=c["n_routed_experts"],
        moe_top_k=c["num_experts_per_tok"],
        moe_shared=c["n_shared_experts"], moe_impl="dropless",
        moe_d_ff=c["moe_intermediate_size"],
        moe_router_experts=c["router_width"],
        moe_expert_offset=c["held_expert_offset"],
        moe_score=c["scoring_func"], moe_norm_topk=bool(c["norm_topk_prob"]),
        moe_routed_scale=float(c["routed_scaling_factor"]),
        mla_kv_rank=c["kv_lora_rank"], mla_nope_dim=nope,
        mla_rope_dim=rope, mla_v_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]),
        rope_interleave=bool(c["rope_interleave"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), attn_shard="batch",
        param_dtype=cfg["precision"]["param_dtype"])
