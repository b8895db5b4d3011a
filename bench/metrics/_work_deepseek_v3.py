"""The work a DeepSeek-V3-family cell asks of the chip, from shapes.

The held share of each layer (``bench/reference/deepseek_v3.py``): the
held heads' latent attention, the dense layer's FFN, and per expert
layer the router (every published expert), the shared experts and the
held experts' share of the routed tokens.  Routed work is counted at its
expectation, ``top_k * held / router`` (token, slot) pairs per token,
whatever the seeded router's actual loads; each pair costs one expert's
SwiGLU.  Never from XLA's ``cost_analysis()``.

  matmul_weights    weights in a matrix product per token, routed at
                    their expectation
  forward_flops     FLOPs of one causal sequence's forward pass
  train_flops       forward and backward, no recomputation (3x forward)
  held_pairs        expected (token, slot) pairs on the held experts
  expert_work       bytes and FLOPs the held experts' grouped matmul
                    cannot avoid in one train step
"""
from __future__ import annotations


def dims(cfg: dict) -> dict:
    c = cfg["config"]
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "r": c["kv_lora_rank"], "nope": c["qk_nope_head_dim"],
            "rope": c["qk_rope_head_dim"], "vd": c["v_head_dim"],
            "ff": c["intermediate_size"], "eff": c["moe_intermediate_size"],
            "held": c["n_routed_experts"], "router": c["router_width"],
            "top_k": c["num_experts_per_tok"],
            "shared": c["n_shared_experts"],
            "lead": c["first_k_dense_replace"],
            "layers": c["num_hidden_layers"], "v": c["vocab_size"]}


def _expert(m: dict) -> int:
    return 3 * m["d"] * m["eff"]                # one expert's SwiGLU


def matmul_weights(cfg: dict) -> float:
    """Weights used in a matrix product per token (the embedding lookup
    is a gather); the held experts at their expected share of tokens."""
    m = dims(cfg)
    d, h = m["d"], m["h"]
    mla = (d * h * (m["nope"] + m["rope"]) + d * (m["r"] + m["rope"])
           + m["r"] * h * (m["nope"] + m["vd"]) + h * m["vd"] * d)
    moe = (d * m["router"] + m["shared"] * _expert(m)
           + m["top_k"] * m["held"] / m["router"] * _expert(m))
    return (m["layers"] * mla + m["lead"] * 3 * d * m["ff"]
            + (m["layers"] - m["lead"]) * moe + d * m["v"])


def forward_flops(cfg: dict, tokens: int) -> float:
    """One causal ``tokens``-long sequence, forward only: the weight
    matmuls and, per causal (q, k) pair and head, the scores over the
    q/k width and the weighted sum over the v width."""
    m = dims(cfg)
    pairs = tokens * (tokens + 1) / 2
    mix = (m["layers"] * 2 * pairs * m["h"]
           * (m["nope"] + m["rope"] + m["vd"]))
    return 2.0 * matmul_weights(cfg) * tokens + mix


def train_flops(cfg: dict, tokens: int) -> float:
    """Forward and backward of one sequence: three forward passes' worth,
    recomputation not counted."""
    return 3.0 * forward_flops(cfg, tokens)


def held_pairs(cfg: dict, tokens: int) -> float:
    """Expected (token, slot) pairs routed to the held experts, over all
    expert layers, for ``tokens`` tokens."""
    m = dims(cfg)
    return ((m["layers"] - m["lead"]) * tokens * m["top_k"] * m["held"]
            / m["router"])


def expert_work(cfg: dict, workers: int, tokens: int,
                weight_bytes: int = 2) -> tuple:
    """``(bytes, flops)`` of the held experts' grouped matmuls in one train
    step of ``workers`` workers of ``tokens`` tokens each: per worker and
    expert layer, the held weights read once forward and once backward
    and their gradient written once; the expected pairs' forward FLOPs
    three times (forward and backward)."""
    m = dims(cfg)
    held_bytes = m["held"] * _expert(m) * weight_bytes
    nbytes = workers * (m["layers"] - m["lead"]) * 3 * held_bytes
    flops = 3 * 2.0 * workers * held_pairs(cfg, tokens) * _expert(m)
    return nbytes, flops


def parameter_count(cfg: dict) -> int:
    """Every trainable coordinate of the held share."""
    m = dims(cfg)
    d, h = m["d"], m["h"]
    mla = (d * h * (m["nope"] + m["rope"]) + d * (m["r"] + m["rope"])
           + m["r"] + m["r"] * h * (m["nope"] + m["vd"]) + h * m["vd"] * d
           + 2 * d)
    moe = (d * m["router"] + m["router"]
           + (m["held"] + m["shared"]) * _expert(m))
    return (m["layers"] * mla + m["lead"] * 3 * d * m["ff"]
            + (m["layers"] - m["lead"]) * moe + 2 * m["v"] * d + d)
