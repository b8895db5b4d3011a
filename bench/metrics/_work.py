"""The work a cell asks of the chip, computed from shapes.

Never from XLA's ``cost_analysis()``: it counts the body of a scanned
layer stack once, whatever the number of layers.  Each function reads a
configuration's ``config`` dict and its ``model_type``.

  matmul_weights    weights that take part in a matrix product per token
  forward_flops     FLOPs of one causal sequence's forward pass
  train_flops       forward and backward, no recomputation (3x forward)
  decode_flops      one token against a context of a given length
  agg_work          bytes and FLOPs the robust aggregation cannot avoid
"""
from __future__ import annotations


def _qwen2(c: dict) -> dict:
    d, nq = c["hidden_size"], c["num_attention_heads"]
    return {"d": d, "nq": nq, "nkv": c["num_key_value_heads"],
            "hd": c.get("head_dim", d // nq), "ff": c["intermediate_size"],
            "v": c["vocab_size"], "layers": c["num_hidden_layers"],
            "tied": bool(c["tie_word_embeddings"])}


def _mamba2(c: dict) -> dict:
    d = c["d_model"]
    d_in = c["expand"] * d
    mult = c.get("pad_vocab_size_multiple", 1)
    return {"d": d, "d_in": d_in, "n": c["d_state"], "p": c["headdim"],
            "h": d_in // c["headdim"], "k": c["d_conv"],
            "v": -(-c["vocab_size"] // mult) * mult, "layers": c["n_layer"]}


def matmul_weights(cfg: dict) -> int:
    """Weights used in a matrix product for every token (the embedding
    lookup is a gather; a tied embedding counts once, as the head)."""
    c = cfg["config"]
    if cfg["model_type"] == "qwen2":
        m = _qwen2(c)
        per_layer = (m["d"] * m["hd"] * (m["nq"] + 2 * m["nkv"])
                     + m["nq"] * m["hd"] * m["d"] + 3 * m["d"] * m["ff"])
        return m["layers"] * per_layer + m["v"] * m["d"]
    if cfg["model_type"] == "mamba2":
        m = _mamba2(c)
        in_proj = m["d"] * (2 * m["d_in"] + 2 * m["n"] + m["h"])
        return m["layers"] * (in_proj + m["d_in"] * m["d"]) + m["v"] * m["d"]
    raise KeyError(f"no work model for {cfg['model_type']!r}")


def _mixer_flops(cfg: dict, tokens: int) -> float:
    """Forward FLOPs of the sequence mixing beyond the weight matmuls."""
    c = cfg["config"]
    if cfg["model_type"] == "qwen2":
        m = _qwen2(c)
        pairs = tokens * (tokens + 1) / 2                # causal (q, k)
        return m["layers"] * 2 * 2 * pairs * m["nq"] * m["hd"]
    if cfg["model_type"] == "mamba2":
        m = _mamba2(c)
        # per token and layer: the depthwise convolution, the state's
        # input dt * B (x) x and its read-out C . h (2 FLOPs a product)
        conv = 2 * m["k"] * (m["d_in"] + 2 * m["n"])
        ssm = 2 * 2 * m["h"] * m["n"] * m["p"]
        return m["layers"] * tokens * (conv + ssm)
    raise KeyError(f"no work model for {cfg['model_type']!r}")


def forward_flops(cfg: dict, tokens: int) -> float:
    """One causal ``tokens``-long sequence, forward only."""
    return 2.0 * matmul_weights(cfg) * tokens + _mixer_flops(cfg, tokens)


def train_flops(cfg: dict, tokens: int) -> float:
    """Forward and backward of one sequence: three forward passes' worth
    (the backward pass takes two), recomputation not counted."""
    return 3.0 * forward_flops(cfg, tokens)


def decode_flops(cfg: dict, context: int) -> float:
    """One generated token attending to ``context`` earlier positions
    (itself included)."""
    c = cfg["config"]
    if cfg["model_type"] == "qwen2":
        m = _qwen2(c)
        attn = m["layers"] * 2 * 2 * context * m["nq"] * m["hd"]
        return 2.0 * matmul_weights(cfg) + attn
    return forward_flops(cfg, 1)


def agg_work(cfg: dict, n_workers: int, grad_bytes: int = 4) -> tuple:
    """``(bytes, flops)`` that any implementation of the robust
    aggregation must spend on an ``(n, d)`` gradient stack: one read of
    the stack at its type and one float32 write of the aggregate; the
    Gram matrix's ``2 n^2 d`` FLOPs."""
    d = parameter_count(cfg)
    return (n_workers * d * grad_bytes + 4 * d, 2.0 * n_workers ** 2 * d)


def parameter_count(cfg: dict) -> int:
    """Every trainable coordinate (the aggregation's ``d``)."""
    c = cfg["config"]
    if cfg["model_type"] == "qwen2":
        m = _qwen2(c)
        per_layer = (m["d"] * m["hd"] * (m["nq"] + 2 * m["nkv"])
                     + m["nq"] * m["hd"] * m["d"]
                     + m["hd"] * (m["nq"] + 2 * m["nkv"])
                     + 3 * m["d"] * m["ff"] + 2 * m["d"])
        head = 0 if m["tied"] else m["v"] * m["d"]
        return m["layers"] * per_layer + m["v"] * m["d"] + head + m["d"]
    if cfg["model_type"] == "mamba2":
        m = _mamba2(c)
        conv = m["d_in"] + 2 * m["n"]
        per_layer = (m["d"] * (2 * m["d_in"] + 2 * m["n"] + m["h"])
                     + m["k"] * conv + conv + 3 * m["h"] + m["d_in"]
                     + m["d_in"] * m["d"] + m["d"])
        return m["layers"] * per_layer + m["v"] * m["d"] + m["d"]
    raise KeyError(f"no work model for {cfg['model_type']!r}")
