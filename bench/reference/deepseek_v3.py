"""Plain float32 reference of a DeepSeek-V3 decoder's held share.

Follows the published DeepSeek-V3 architecture (``model_type``
``deepseek_v3``, as Kanana-2-30B-A3B publishes it): token embedding; the
first ``first_k_dense_replace`` layers with a dense SwiGLU feed-forward,
the rest with a sparse expert layer; each layer a pre-RMSNorm multi-head
latent attention without q-LoRA and a pre-RMSNorm feed-forward; a final
RMSNorm and an untied head.

Latent attention, per head: ``q = x W_q`` is ``[q_nope | q_rope]``;
``x W_kva`` is ``[c | k_rope]`` with ``c`` RMS-normed and expanded by
``W_kvb`` to ``[k_nope | v]``; RoPE rotates the pairs ``(2i, 2i + 1)`` of
``q_rope`` and of ``k_rope`` (one ``k_rope`` for every head); the scores
``(q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)`` are causally
masked and softmaxed over ``v``, then ``W_o``.

Expert layer: ``s = sigmoid(x W_r)`` in float32 over every published
expert; the chosen experts are the top ``num_experts_per_tok`` of ``s +
e_score_correction_bias``; their weights are the chosen ``s``, divided by
their sum (``norm_topk_prob``) and times ``routed_scaling_factor``; the
shared experts (one SwiGLU ``n_shared_experts`` times as wide) are added
for every token.  Each held expert is applied to every token and masked
by its weight: no sorting, no grouped matmul.

Departures from the published model, each shared with the program:
- the held share of a layer spread over several chips: experts
  ``held_expert_offset .. + n_routed_experts - 1`` of ``router_width``,
  and ``num_attention_heads`` of the heads; what the absent experts and
  heads would add is left out;
- a slice of the vocabulary, over which the logits and the loss are;
- ``e_score_correction_bias`` is a parameter leaf that no load-balancing
  rule updates (the published ``noaux_tc`` moves it by a fixed step
  against each expert's load; here it keeps its draw, zero in a run);
- no multi-token-prediction layer.

Callers run it under ``jax.default_matmul_precision("highest")``.  In
another precision (the calibration's control) activations and weights
keep the parameters' type and each normalisation, the router and the
softmax run in float32, as in the program.
Parameters are a nested dict mirroring the program's layout
(``param_shapes``): the dense layers under ``lead/l<i>``, the expert
layers stacked on a leading axis under ``periods/s0``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dims(c: dict) -> dict:
    """The sizes this reference reads from a configuration's ``config``."""
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "r": c["kv_lora_rank"], "nope": c["qk_nope_head_dim"],
            "rope": c["qk_rope_head_dim"], "vd": c["v_head_dim"],
            "ff": c["intermediate_size"], "eff": c["moe_intermediate_size"],
            "experts": c["n_routed_experts"], "router": c["router_width"],
            "offset": c["held_expert_offset"],
            "top_k": c["num_experts_per_tok"],
            "shared": c["n_shared_experts"],
            "lead": c["first_k_dense_replace"],
            "layers": c["num_hidden_layers"], "v": c["vocab_size"],
            "eps": c["rms_norm_eps"], "theta": float(c["rope_theta"]),
            "scale": float(c["routed_scaling_factor"]),
            "norm_topk": bool(c["norm_topk_prob"])}


def _mla_shapes(m: dict, lead: tuple) -> dict:
    d, h = m["d"], m["h"]
    return {"attn/wq": lead + (d, h * (m["nope"] + m["rope"])),
            "attn/wkv_a": lead + (d, m["r"] + m["rope"]),
            "attn/kv_norm/scale": lead + (m["r"],),
            "attn/wkv_b": lead + (m["r"], h * (m["nope"] + m["vd"])),
            "attn/wo": lead + (h * m["vd"], d),
            "ln/scale": lead + (d,), "ln_f/scale": lead + (d,)}


def param_shapes(c: dict) -> dict:
    """``{path: shape}`` of every parameter."""
    m = dims(c)
    d, eff, E = m["d"], m["eff"], m["experts"]
    s = {"embed/table": (m["v"], d), "final_norm/scale": (d,),
         "lm_head/w": (d, m["v"])}
    for i in range(m["lead"]):
        one = dict(_mla_shapes(m, ()), **{
            "ffn/wi": (d, m["ff"]), "ffn/wg": (d, m["ff"]),
            "ffn/wo": (m["ff"], d)})
        s.update({f"lead/l{i}/{k}": v for k, v in one.items()})
    L = (m["layers"] - m["lead"],)
    sw = m["shared"] * eff
    stacked = dict(_mla_shapes(m, L), **{
        "moe/router": L + (d, m["router"]),
        "moe/e_score_correction_bias": L + (m["router"],),
        "moe/experts/wi": L + (E, d, eff), "moe/experts/wg": L + (E, d, eff),
        "moe/experts/wo": L + (E, eff, d),
        "moe/shared/wi": L + (d, sw), "moe/shared/wg": L + (d, sw),
        "moe/shared/wo": L + (sw, d)})
    s.update({f"periods/s0/{k}": v for k, v in stacked.items()})
    return s


def init_rule(path: str, shape: tuple) -> tuple:
    """``(kind, scale)`` of the seeded draw for one leaf: ``normal`` draws
    ``scale * N(0, 1)``, ``one`` draws ``1 + scale * N(0, 1)``."""
    name = path.rsplit("/", 1)[-1]
    if path == "embed/table":
        return "normal", 0.02
    if name == "scale":
        return "one", 0.1
    if name == "e_score_correction_bias":
        return "normal", 0.0
    return "normal", shape[-2] ** -0.5          # fan-in of a (.., in, out)


def rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def rope_pairs(x, theta):
    """``x``: (S, H, D); rotates each pair ``(2i, 2i + 1)`` of the last
    axis by ``position * theta^(-2i/D)``."""
    s, _, dd = x.shape
    inv = theta ** (-jnp.arange(0, dd, 2, dtype=jnp.float32) / dd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv        # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape)


def swiglu(p, x):
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]


def latent_attention(a: dict, x, m: dict):
    """``x``: (S, d) normed input -> (S, d)."""
    s = x.shape[0]
    h, r, nope, vd = m["h"], m["r"], m["nope"], m["vd"]
    q = (x @ a["wq"]).reshape(s, h, nope + m["rope"])
    ckr = x @ a["wkv_a"]
    c = rmsnorm(ckr[:, :r], a["kv_norm"]["scale"], m["eps"]).astype(x.dtype)
    kv = (c @ a["wkv_b"]).reshape(s, h, nope + vd)
    q_rope = rope_pairs(q[..., nope:].astype(jnp.float32), m["theta"])
    k_rope = rope_pairs(ckr[:, None, r:].astype(jnp.float32), m["theta"])[:, 0]
    scores = (jnp.einsum("qhd,khd->hqk", q[..., :nope], kv[..., :nope])
              .astype(jnp.float32)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_rope))
    scores = scores / jnp.sqrt(jnp.float32(nope + m["rope"]))
    causal = jnp.tril(jnp.ones((s, s), bool))
    w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", w.astype(x.dtype), kv[..., nope:])
    return o.reshape(s, h * vd) @ a["wo"]


def route(p: dict, x, m: dict):
    """``(idx, weights)``, each (S, top_k): the chosen experts of the
    router's and their weights, in float32."""
    s = jax.nn.sigmoid(x.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    _, idx = jax.lax.top_k(
        s + p["e_score_correction_bias"].astype(jnp.float32), m["top_k"])
    w = jnp.take_along_axis(s, idx, -1)
    if m["norm_topk"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * m["scale"]


def held_experts(p: dict, x, m: dict):
    """The held experts' part of the routed output, (S, d) float32: each
    held expert on every token, weighted by the weight its token gave it
    (zero where the token chose it not)."""
    idx, w = route(p, x, m)
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(m["experts"]):
        gate = jnp.sum(jnp.where(idx == m["offset"] + e, w, 0.0), -1)
        pe = jax.tree_util.tree_map(lambda t: t[e], p["experts"])
        out = out + gate[:, None] * swiglu(pe, x).astype(jnp.float32)
    return out


def expert_layer(p: dict, x, m: dict):
    """The held share of the expert layer plus the shared experts."""
    return held_experts(p, x, m).astype(x.dtype) + swiglu(p["shared"], x)


def layer(p: dict, x, m: dict):
    """One decoder layer on ``x``: (S, d)."""
    h = rmsnorm(x, p["ln"]["scale"], m["eps"]).astype(x.dtype)
    x = x + latent_attention(p["attn"], h, m).astype(x.dtype)
    h = rmsnorm(x, p["ln_f"]["scale"], m["eps"]).astype(x.dtype)
    y = swiglu(p["ffn"], h) if "ffn" in p else expert_layer(p["moe"], h, m)
    return x + y.astype(x.dtype)


def logits(params: dict, c: dict, tokens):
    """``tokens``: (S,) int -> (S, vocab) float32 logits."""
    m = dims(c)
    x = params["embed"]["table"][tokens]
    step = jax.checkpoint(lambda p, x: layer(p, x, m))
    for i in range(m["lead"]):
        x = step(params["lead"][f"l{i}"], x)
    for i in range(m["layers"] - m["lead"]):
        x = step(jax.tree_util.tree_map(lambda t: t[i],
                                        params["periods"]["s0"]), x)
    x = rmsnorm(x, params["final_norm"]["scale"], m["eps"]).astype(x.dtype)
    return (x @ params["lm_head"]["w"]).astype(jnp.float32)


def loss(params: dict, c: dict, tokens, labels):
    """Mean next-token cross-entropy of one sequence."""
    z = logits(params, c, tokens)
    lse = jax.nn.logsumexp(z, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(z, labels[:, None], -1)[:, 0])
