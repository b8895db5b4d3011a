"""Plain float32 reference of a dense decoder (Qwen2 family, QKV bias).

Follows the published Qwen2 architecture: token embedding, per layer a
pre-RMSNorm causal self-attention with biased q/k/v projections, rotary
position embedding (half-split rotation) and an unbiased output
projection, then a pre-RMSNorm SwiGLU feed-forward, a final RMSNorm and
the unembedding (tied to the embedding where the configuration says so).
No cache, no batching tricks, no kernels: whole-sequence forward passes
in ``jax.numpy``.  Callers run it under
``jax.default_matmul_precision("highest")``.

Parameters are a nested dict whose leaves and names mirror the layout
the benchmark generates (``param_shapes``): layers stacked on a leading
axis under ``periods/s0``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dims(c: dict) -> dict:
    """The sizes this reference reads from a configuration's ``config``."""
    d = c["hidden_size"]
    nq = c["num_attention_heads"]
    return {"d": d, "nq": nq, "nkv": c["num_key_value_heads"],
            "hd": c.get("head_dim", d // nq), "ff": c["intermediate_size"],
            "v": c["vocab_size"], "layers": c["num_hidden_layers"],
            "eps": c["rms_norm_eps"], "theta": float(c["rope_theta"]),
            "tied": bool(c["tie_word_embeddings"])}


def param_shapes(c: dict) -> dict:
    """``{path: shape}`` of every parameter, layers stacked on axis 0."""
    m = dims(c)
    d, hd, L = m["d"], m["hd"], m["layers"]
    s = {
        "embed/table": (m["v"], d),
        "final_norm/scale": (d,),
        "periods/s0/ln/scale": (L, d),
        "periods/s0/attn/wq": (L, d, m["nq"] * hd),
        "periods/s0/attn/wk": (L, d, m["nkv"] * hd),
        "periods/s0/attn/wv": (L, d, m["nkv"] * hd),
        "periods/s0/attn/wo": (L, m["nq"] * hd, d),
        "periods/s0/attn/bq": (L, m["nq"] * hd),
        "periods/s0/attn/bk": (L, m["nkv"] * hd),
        "periods/s0/attn/bv": (L, m["nkv"] * hd),
        "periods/s0/ln_f/scale": (L, d),
        "periods/s0/ffn/wi": (L, d, m["ff"]),
        "periods/s0/ffn/wg": (L, d, m["ff"]),
        "periods/s0/ffn/wo": (L, m["ff"], d),
    }
    if not m["tied"]:
        s["lm_head/w"] = (d, m["v"])
    return s


def init_rule(path: str, shape: tuple) -> tuple:
    """``(kind, scale)`` of the seeded draw for one leaf: ``normal`` draws
    ``scale * N(0, 1)``, ``one`` draws ``1 + scale * N(0, 1)``."""
    name = path.rsplit("/", 1)[-1]
    if path == "embed/table":
        return "normal", 0.02
    if name == "scale":
        return "one", 0.1
    if name in ("bq", "bk", "bv"):
        return "normal", 0.02
    return "normal", shape[-2] ** -0.5          # fan-in of a (.., in, out)


def rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """``x``: (S, H, D); rotates the two halves of the head dimension."""
    s, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv       # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def layer(p: dict, x, m: dict):
    """One decoder layer on ``x``: (S, d)."""
    s = x.shape[0]
    h = rmsnorm(x, p["ln"]["scale"], m["eps"]).astype(x.dtype)
    a = p["attn"]
    q = (h @ a["wq"] + a["bq"]).reshape(s, m["nq"], m["hd"])
    k = (h @ a["wk"] + a["bk"]).reshape(s, m["nkv"], m["hd"])
    v = (h @ a["wv"] + a["bv"]).reshape(s, m["nkv"], m["hd"])
    q, k = rope(q, m["theta"]).astype(x.dtype), rope(k, m["theta"]).astype(x.dtype)
    rep = m["nq"] // m["nkv"]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(m["hd"]))
    causal = jnp.tril(jnp.ones((s, s), bool))
    w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", w.astype(x.dtype), v).reshape(s, -1)
    x = x + (o @ a["wo"]).astype(x.dtype)
    h = rmsnorm(x, p["ln_f"]["scale"], m["eps"]).astype(x.dtype)
    f = p["ffn"]
    x = x + ((jax.nn.silu(h @ f["wg"]) * (h @ f["wi"])) @ f["wo"]).astype(x.dtype)
    return x


def logits(params: dict, c: dict, tokens):
    """``tokens``: (S,) int -> (S, vocab) float32 logits."""
    m = dims(c)
    x = params["embed"]["table"][tokens]
    for i in range(m["layers"]):
        p = jax.tree_util.tree_map(lambda t: t[i], params["periods"]["s0"])
        x = jax.checkpoint(lambda p, x: layer(p, x, m))(p, x)
    x = rmsnorm(x, params["final_norm"]["scale"], m["eps"]).astype(x.dtype)
    head = (params["embed"]["table"].T if m["tied"]
            else params["lm_head"]["w"])
    return (x @ head).astype(jnp.float32)


def loss(params: dict, c: dict, tokens, labels):
    """Mean next-token cross-entropy of one sequence."""
    z = logits(params, c, tokens)
    lse = jax.nn.logsumexp(z, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(z, labels[:, None], -1)[:, 0])

