"""Plain float32 reference of Mamba-2 (arXiv:2405.21060), attention-free.

Per layer: pre-RMSNorm, one input projection into the gate ``z``, the
convolved channels ``(x, B, C)`` and the step sizes ``dt``; a causal
depthwise convolution with SiLU over ``(x, B, C)``; the selective state
space recurrence

    h_t = exp(dt_t * A) h_{t-1} + dt_t * B_t (x) x_t,    y_t = C_t . h_t + D x_t

run token by token (a ``lax.scan`` over the sequence, no chunking); the
gated RMSNorm ``norm(y * silu(z))``; the output projection; the residual.
Then a final RMSNorm and the tied unembedding.  One group (``ngroups =
1``): every head reads the same ``B`` and ``C``.  Callers run it under
``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.transformer import rmsnorm


def dims(c: dict) -> dict:
    """The sizes this reference reads from a configuration's ``config``."""
    d = c["d_model"]
    d_in = c["expand"] * d
    return {"d": d, "d_in": d_in, "n": c["d_state"], "p": c["headdim"],
            "h": d_in // c["headdim"], "k": c["d_conv"],
            "v": c["vocab_size"], "layers": c["n_layer"],
            "eps": c["norm_epsilon"]}


def padded_vocab(c: dict) -> int:
    """The embedding's rows: ``vocab_size`` rounded up to
    ``pad_vocab_size_multiple``, as the published model builds it."""
    mult = c.get("pad_vocab_size_multiple", 1)
    return -(-c["vocab_size"] // mult) * mult


def param_shapes(c: dict) -> dict:
    """``{path: shape}`` of every parameter, layers stacked on axis 0."""
    m = dims(c)
    d, d_in, n, h, L = m["d"], m["d_in"], m["n"], m["h"], m["layers"]
    conv = d_in + 2 * n
    return {
        "embed/table": (padded_vocab(c), d),
        "final_norm/scale": (d,),
        "periods/s0/ln/scale": (L, d),
        "periods/s0/mix/in_proj": (L, d, 2 * d_in + 2 * n + h),
        "periods/s0/mix/conv_w": (L, m["k"], conv),
        "periods/s0/mix/conv_b": (L, conv),
        "periods/s0/mix/A_log": (L, h),
        "periods/s0/mix/D": (L, h),
        "periods/s0/mix/dt_bias": (L, h),
        "periods/s0/mix/norm/scale": (L, d_in),
        "periods/s0/mix/out_proj": (L, d_in, d),
    }


def init_rule(path: str, shape: tuple) -> tuple:
    """``(kind, scale)`` of the seeded draw for one leaf (see
    ``harness.weights``).  ``A_log`` and ``dt_bias`` follow the published
    initialisation: ``A`` uniform in [1, 16], ``dt`` log-uniform in
    [1e-3, 1e-1] through the inverse softplus."""
    name = path.rsplit("/", 1)[-1]
    if path == "embed/table":
        return "normal", 0.02
    if name in ("scale", "D"):
        return "one", 0.1
    if name == "A_log":
        return "log_uniform", (1.0, 16.0)
    if name == "dt_bias":
        return "inv_softplus_log_uniform", (1e-3, 1e-1)
    if name == "conv_w":
        return "normal", shape[-2] ** -0.5
    if name == "conv_b":
        return "normal", 0.02
    return "normal", shape[-2] ** -0.5


def _ssm_scan(x, dt, A, B, C):
    """``x``: (S, h, p), ``dt``: (S, h), ``A``: (h,), ``B``/``C``: (S, n)
    -> y: (S, h, p), one token at a time."""
    def step(state, inp):
        xt, dtt, bt, ct = inp
        state = (jnp.exp(dtt * A)[:, None, None] * state
                 + dtt[:, None, None] * bt[None, :, None] * xt[:, None, :])
        return state, jnp.einsum("n,hnp->hp", ct, state)

    h, p = x.shape[1:]
    state0 = jnp.zeros((h, B.shape[-1], p), x.dtype)
    _, y = jax.lax.scan(step, state0, (x, dt, B, C), unroll=8)
    return y


def layer(p: dict, x, m: dict):
    """One Mamba-2 layer on ``x``: (S, d)."""
    s = x.shape[0]
    d_in, n, h, k = m["d_in"], m["n"], m["h"], m["k"]
    u = rmsnorm(x, p["ln"]["scale"], m["eps"]).astype(x.dtype)
    mx = p["mix"]
    proj = u @ mx["in_proj"]
    z, xbc, dt = (proj[:, :d_in], proj[:, d_in:2 * d_in + 2 * n],
                  proj[:, 2 * d_in + 2 * n:])
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype),
                              xbc])
    conv = sum(padded[i:i + s] * mx["conv_w"][i] for i in range(k))
    conv = jax.nn.silu(conv + mx["conv_b"])
    xs = conv[:, :d_in].reshape(s, h, m["p"]).astype(jnp.float32)
    B = conv[:, d_in:d_in + n].astype(jnp.float32)
    C = conv[:, d_in + n:].astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + mx["dt_bias"])
    A = -jnp.exp(mx["A_log"].astype(jnp.float32))
    y = _ssm_scan(xs, dt, A, B, C) + xs * mx["D"].astype(jnp.float32)[:, None]
    y = y.reshape(s, d_in).astype(x.dtype) * jax.nn.silu(z)
    y = rmsnorm(y, mx["norm"]["scale"], m["eps"]).astype(x.dtype)
    return x + (y @ mx["out_proj"]).astype(x.dtype)


def logits(params: dict, c: dict, tokens):
    """``tokens``: (S,) int -> (S, padded vocab) float32 logits."""
    m = dims(c)
    x = params["embed"]["table"][tokens]
    for i in range(m["layers"]):
        p = jax.tree_util.tree_map(lambda t: t[i], params["periods"]["s0"])
        x = jax.checkpoint(lambda p, x: layer(p, x, m))(p, x)
    x = rmsnorm(x, params["final_norm"]["scale"], m["eps"]).astype(x.dtype)
    return (x @ params["embed"]["table"].T).astype(jnp.float32)


def loss(params: dict, c: dict, tokens, labels):
    """Mean next-token cross-entropy of one sequence."""
    z = logits(params, c, tokens)
    lse = jax.nn.logsumexp(z, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(z, labels[:, None], -1)[:, 0])

