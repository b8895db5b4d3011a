"""Mixture-of-Experts FFN: capacity-based (GShard/Switch-style) dispatch
over every expert, and the dropless held share of an expert-parallel layer.

Capacity paths (``moe_ffn``).  Dense one-hot dispatch/combine einsums — the TPU-idiomatic formulation:
tokens are routed to per-expert capacity buffers, experts run as one batched
(stacked) matmul, results are combined with the gate weights.  The expert
axis is the natural target for expert-parallel sharding over the `model`
mesh axis (see repro.dist.sharding).  Tokens overflowing an expert's
capacity are dropped (their FFN output is zero; the residual path carries
them), matching Switch Transformer semantics.

Returns a Switch-style load-balance auxiliary loss.

Dropless held share (``moe_dropless``).  The layer is told which experts
it holds (a contiguous block of a larger layer, as one chip of an
expert-parallel group holds them): it routes every token over all the
published experts, never drops one, and computes only its own experts'
part of the result with one grouped matmul over the (token, slot) pairs
that chose them, plus the shared experts every holder computes alike.
What the absent experts would add is left to their holders.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.obs.trace import named_span

#: process-wide toggle (set by the launcher): when True, expert weights are
#: sharding-constrained to tensor-parallel-only specs at their use site.
#: With FSDP ("data") storage sharding on a *contraction* dim, XLA's SPMD
#: partitioner otherwise computes every worker's expert hiddens on every
#: data shard and all-reduces them — redundant compute plus the dominant
#: collective (measured on mixtral train_4k).  The constraint
#: turns that into one small per-layer weight all-gather instead.
EXPERT_WEIGHT_GATHER: bool = False


def _gathered_experts(experts: dict) -> dict:
    if not EXPERT_WEIGHT_GATHER:
        return experts
    from jax.sharding import PartitionSpec as P
    try:
        out = {}
        for name, w in experts.items():
            if name == "wo":                      # (E, d_ff, d): row-parallel
                spec = P(None, "model", None)
            else:                                 # wi/wg (E, d, d_ff): column
                spec = P(None, None, "model")
            out[name] = jax.lax.with_sharding_constraint(w, spec)
        return out
    except Exception:
        return experts


def init_moe(key, d: int, d_ff: int, n_experts: int, n_shared: int,
             act: str, dtype, router_experts: int = 0,
             score_bias: bool = False) -> dict:
    """``n_experts`` held experts of width ``d_ff``, a router over
    ``router_experts`` (default: the held ones), ``n_shared`` shared
    experts as one FFN of width ``n_shared * d_ff``, and with
    ``score_bias`` the selection bias (``e_score_correction_bias``, zero)."""
    keys = jax.random.split(key, 3)
    router_experts = router_experts or n_experts
    ek = jax.random.split(keys[0], n_experts)
    experts = jax.vmap(lambda k: layers.init_ffn(k, d, d_ff, act, dtype))(ek)
    p = {"router": layers.he_init(keys[1], (d, router_experts), jnp.float32),
         "experts": experts}
    if score_bias:
        p["e_score_correction_bias"] = jnp.zeros((router_experts,),
                                                 jnp.float32)
    if n_shared > 0:
        p["shared"] = layers.init_ffn(keys[2], d, d_ff * n_shared, act, dtype)
    return p


def moe_ffn(p: dict, x: jnp.ndarray, *, top_k: int, act: str,
            capacity_factor: float = 1.25, impl: str = "einsum"
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out: (B, S, D), aux_loss: scalar).

    impl="einsum": GShard-style dense one-hot dispatch/combine — simple,
    but materializes (T, E, C) tensors whose collectives dominate at scale.
    impl="scatter": scatter/gather dispatch — same routing semantics
    (identical positions/drops), never materializes (T, E, C).
    """
    if impl == "scatter":
        return _moe_ffn_scatter(p, x, top_k=top_k, act=act,
                                capacity_factor=capacity_factor)
    b, s, d = x.shape
    e = p["router"].shape[1]
    t = b * s
    xt = x.reshape(t, d)

    logits = (xt.astype(jnp.float32) @ p["router"])        # (T, E)
    gates = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(gates, top_k)      # (T, k)
    # renormalize the chosen gates (mixtral-style)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    capacity = max(1, int(math.ceil(top_k * t / e * capacity_factor)))

    # build (T, E, C) dispatch and combine tensors, one top-k slot at a time
    dispatch = jnp.zeros((t, e, capacity), jnp.bool_)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    fill = jnp.zeros((e,), jnp.int32)                      # tokens per expert
    for slot in range(top_k):
        idx = gate_idx[:, slot]                            # (T,)
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)   # (T, E)
        pos = fill[None, :] + jnp.cumsum(onehot, axis=0) - onehot  # (T, E)
        pos_tok = jnp.sum(pos * onehot, axis=1)            # (T,)
        keep = pos_tok < capacity
        disp = (jax.nn.one_hot(pos_tok, capacity, dtype=jnp.float32)
                [:, None, :] * onehot[:, :, None].astype(jnp.float32))
        disp = disp * keep[:, None, None]
        dispatch = dispatch | (disp > 0)
        combine = combine + disp * gate_vals[:, slot][:, None, None]
        fill = fill + jnp.sum(onehot, axis=0)

    # dispatch tokens to expert buffers: (E, C, D)
    exp_in = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), xt)

    def run_expert(ep, xe):
        return layers.ffn(ep, xe, act)

    exp_out = jax.vmap(run_expert)(p["experts"], exp_in)   # (E, C, D)
    out = jnp.einsum("ecd,tec->td", exp_out.astype(jnp.float32), combine)
    out = out.astype(x.dtype).reshape(b, s, d)

    if "shared" in p:
        out = out + layers.ffn(p["shared"], x, act)

    # Switch load-balance loss: E * sum_e (mean gate_e * mean dispatch_e)
    me = jnp.mean(gates, axis=0)                           # (E,)
    ce = jnp.mean(
        jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)
    return out, aux


def _moe_ffn_scatter(p: dict, x: jnp.ndarray, *, top_k: int, act: str,
                     capacity_factor: float = 1.25
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter/gather dispatch: routing-identical to the einsum path (same
    cumsum positions, same capacity drops) but the only O(T * E) tensor is
    the int32 position cumsum; token movement is a scatter-add into the
    (E, C, D) expert buffers and a gather back."""
    b, s, d = x.shape
    e = p["router"].shape[1]
    t = b * s
    xt = x.reshape(t, d)

    logits = (xt.astype(jnp.float32) @ p["router"])        # (T, E)
    gates = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(gates, top_k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    capacity = max(1, int(math.ceil(top_k * t / e * capacity_factor)))

    exp_in = jnp.zeros((e, capacity, d), x.dtype)
    fill = jnp.zeros((e,), jnp.int32)
    slots = []
    for slot in range(top_k):
        idx = gate_idx[:, slot]                            # (T,)
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)   # (T, E)
        pos = fill[None, :] + jnp.cumsum(onehot, axis=0) - onehot
        pos_tok = jnp.sum(pos * onehot, axis=1)            # (T,)
        keep = pos_tok < capacity
        pc = jnp.minimum(pos_tok, capacity - 1)
        exp_in = exp_in.at[idx, pc].add(
            jnp.where(keep[:, None], xt, 0).astype(exp_in.dtype))
        slots.append((idx, pc, keep))
        fill = fill + jnp.sum(onehot, axis=0)

    def run_expert(ep, xe):
        return layers.ffn(ep, xe, act)

    exp_out = jax.vmap(run_expert)(_gathered_experts(p["experts"]),
                                   exp_in)   # (E, C, D)

    out = jnp.zeros((t, d), jnp.float32)
    for slot, (idx, pc, keep) in enumerate(slots):
        # gather + weight in the compute dtype (keeps expert cotangents
        # bf16 on bf16 models), accumulate in fp32
        y = exp_out[idx, pc]                               # gather (T, D)
        w = (gate_vals[:, slot] * keep.astype(jnp.float32)).astype(y.dtype)
        out = out + (y * w[:, None]).astype(jnp.float32)
    out = out.astype(x.dtype).reshape(b, s, d)

    if "shared" in p:
        out = out + layers.ffn(p["shared"], x, act)

    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(
        jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)
    return out, aux


def route(p: dict, xt: jnp.ndarray, *, top_k: int, score: str,
          norm_topk: bool, scale: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """DeepSeek-V3 routing over every expert of the router, in float32.

    Scores are ``sigmoid`` (or ``softmax``) of ``x W_r``; the chosen
    experts are the ``top_k`` of ``score + e_score_correction_bias``; their
    weights are the chosen scores, renormalised to sum to one when
    ``norm_topk``, times ``scale``.  Returns ``(idx, weights)``, each
    ``(T, top_k)``.
    """
    logits = jnp.dot(xt.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = (jax.nn.sigmoid(logits) if score == "sigmoid"
         else jax.nn.softmax(logits, axis=-1))
    choice = s + p["e_score_correction_bias"].astype(jnp.float32) \
        if "e_score_correction_bias" in p else s
    _, idx = jax.lax.top_k(choice, top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * scale


def moe_dropless(p: dict, x: jnp.ndarray, *, top_k: int, act: str,
                 offset: int = 0, score: str = "sigmoid",
                 norm_topk: bool = True, scale: float = 1.0
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The held experts' part of a dropless expert layer, plus the shared
    experts.  x: (B, S, D) -> (out (B, S, D), load (E_held,) int32).

    ``p["experts"]`` holds experts ``offset .. offset + E_held - 1`` of the
    router's.  The (token, slot) pairs that chose a held expert are sorted
    by expert, run through one grouped SwiGLU (``jax.lax.ragged_dot``)
    and added back weighted; ``load`` counts the pairs per held expert.
    The rows past the groups (the pairs held elsewhere) are masked out of
    every product and so of every cotangent: the TPU's grouped matmul
    leaves them unwritten, and their garbage would reach the gradients.
    ``ragged_dot`` has no batching rule for per-example group sizes, so a
    caller computes examples that route apart one after another
    (``jax.lax.map``), not under ``jax.vmap``.
    """
    if act != "swiglu":
        raise ValueError(f"the dropless expert layer is SwiGLU, got {act!r}")
    b, s, d = x.shape
    t = b * s
    held = p["experts"]["wi"].shape[0]
    xt = x.reshape(t, d)
    with named_span("moe/route"):
        idx, w = route(p, xt, top_k=top_k, score=score, norm_topk=norm_topk,
                       scale=scale)
    with named_span("moe/dispatch"):
        local = idx.reshape(-1) - offset                   # (T * k,)
        mine = (local >= 0) & (local < held)
        group = jnp.where(mine, local, held)               # held last
        order = jnp.argsort(group, stable=True)
        tok = order // top_k
        load = jnp.sum(jax.nn.one_hot(group, held, dtype=jnp.int32), axis=0)
        rows = jnp.take(mine, order)[:, None]              # sorted, held
        xs = jnp.where(rows, jnp.take(xt, tok, axis=0), 0)  # (T * k, D)
        ws = jnp.where(rows[:, 0], jnp.take(w.reshape(-1), order), 0.0)
    with named_span("moe/experts"):
        e = p["experts"]

        def gmm(lhs, rhs):
            return jnp.where(rows, jax.lax.ragged_dot(lhs, rhs, load), 0)

        y = gmm(jax.nn.silu(gmm(xs, e["wg"])) * gmm(xs, e["wi"]), e["wo"])
    with named_span("moe/combine"):
        out = jnp.zeros((t, d), jnp.float32).at[tok].add(
            y.astype(jnp.float32) * ws[:, None])
        out = out.astype(x.dtype).reshape(b, s, d)
        if "shared" in p:
            out = out + layers.ffn(p["shared"], x, act)
    return out, load
