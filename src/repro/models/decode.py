"""Serving path: KV/SSM caches, prefill, and single-token decode.

Cache layout mirrors the parameter layout: per-period stacked leaves
(scanned), per-tail-layer unstacked.  Attention slots use a full cache of
``cache_len`` positions; sliding-window / chunked slots use a bounded ring
cache of ``window`` / ``chunk`` positions — this is what makes long_500k
decode feasible for SWA/chunked/SSM architectures (the KV state does not
grow with context).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers, moe, ssm
from repro.models.attention import decode_attention, rope, verify_attention
from repro.models.config import ModelConfig
from repro.models.layers import _dtype


def check_decodable(cfg: ModelConfig) -> None:
    """Refuse a model this path cannot serve: its caches are full k/v per
    head, and a latent-attention (``mla``) model needs a latent cache (the
    normed latent ``c`` and the shared ``k_rope`` per position), which is
    not implemented; leading dense layers have no cache slot either."""
    if "mla" in cfg.layer_pattern:
        raise NotImplementedError(
            "mla slots cannot decode: the latent KV cache (normed latent c "
            "and shared k_rope per position) is not implemented; "
            "latent-attention models are train-only")
    if cfg.dense_lead:
        raise NotImplementedError(
            "leading dense layers (dense_lead) have no decode cache")


def slot_cache_len(cfg: ModelConfig, slot: str, cache_len: int) -> int:
    if slot == "swa" and cfg.window > 0:
        return min(cfg.window, cache_len)
    if slot == "chunked" and cfg.chunk > 0:
        return min(cfg.chunk, cache_len)
    return cache_len


# ---------------------------------------------------------------------------
# cache init
# ---------------------------------------------------------------------------

def _init_slot_cache(cfg: ModelConfig, slot: str, batch: int,
                     cache_len: int, dtype) -> dict:
    hd, hkv = cfg.head_dim, cfg.n_kv_heads
    if slot == "mamba":
        return ssm.init_mamba_cache(cfg, batch, dtype)
    L = slot_cache_len(cfg, slot, cache_len)
    c = {"k": jnp.zeros((batch, L, hkv, hd), dtype),
         "v": jnp.zeros((batch, L, hkv, hd), dtype)}
    if slot == "xattn":
        se = cfg.encoder_seq or cfg.vision_seq
        c["xk"] = jnp.zeros((batch, se, hkv, hd), dtype)
        c["xv"] = jnp.zeros((batch, se, hkv, hd), dtype)
    return c


def init_cache(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    check_decodable(cfg)
    dtype = _dtype(cfg.param_dtype)
    periods = {}
    for j, slot in enumerate(cfg.layer_pattern):
        one = _init_slot_cache(cfg, slot, batch, cache_len, dtype)
        periods[f"s{j}"] = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                x[None], (cfg.n_periods,) + x.shape), one)
    tail = {}
    for t in range(cfg.n_tail):
        slot = cfg.slot(cfg.n_periods * cfg.period + t)
        tail[f"t{t}"] = _init_slot_cache(cfg, slot, batch, cache_len, dtype)
    return {"periods": periods, "tail": tail}


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------

def _decode_attn_slot(p, c, x, cfg: ModelConfig, slot: str, pos
                      ) -> Tuple[dict, jnp.ndarray]:
    """``pos``: scalar or (B,) — per-sequence positions, so mixed-length
    continuous batching ropes/writes every slot at its own index."""
    b, _, d = x.shape
    hd = cfg.head_dim
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    h = layers.rmsnorm(p["ln"], x)
    q = (h @ p["attn"]["wq"] + p["attn"].get("bq", 0.0)
         ).reshape(b, 1, cfg.n_heads, hd)
    k = (h @ p["attn"]["wk"] + p["attn"].get("bk", 0.0)
         ).reshape(b, 1, cfg.n_kv_heads, hd)
    v = (h @ p["attn"]["wv"] + p["attn"].get("bv", 0.0)
         ).reshape(b, 1, cfg.n_kv_heads, hd)
    if slot != "attn_nope":
        posv = pos[:, None]  # (B, 1) broadcasts through rope to (B, S=1)
        q = rope(q, posv, cfg.rope_theta)
        k = rope(k, posv, cfg.rope_theta)
    L = c["k"].shape[1]
    ring = slot in ("swa", "chunked")
    idx = (pos % L) if ring else jnp.minimum(pos, L - 1)  # (B,)
    bidx = jnp.arange(b)
    kc = c["k"].at[bidx, idx].set(k[:, 0].astype(c["k"].dtype))
    vc = c["v"].at[bidx, idx].set(v[:, 0].astype(c["v"].dtype))
    valid = jnp.minimum(pos + 1, L)
    o = decode_attention(q, kc, vc, valid_len=valid)
    y = o.reshape(b, 1, cfg.n_heads * hd) @ p["attn"]["wo"]
    newc = dict(c)
    newc["k"], newc["v"] = kc, vc
    return newc, y


def _decode_layer(p, c, x, cfg: ModelConfig, slot: str, pos
                  ) -> Tuple[dict, jnp.ndarray]:
    if slot == "mamba":
        h = layers.rmsnorm(p["ln"], x)
        newc, y = ssm.mamba_decode_step(p["mix"], c, h, cfg)
        x = x + y
    else:
        newc, y = _decode_attn_slot(p, c, x, cfg, slot, pos)
        x = x + y
        if slot == "xattn":
            b = x.shape[0]
            hd = cfg.head_dim
            h = layers.rmsnorm(p["ln_x"], x)
            q = (h @ p["xatt"]["wq"] + p["xatt"].get("bq", 0.0)
                 ).reshape(b, 1, cfg.n_heads, hd)
            o = decode_attention(q, c["xk"], c["xv"])
            x = x + o.reshape(b, 1, cfg.n_heads * hd) @ p["xatt"]["wo"]
    if "ffn" in p:
        x = x + layers.ffn(p["ffn"], layers.rmsnorm(p["ln_f"], x),
                           cfg.ffn_act)
    elif "moe" in p:
        y, _ = moe.moe_ffn(p["moe"], layers.rmsnorm(p["ln_f"], x),
                           top_k=cfg.moe_top_k, act=cfg.ffn_act,
                           capacity_factor=cfg.capacity_factor,
                           impl=cfg.moe_impl)
        x = x + y
    return newc, x


def decode_step(params, cfg: ModelConfig, cache: dict, token: jnp.ndarray,
                pos) -> Tuple[jnp.ndarray, dict]:
    """token: (B, 1) int32; pos: scalar or (B,) per-sequence positions.
    Returns (logits (B, 1, V), new_cache)."""
    check_decodable(cfg)
    x = layers.embed(params["embed"], token)

    def body(x, xs):
        period_p, period_c = xs
        newc = {}
        for j, slot in enumerate(cfg.layer_pattern):
            newc[f"s{j}"], x = _decode_layer(period_p[f"s{j}"],
                                             period_c[f"s{j}"], x, cfg,
                                             slot, pos)
        return x, newc

    x, new_periods = jax.lax.scan(
        body, x, (params["periods"], cache["periods"]),
        unroll=cfg.unroll_scan)

    new_tail = {}
    for t in range(cfg.n_tail):
        slot = cfg.slot(cfg.n_periods * cfg.period + t)
        new_tail[f"t{t}"], x = _decode_layer(
            params["tail"][f"t{t}"], cache["tail"][f"t{t}"], x, cfg, slot,
            pos)

    x = layers.rmsnorm(params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = layers.unembed(params["embed"], x)
    else:
        logits = layers.linear(params["lm_head"], x)
    if cfg.logit_softcap > 0:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits, {"periods": new_periods, "tail": new_tail}


# ---------------------------------------------------------------------------
# verify step (a causal block of new tokens — the speculative-decoding path)
# ---------------------------------------------------------------------------

_RING_SLOTS = ("swa", "chunked")


def verify_supported(cfg: ModelConfig) -> Tuple[bool, str]:
    """Whether ``verify_step`` (and hence speculative decoding) applies.

    The multi-token verify block relies on positional cache rollback: a
    rejected draft suffix leaves garbage cache entries *above* the
    accepted position, which per-query causal masking hides until the
    next block overwrites them.  Two cache families break that invariant:

    * ring caches (``swa`` / ``chunked`` slots) wrap rejected writes onto
      *valid* old window entries, which stay visible;
    * recurrent SSM state (``mamba``) advances destructively — there is
      no positional index to roll back to.

    Args:
      cfg: model configuration to probe.

    Returns:
      ``(ok, reason)`` — ``reason`` names the offending layer slot when
      ``ok`` is False (empty string otherwise).
    """
    slots = set(cfg.layer_pattern)
    slots.update(cfg.slot(cfg.n_periods * cfg.period + t)
                 for t in range(cfg.n_tail))
    for slot in sorted(slots):
        if slot == "mamba":
            return False, ("mamba: recurrent SSM state cannot roll back "
                           "a rejected draft suffix")
        if slot in _RING_SLOTS:
            return False, (f"{slot}: ring cache wraps rejected draft "
                           f"writes onto valid window entries")
    return True, ""


def _verify_attn_slot(p, c, x, cfg: ModelConfig, slot: str, pos
                      ) -> Tuple[dict, jnp.ndarray]:
    """One attention layer over a ``(B, S)`` verify block: token ``j``
    sits at position ``pos + j``.  All S keys are written first, then
    every query attends its own causal prefix (``verify_attention``) —
    so entry ``j``'s output equals the sequential decode that fed the
    same ``j`` tokens, and garbage above the block (rejected drafts of
    earlier rounds) stays masked."""
    b, s, d = x.shape
    hd = cfg.head_dim
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    qpos = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]  # (B, S)
    h = layers.rmsnorm(p["ln"], x)
    q = (h @ p["attn"]["wq"] + p["attn"].get("bq", 0.0)
         ).reshape(b, s, cfg.n_heads, hd)
    k = (h @ p["attn"]["wk"] + p["attn"].get("bk", 0.0)
         ).reshape(b, s, cfg.n_kv_heads, hd)
    v = (h @ p["attn"]["wv"] + p["attn"].get("bv", 0.0)
         ).reshape(b, s, cfg.n_kv_heads, hd)
    if slot != "attn_nope":
        q = rope(q, qpos, cfg.rope_theta)
        k = rope(k, qpos, cfg.rope_theta)
    L = c["k"].shape[1]
    if slot in _RING_SLOTS or slot == "mamba":
        raise ValueError(
            f"verify_step does not support {slot!r} slots (ring/SSM "
            f"caches cannot roll back rejected draft tokens)")
    idx = jnp.minimum(qpos, L - 1)  # (B, S)
    bidx = jnp.arange(b)[:, None]
    kc = c["k"].at[bidx, idx].set(k.astype(c["k"].dtype))
    vc = c["v"].at[bidx, idx].set(v.astype(c["v"].dtype))
    q_valid = jnp.minimum(qpos + 1, L)  # per-query causal prefix
    o = verify_attention(q, kc, vc, q_valid=q_valid)
    y = o.reshape(b, s, cfg.n_heads * hd) @ p["attn"]["wo"]
    newc = dict(c)
    newc["k"], newc["v"] = kc, vc
    return newc, y


def _verify_layer(p, c, x, cfg: ModelConfig, slot: str, pos
                  ) -> Tuple[dict, jnp.ndarray]:
    newc, y = _verify_attn_slot(p, c, x, cfg, slot, pos)
    x = x + y
    if slot == "xattn":
        b, s = x.shape[0], x.shape[1]
        hd = cfg.head_dim
        h = layers.rmsnorm(p["ln_x"], x)
        q = (h @ p["xatt"]["wq"] + p["xatt"].get("bq", 0.0)
             ).reshape(b, s, cfg.n_heads, hd)
        o = verify_attention(q, c["xk"], c["xv"])
        x = x + o.reshape(b, s, cfg.n_heads * hd) @ p["xatt"]["wo"]
    if "ffn" in p:
        x = x + layers.ffn(p["ffn"], layers.rmsnorm(p["ln_f"], x),
                           cfg.ffn_act)
    elif "moe" in p:
        y, _ = moe.moe_ffn(p["moe"], layers.rmsnorm(p["ln_f"], x),
                           top_k=cfg.moe_top_k, act=cfg.ffn_act,
                           capacity_factor=cfg.capacity_factor,
                           impl=cfg.moe_impl)
        x = x + y
    return newc, x


def verify_step(params, cfg: ModelConfig, cache: dict, tokens: jnp.ndarray,
                pos) -> Tuple[jnp.ndarray, dict]:
    """Decode a causal block of ``S`` tokens in one forward pass.

    The speculative-verify analogue of ``decode_step``: ``tokens[:, j]``
    is consumed at position ``pos + j`` and ``logits[:, j]`` predicts the
    token at position ``pos + j + 1`` — exactly what ``S`` sequential
    ``decode_step`` calls on the same tokens would produce, but with one
    model pass (keys written first, per-query causal masking).  Requires
    full attention caches (``verify_supported``).

    Args:
      params: parameter pytree of one model.
      cfg: model configuration.
      cache: decode-cache pytree (``init_cache`` layout).
      tokens: ``(B, S)`` int32 token block.
      pos: scalar or ``(B,)`` int32 — per-slot position of ``tokens[:, 0]``.

    Returns:
      ``(logits (B, S, V), new_cache)`` — the cache gains the block's
      ``S`` key/value entries per attention layer.
    """
    check_decodable(cfg)
    x = layers.embed(params["embed"], tokens)

    def body(x, xs):
        period_p, period_c = xs
        newc = {}
        for j, slot in enumerate(cfg.layer_pattern):
            newc[f"s{j}"], x = _verify_layer(period_p[f"s{j}"],
                                             period_c[f"s{j}"], x, cfg,
                                             slot, pos)
        return x, newc

    x, new_periods = jax.lax.scan(
        body, x, (params["periods"], cache["periods"]),
        unroll=cfg.unroll_scan)

    new_tail = {}
    for t in range(cfg.n_tail):
        slot = cfg.slot(cfg.n_periods * cfg.period + t)
        new_tail[f"t{t}"], x = _verify_layer(
            params["tail"][f"t{t}"], cache["tail"][f"t{t}"], x, cfg, slot,
            pos)

    x = layers.rmsnorm(params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = layers.unembed(params["embed"], x)
    else:
        logits = layers.linear(params["lm_head"], x)
    if cfg.logit_softcap > 0:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits, {"periods": new_periods, "tail": new_tail}


# ---------------------------------------------------------------------------
# prefill (fills caches; used by the serving engine + consistency tests)
# ---------------------------------------------------------------------------

def _prefill_slot(p, x, cfg: ModelConfig, slot: str, positions, enc_out,
                  cache_len: int, impl: str):
    """Apply one layer full-sequence AND return its filled cache."""
    from repro.models.transformer import (_apply_layer, _self_attention)
    b, s, d = x.shape
    hd = cfg.head_dim
    if slot == "mamba":
        h = layers.rmsnorm(p["ln"], x)
        y, cache = ssm.mamba_prefill(p["mix"], h, cfg)
        x = x + y
        if "ffn" in p:
            x = x + layers.ffn(p["ffn"], layers.rmsnorm(p["ln_f"], x),
                               cfg.ffn_act)
        elif "moe" in p:
            y2, _ = moe.moe_ffn(p["moe"], layers.rmsnorm(p["ln_f"], x),
                                top_k=cfg.moe_top_k, act=cfg.ffn_act,
                                capacity_factor=cfg.capacity_factor,
                                impl=cfg.moe_impl)
            x = x + y2
        return x, cache
    # attention slots: recompute k/v to stash (cheap vs the attention itself)
    h = layers.rmsnorm(p["ln"], x)
    k = (h @ p["attn"]["wk"] + p["attn"].get("bk", 0.0)
         ).reshape(b, s, cfg.n_kv_heads, hd)
    v = (h @ p["attn"]["wv"] + p["attn"].get("bv", 0.0)
         ).reshape(b, s, cfg.n_kv_heads, hd)
    if slot != "attn_nope":
        k = rope(k, positions, cfg.rope_theta)
    L = slot_cache_len(cfg, slot, cache_len)
    if s >= L:
        kc, vc = k[:, -L:], v[:, -L:]
    else:
        pad = ((0, 0), (0, L - s), (0, 0), (0, 0))
        kc, vc = jnp.pad(k, pad), jnp.pad(v, pad)
    cache = {"k": kc, "v": vc}
    if slot == "xattn":
        hx = enc_out
        cache["xk"] = (hx @ p["xatt"]["wk"] + p["xatt"].get("bk", 0.0)
                       ).reshape(b, hx.shape[1], cfg.n_kv_heads, hd)
        cache["xv"] = (hx @ p["xatt"]["wv"] + p["xatt"].get("bv", 0.0)
                       ).reshape(b, hx.shape[1], cfg.n_kv_heads, hd)
    x, _, _ = _apply_layer(p, x, cfg, slot, 0, positions, enc_out, impl)
    return x, cache


def prefill(params, cfg: ModelConfig, tokens: jnp.ndarray,
            extra: Optional[jnp.ndarray] = None, cache_len: int = 0,
            impl: str = "auto") -> Tuple[jnp.ndarray, dict]:
    """Full-sequence forward that also returns populated decode caches.
    ``cache_len`` defaults to the sequence length."""
    check_decodable(cfg)
    from repro.models.transformer import _run_encoder
    b, s = tokens.shape
    cache_len = cache_len or s
    x = layers.embed(params["embed"], tokens)
    if cfg.arch_type == "audio":
        enc_out = _run_encoder(params, cfg, extra, impl)
    elif cfg.arch_type == "vlm":
        enc_out = extra
    else:
        enc_out = None
    positions = jnp.arange(s)

    def body(x, period_p):
        caches = {}
        for j, slot in enumerate(cfg.layer_pattern):
            x, caches[f"s{j}"] = _prefill_slot(
                period_p[f"s{j}"], x, cfg, slot, positions, enc_out,
                cache_len, impl)
        return x, caches

    x, period_caches = jax.lax.scan(body, x, params["periods"],
                                    unroll=cfg.unroll_scan)
    tail_caches = {}
    for t in range(cfg.n_tail):
        slot = cfg.slot(cfg.n_periods * cfg.period + t)
        x, tail_caches[f"t{t}"] = _prefill_slot(
            params["tail"][f"t{t}"], x, cfg, slot, positions, enc_out,
            cache_len, impl)

    x = layers.rmsnorm(params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = layers.unembed(params["embed"], x)
    else:
        logits = layers.linear(params["lm_head"], x)
    if cfg.logit_softcap > 0:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits, {"periods": period_caches, "tail": tail_caches}
