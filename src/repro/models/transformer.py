"""Model assembly: embeddings + scanned layer periods + decode caches.

The layer stack is grouped into repeating *periods* (cfg.layer_pattern);
parameters for each slot are stacked on a leading ``n_periods`` axis and the
stack is traversed with ``jax.lax.scan`` (small HLO, fast compiles, natural
remat boundary).  Remainder layers ("tail", when n_layers % period != 0) are
unrolled with their own parameters, as are the leading dense layers
("lead", ``cfg.dense_lead``) before the periods.

Three entry points:
  forward      — full-sequence logits (training / evaluation);
                 ``forward_with_loads`` also returns the dropless expert
                 layers' per-expert loads
  prefill      — full-sequence logits + populated decode caches
  decode_step  — one token against the caches (serving)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers, moe, ssm
from repro.models.attention import (attention, decode_attention, rope)
from repro.models.config import ModelConfig
from repro.models.layers import _dtype
from repro.obs.trace import named_span


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_attn(key, cfg: ModelConfig, dtype) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": layers.he_init(kq, (d, cfg.n_heads * hd), dtype),
        "wk": layers.he_init(kk, (d, cfg.n_kv_heads * hd), dtype),
        "wv": layers.he_init(kv, (d, cfg.n_kv_heads * hd), dtype),
        "wo": layers.he_init(ko, (cfg.n_heads * hd, d), dtype,
                             fan_in=cfg.n_heads * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
    return p


def _init_mla(key, cfg: ModelConfig, dtype) -> dict:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.mla_kv_rank
    nope, rp, vd = cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
    kq, ka, kb, ko = jax.random.split(key, 4)
    return {
        "wq": layers.he_init(kq, (d, h * (nope + rp)), dtype),
        "wkv_a": layers.he_init(ka, (d, r + rp), dtype),
        "kv_norm": layers.init_rmsnorm(r, dtype),
        "wkv_b": layers.he_init(kb, (r, h * (nope + vd)), dtype),
        "wo": layers.he_init(ko, (h * vd, d), dtype, fan_in=h * vd),
    }


def _init_slot(key, cfg: ModelConfig, slot: str, layer_idx: int,
               dtype, enc: bool = False) -> dict:
    d = cfg.d_model
    keys = jax.random.split(key, 4)
    p: Dict[str, Any] = {"ln": layers.init_rmsnorm(d, dtype)}
    if slot == "mamba":
        p["mix"] = ssm.init_mamba(keys[0], cfg, dtype)
    elif slot == "mla":
        p["attn"] = _init_mla(keys[0], cfg, dtype)
    else:
        p["attn"] = _init_attn(keys[0], cfg, dtype)
        if slot == "xattn":
            p["ln_x"] = layers.init_rmsnorm(d, dtype)
            p["xatt"] = _init_attn(keys[1], cfg, dtype)
    if cfg.d_ff > 0:
        p["ln_f"] = layers.init_rmsnorm(d, dtype)
        act = "gelu" if enc else cfg.ffn_act
        if not enc and cfg.is_moe_layer(layer_idx):
            p["moe"] = moe.init_moe(
                keys[2], d, cfg.expert_d_ff, cfg.moe_experts, cfg.moe_shared,
                act, dtype, router_experts=cfg.router_experts,
                score_bias=cfg.moe_impl == "dropless")
        else:
            p["ffn"] = layers.init_ffn(keys[2], d, cfg.d_ff, act, dtype)
    return p


def init_model(key, cfg: ModelConfig) -> dict:
    dtype = _dtype(cfg.param_dtype)
    k_embed, k_per, k_tail, k_enc, k_head = jax.random.split(key, 5)
    params: Dict[str, Any] = {
        "embed": layers.init_embedding(k_embed, cfg.vocab_size,
                                       cfg.d_model, dtype),
        "final_norm": layers.init_rmsnorm(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.init_linear(
            k_head, cfg.d_model, cfg.vocab_size, dtype)

    if cfg.dense_lead:
        k_lead = jax.random.fold_in(key, 7)
        params["lead"] = {
            f"l{i}": _init_slot(jax.random.fold_in(k_lead, i), cfg,
                                cfg.slot(i), i, dtype)
            for i in range(cfg.dense_lead)}

    period_keys = jax.random.split(k_per, max(cfg.n_periods, 1))
    periods = {}
    for j, slot in enumerate(cfg.layer_pattern):
        def init_one(k, j=j, slot=slot):
            sk = jax.random.fold_in(k, j)
            return _init_slot(sk, cfg, slot, cfg.dense_lead + j, dtype)
        periods[f"s{j}"] = jax.vmap(init_one)(period_keys)
    params["periods"] = periods

    tail = {}
    for t in range(cfg.n_tail):
        layer_idx = cfg.dense_lead + cfg.n_periods * cfg.period + t
        slot = cfg.slot(layer_idx)
        tail[f"t{t}"] = _init_slot(jax.random.fold_in(k_tail, t), cfg, slot,
                                   layer_idx, dtype)
    params["tail"] = tail

    if cfg.encoder_layers > 0:
        enc_keys = jax.random.split(k_enc, cfg.encoder_layers)
        params["encoder"] = {
            "layers": jax.vmap(
                lambda k: _init_slot(k, cfg, "bidir", 0, dtype, enc=True)
            )(enc_keys),
            "final_norm": layers.init_rmsnorm(cfg.d_model, dtype),
        }
    return params


# ---------------------------------------------------------------------------
# full-sequence forward
# ---------------------------------------------------------------------------

def _attn_constrain(t: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """cfg.attn_shard == "batch": pin (b, s, h, hd) to batch-sharding over
    `model` so score einsums are local (no head_dim splitting).  Under the
    worker vmap (spmd_axis_name="data") the worker dim is inserted
    automatically.  No-op when no mesh is in context (``jax.set_mesh``):
    the single-device path has nothing to shard over."""
    if cfg.attn_shard != "batch" or jax.sharding.get_abstract_mesh().empty:
        return t
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        t, P("model", *([None] * (t.ndim - 1))))


def _self_attention(p, x, cfg: ModelConfig, slot: str, positions,
                    impl: str) -> jnp.ndarray:
    b, s, d = x.shape
    hd = cfg.head_dim
    q = (x @ p["wq"] + p.get("bq", 0.0)).reshape(b, s, cfg.n_heads, hd)
    k = (x @ p["wk"] + p.get("bk", 0.0)).reshape(b, s, cfg.n_kv_heads, hd)
    v = (x @ p["wv"] + p.get("bv", 0.0)).reshape(b, s, cfg.n_kv_heads, hd)
    q, k, v = (_attn_constrain(t, cfg) for t in (q, k, v))
    if slot not in ("attn_nope",):
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    kind = {"attn": "attn", "attn_nope": "attn", "swa": "swa",
            "chunked": "chunked", "bidir": "bidir", "xattn": "attn"}[slot]
    o = attention(q, k, v, kind=kind, window=cfg.window, chunk=cfg.chunk,
                  impl=impl)
    o = _attn_constrain(o, cfg)
    return o.reshape(b, s, cfg.n_heads * hd) @ p["wo"]


def _latent_attention(p, x, cfg: ModelConfig, positions, impl: str
                      ) -> jnp.ndarray:
    """Multi-head latent attention (DeepSeek-V3, no q-LoRA) for the held
    heads: ``q = x W_q`` is ``[q_nope | q_rope]`` per head; ``x W_kva`` is
    ``[c | k_rope]``, the latent ``c`` RMS-normed and expanded by ``W_kvb``
    to ``[k_nope | v]`` per head; ``k_rope`` (RoPE applied once) is shared
    by every head.  Training only: no latent cache yet."""
    b, s, _ = x.shape
    h, r = cfg.n_heads, cfg.mla_kv_rank
    nope, rp, vd = cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
    with named_span("mla"):
        q = (x @ p["wq"]).reshape(b, s, h, nope + rp)
        ckr = x @ p["wkv_a"]
        c = layers.rmsnorm(p["kv_norm"], ckr[..., :r])
        kv = (c @ p["wkv_b"]).reshape(b, s, h, nope + vd)
        q_rope = rope(q[..., nope:], positions, cfg.rope_theta,
                      interleave=cfg.rope_interleave)
        k_rope = rope(ckr[..., None, r:], positions, cfg.rope_theta,
                      interleave=cfg.rope_interleave)
        q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, h, rp))],
            axis=-1)
        q, k, v = (_attn_constrain(t, cfg) for t in (q, k, kv[..., nope:]))
        o = _attn_constrain(attention(q, k, v, kind="attn", impl=impl), cfg)
        return o.reshape(b, s, h * vd) @ p["wo"]


def _cross_attention(p, x, enc_out, cfg: ModelConfig, impl: str
                     ) -> jnp.ndarray:
    b, s, d = x.shape
    hd = cfg.head_dim
    se = enc_out.shape[1]
    q = (x @ p["wq"] + p.get("bq", 0.0)).reshape(b, s, cfg.n_heads, hd)
    k = (enc_out @ p["wk"] + p.get("bk", 0.0)).reshape(
        b, se, cfg.n_kv_heads, hd)
    v = (enc_out @ p["wv"] + p.get("bv", 0.0)).reshape(
        b, se, cfg.n_kv_heads, hd)
    o = attention(q, k, v, kind="cross", impl=impl)
    return o.reshape(b, s, cfg.n_heads * hd) @ p["wo"]


def _apply_layer(p, x, cfg: ModelConfig, slot: str, layer_idx: int,
                 positions, enc_out, impl: str
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray]]:
    """One layer -> ``(x, aux loss, load)``; ``load`` is a dropless
    expert layer's ``(E_held,)`` pairs per held expert, else ``None``."""
    aux = jnp.zeros((), jnp.float32)
    load = None
    if slot == "mamba":
        x = x + ssm.mamba_forward(p["mix"], layers.rmsnorm(p["ln"], x), cfg)
    elif slot == "mla":
        x = x + _latent_attention(p["attn"], layers.rmsnorm(p["ln"], x),
                                  cfg, positions, impl)
    else:
        x = x + _self_attention(p["attn"], layers.rmsnorm(p["ln"], x), cfg,
                                slot, positions, impl)
        if slot == "xattn":
            x = x + _cross_attention(p["xatt"],
                                     layers.rmsnorm(p["ln_x"], x),
                                     enc_out, cfg, impl)
    if "ffn" in p:
        x = x + layers.ffn(p["ffn"], layers.rmsnorm(p["ln_f"], x), cfg.ffn_act)
    elif "moe" in p and cfg.moe_impl == "dropless":
        y, load = moe.moe_dropless(
            p["moe"], layers.rmsnorm(p["ln_f"], x), top_k=cfg.moe_top_k,
            act=cfg.ffn_act, offset=cfg.moe_expert_offset,
            score=cfg.moe_score, norm_topk=cfg.moe_norm_topk,
            scale=cfg.moe_routed_scale)
        x = x + y
    elif "moe" in p:
        y, a = moe.moe_ffn(p["moe"], layers.rmsnorm(p["ln_f"], x),
                           top_k=cfg.moe_top_k, act=cfg.ffn_act,
                           capacity_factor=cfg.capacity_factor,
                           impl=cfg.moe_impl)
        x = x + y
        aux = aux + a
    return x, aux, load


def _run_encoder(params, cfg: ModelConfig, enc_embeds, impl: str
                 ) -> jnp.ndarray:
    positions = jnp.arange(enc_embeds.shape[1])

    def body(x, lp):
        x, _, _ = _apply_layer(lp, x, cfg, "bidir", 0, positions, None, impl)
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(body), enc_embeds,
                        params["encoder"]["layers"],
                        unroll=cfg.unroll_scan)
    return layers.rmsnorm(params["encoder"]["final_norm"], x)


def forward(params, cfg: ModelConfig, tokens: jnp.ndarray,
            extra: Optional[jnp.ndarray] = None, impl: str = "auto"
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens: (B, S) -> (logits (B, S, V), aux_loss scalar).

    ``extra`` carries stubbed modality embeddings: whisper frame embeddings
    or VLM patch embeddings, shape (B, S_enc, d_model)."""
    logits, aux, _ = forward_with_loads(params, cfg, tokens, extra, impl)
    return logits, aux


def forward_with_loads(params, cfg: ModelConfig, tokens: jnp.ndarray,
                       extra: Optional[jnp.ndarray] = None,
                       impl: str = "auto"
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """:func:`forward` plus the dropless expert layers' loads: ``(logits,
    aux, loads)``, ``loads`` the ``(n_layers_moe, E_held)`` int32 count of
    (token, slot) pairs per held expert in layer order (shape ``(0, 0)``
    in a model without dropless expert layers)."""
    x = layers.embed(params["embed"], tokens)
    if cfg.arch_type in ("audio",):
        assert extra is not None, "whisper needs encoder frame embeddings"
        enc_out = _run_encoder(params, cfg, extra, impl)
    elif cfg.arch_type == "vlm":
        assert extra is not None, "vlm needs patch embeddings"
        enc_out = extra
    else:
        enc_out = None

    positions = jnp.arange(tokens.shape[1])
    aux = jnp.zeros((), jnp.float32)
    loads = []

    def unrolled(x, aux, layer_p, layer_idx):
        x, a, load = jax.checkpoint(
            functools.partial(_apply_layer, cfg=cfg,
                              slot=cfg.slot(layer_idx), layer_idx=layer_idx,
                              positions=positions, enc_out=enc_out,
                              impl=impl))(layer_p, x)
        if load is not None:
            loads.append(load[None])
        return x, aux + a

    for i in range(cfg.dense_lead):
        x, aux = unrolled(x, aux, params["lead"][f"l{i}"], i)

    def body(carry, period_p):
        x, aux = carry
        period_loads = []
        for j, slot in enumerate(cfg.layer_pattern):
            x, a, load = _apply_layer(period_p[f"s{j}"], x, cfg, slot,
                                      cfg.dense_lead + j, positions, enc_out,
                                      impl)
            aux = aux + a
            if load is not None:
                period_loads.append(load)
        return (x, aux), (jnp.stack(period_loads) if period_loads else None)

    (x, aux), period_loads = jax.lax.scan(
        jax.checkpoint(body), (x, aux), params["periods"],
        unroll=cfg.unroll_scan)
    if period_loads is not None:
        loads.append(period_loads.reshape(-1, period_loads.shape[-1]))
    for t in range(cfg.n_tail):
        x, aux = unrolled(x, aux, params["tail"][f"t{t}"],
                          cfg.dense_lead + cfg.n_periods * cfg.period + t)

    x = layers.rmsnorm(params["final_norm"], x)
    if cfg.logits_dtype == "bfloat16":
        x = x.astype(jnp.bfloat16)
        emb = jax.tree_util.tree_map(lambda w: w.astype(jnp.bfloat16),
                                     params.get("lm_head",
                                                params["embed"]))
    else:
        emb = params.get("lm_head", params["embed"])
    if cfg.tie_embeddings:
        logits = layers.unembed(emb, x)
    else:
        logits = layers.linear(emb, x)
    if cfg.logit_softcap > 0:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    loads = (jnp.concatenate(loads) if loads
             else jnp.zeros((0, 0), jnp.int32))
    return logits, aux, loads
