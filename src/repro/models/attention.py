"""Attention: GQA/MQA with RoPE, full / sliding-window / chunked-local /
bidirectional / cross variants, a naive einsum path and a blockwise
(flash-style, online-softmax) path, plus single-token decode against a KV
cache.

Shapes: q (B, Sq, Hq, D); k (B, Sk, Hkv, D), v (B, Sk, Hkv, Dv) with
Hq = G * Hkv (``Dv`` may differ from ``D``, as in latent attention; the
scores are scaled by ``D``).  Softmax statistics are fp32 regardless of
input dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# -- RoPE ---------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
         interleave: bool = False) -> jnp.ndarray:
    """x: (..., S, H, D), positions: (S,) or broadcastable.

    Rotates the pairs ``(i, i + D/2)`` (half-split), or with ``interleave``
    the pairs ``(2i, 2i + 1)``, by ``position / theta^(2i/D)``."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs  # (S, half)
    cos = jnp.cos(ang)[..., None, :]  # (S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    xf = x.astype(jnp.float32)
    if interleave:
        pairs = xf.reshape(x.shape[:-1] + (half, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(x.shape).astype(x.dtype)
    xf1, xf2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin],
        axis=-1).astype(x.dtype)


# -- masks --------------------------------------------------------------------

def _mask_bias(q_pos: jnp.ndarray, k_pos: jnp.ndarray, kind: str,
               window: int, chunk: int) -> jnp.ndarray:
    """(Sq, Sk) additive bias: 0 where attendable, NEG_INF elsewhere."""
    qp = q_pos[:, None]
    kp = k_pos[None, :]
    if kind == "bidir":
        ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    elif kind == "cross":
        ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    else:
        ok = kp <= qp  # causal
        if kind == "swa" and window > 0:
            ok &= (qp - kp) < window
        elif kind == "chunked" and chunk > 0:
            ok &= (qp // chunk) == (kp // chunk)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# -- naive path ---------------------------------------------------------------

def _gqa_scores(q: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """-> (B, Hkv, G, Sq, Sk) fp32 scores."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    return jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                      preferred_element_type=jnp.float32)


def attention_naive(q, k, v, *, kind: str = "attn", window: int = 0,
                    chunk: int = 0, q_offset=0) -> jnp.ndarray:
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scores = _gqa_scores(q, k) / jnp.sqrt(d).astype(jnp.float32)
    q_pos = q_offset + jnp.arange(sq)
    k_pos = jnp.arange(sk)
    bias = _mask_bias(q_pos, k_pos, kind, window, chunk)
    scores = scores + bias[None, None, None]
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(v.dtype), v)
    return out.reshape(b, sq, hq, v.shape[-1])


# -- blockwise (flash-style) path ----------------------------------------------

def attention_blockwise(q, k, v, *, kind: str = "attn", window: int = 0,
                        chunk: int = 0, q_offset=0, block_q: int = 1024,
                        block_k: int = 1024) -> jnp.ndarray:
    """Online-softmax attention, O(block_q * block_k) live scores.

    Outer static loop over q blocks; for causal/local kinds, k blocks that a
    q block can never attend to are *statically skipped* (block-sparsity for
    sliding-window / chunked layouts), cutting both FLOPs and memory traffic.
    """
    b, sq, hq, d = q.shape
    sk, hkv, dv = k.shape[1], k.shape[2], v.shape[-1]
    g = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    pad_q = (-sq) % block_q
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    pad_k = (-sk) % block_k
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq = q.shape[1] // block_q
    nk = k.shape[1] // block_k
    scale = 1.0 / float(d) ** 0.5

    k_pos_all = jnp.arange(k.shape[1])
    outs = []
    static_offset = isinstance(q_offset, int)
    for iq in range(nq):
        qb = q[:, iq * block_q:(iq + 1) * block_q]
        q_pos = q_offset + iq * block_q + jnp.arange(block_q)
        # static k-block range for this q block (block-sparse skipping);
        # only valid when q_offset is a static python int
        lo_blk, hi_blk = 0, nk
        if static_offset and kind in ("attn", "swa", "chunked"):
            q_lo = q_offset + iq * block_q
            q_hi = q_offset + (iq + 1) * block_q - 1
            hi_blk = min(nk, (q_hi // block_k) + 1)           # causal
            if kind == "swa" and window > 0:
                lo_blk = max(0, (q_lo - window + 1) // block_k)
            elif kind == "chunked" and chunk > 0:
                lo_blk = max(0, ((q_lo // chunk) * chunk) // block_k)
        m = jnp.full((b, block_q, hkv, g), NEG_INF, jnp.float32)
        l = jnp.zeros((b, block_q, hkv, g), jnp.float32)
        acc = jnp.zeros((b, block_q, hkv, g, dv), jnp.float32)

        def kv_step(carry, ik):
            m, l, acc = carry
            kb = jax.lax.dynamic_slice_in_dim(k, ik * block_k, block_k, 1)
            vb = jax.lax.dynamic_slice_in_dim(v, ik * block_k, block_k, 1)
            k_pos = ik * block_k + jnp.arange(block_k)
            s = jnp.einsum("bqhgd,bkhd->bqhgk",
                           qb.reshape(b, block_q, hkv, g, d), kb,
                           preferred_element_type=jnp.float32) * scale
            bias = _mask_bias(q_pos, k_pos, kind, window, chunk)
            s = s + bias[None, :, None, None, :]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p.astype(vb.dtype), vb
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        n_blocks = hi_blk - lo_blk
        if n_blocks <= 0:
            outs.append(jnp.zeros((b, block_q, hq, dv), q.dtype))
            continue
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m, l, acc), lo_blk + jnp.arange(n_blocks))
        o = acc / jnp.maximum(l[..., None], 1e-30)
        outs.append(o.reshape(b, block_q, hq, dv).astype(q.dtype))
    out = jnp.concatenate(outs, axis=1)
    return out[:, :sq]


def attention(q, k, v, *, kind: str = "attn", window: int = 0,
              chunk: int = 0, q_offset=0, impl: str = "auto") -> jnp.ndarray:
    if impl == "auto":
        impl = "blockwise" if max(q.shape[1], k.shape[1]) > 8192 else "naive"
    fn = attention_blockwise if impl == "blockwise" else attention_naive
    return fn(q, k, v, kind=kind, window=window, chunk=chunk,
              q_offset=q_offset)


# -- decode (single new token against a cache) ---------------------------------

def decode_attention(q1: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray,
                     valid_len: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """q1: (B, 1, Hq, D); caches: (B, S, Hkv, D).  Attends to the whole
    cache (or the first ``valid_len`` entries)."""
    b, _, hq, d = q1.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    qg = q1.reshape(b, 1, hkv, g, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(d).astype(jnp.float32)
    if valid_len is not None:
        mask = jnp.arange(s)[None, :] < valid_len[:, None]  # (B, S)
        scores = jnp.where(mask[:, None, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(v_cache.dtype), v_cache)
    return out.reshape(b, 1, hq, d)


# -- verify (a block of new tokens against a cache, causal) ---------------------

def verify_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray,
                     q_valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Multi-query decode attention for speculative verify blocks.

    ``q``: (B, Sq, Hq, D) — a block of ``Sq`` new-token queries; caches:
    (B, L, Hkv, D).  Each query ``j`` attends to the first
    ``q_valid[:, j]`` cache entries (per-query causal prefix — the block's
    own keys must already be written into the cache).  ``q_valid=None``
    attends to the whole cache (the cross-attention case).

    At ``Sq = 1`` with ``q_valid = valid_len[:, None]`` this computes
    exactly what :func:`decode_attention` computes — the single-token
    decode step is the degenerate verify block.
    """
    b, sq, hq, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(d).astype(jnp.float32)
    if q_valid is not None:
        mask = jnp.arange(s)[None, None, :] < q_valid[:, :, None]  # (B,Sq,S)
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(v_cache.dtype), v_cache)
    return out.reshape(b, sq, hq, d)
