"""Seeded weights, made on the device in one jitted call.

A configuration's reference module gives every parameter's path and
shape (``param_shapes``) and how to draw it (``init_rule``); this module
turns that into a nested dict of arrays from ``--seed``.  Each leaf's
stream is keyed by the seed and a checksum of the leaf's path, so the
program under test and the reference draw identical weights without one
handing them to the other.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, salt: int = 0):
    """A PRNG key from any non-negative integer seed (64 bits are read)."""
    key = jax.random.PRNGKey(salt)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def _draw(key, shape, rule):
    kind, arg = rule
    if kind == "normal":
        return arg * jax.random.normal(key, shape, jnp.float32)
    if kind == "one":
        return 1.0 + arg * jax.random.normal(key, shape, jnp.float32)
    u = jax.random.uniform(key, shape, jnp.float32)
    lo, hi = np.log(arg[0]), np.log(arg[1])
    if kind == "log_uniform":                   # log of a uniform in [lo, hi]
        return jnp.log(jnp.exp(lo) + u * (jnp.exp(hi) - jnp.exp(lo)))
    if kind == "inv_softplus_log_uniform":      # softplus^-1(log-uniform)
        dt = jnp.exp(lo + u * (hi - lo))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise KeyError(f"unknown init kind {kind!r}")


def nest(flat: dict) -> dict:
    """``{"a/b/c": x}`` -> ``{"a": {"b": {"c": x}}}``."""
    out: dict = {}
    for path, x in flat.items():
        node = out
        *head, last = path.split("/")
        for part in head:
            node = node.setdefault(part, {})
        node[last] = x
    return out


def make_params(ref, c: dict, seed: int, dtype=jnp.float32,
                stack: int = 0, poison_scale: float = 0.0):
    """The configuration's parameters from ``seed``, on the default device.

    Args:
      ref: the configuration's reference module.
      c: the configuration's ``config`` dict.
      seed: the run's seed.
      dtype: the parameters' type.
      stack: if above 0, that many replicas stacked on a leading axis,
        all copies of one draw.
      poison_scale: with ``stack``, the last replica becomes
        ``-poison_scale`` times the mean of the others (a sign-flipped,
        scaled copy).

    Returns:
      A nested dict of arrays.
    """
    shapes = ref.param_shapes(c)
    rules = {p: ref.init_rule(p, s) for p, s in shapes.items()}
    paths = sorted(shapes)

    @jax.jit
    def build(key):
        out = {}
        for p in paths:
            k = jax.random.fold_in(key, zlib.crc32(p.encode()) & 0x7FFFFFFF)
            x = _draw(k, shapes[p], rules[p]).astype(dtype)
            if stack:
                x = jnp.broadcast_to(x[None], (stack,) + x.shape)
                if poison_scale:
                    x = x.at[-1].set((-poison_scale * x[0].astype(jnp.float32))
                                     .astype(dtype))
            out[p] = x
        return out

    return nest(build(seed_key(seed)))
