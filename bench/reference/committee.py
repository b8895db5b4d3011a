"""Plain float32 reference of one robust training step of a committee.

The protocol of El Mhamdi, Guerraoui and Rouhault (ICML 2018), as the
cell configures it:

1. every worker computes its gradient on its own sequences;
2. the last ``f`` workers are replaced by the omniscient L-infinity
   adversary: the honest mean plus ``margin * delta_bar`` on every
   coordinate, where ``delta_bar = 2 / sqrt(pi) * mean over coordinates
   of the honest workers' standard deviation`` (the paper's section B
   estimate of the per-coordinate leeway);
3. Bulyan over Krum: ``theta = n - 2f`` rounds of Krum on the remaining
   workers (each scores a worker by the sum of squared distances to its
   ``max(1, n_rem - f - 2)`` nearest remaining neighbours and moves the
   lowest score, first index on ties, into the selection), then per
   coordinate the mean of the ``beta = theta - 2f`` selected values
   closest to the coordinate-wise median (the lower middle value);
4. AdamW with bias correction and decoupled weight decay.

Gradients are pytrees of arrays with a leading worker axis; distances
are summed over all leaves, the coordinate phase runs per leaf.  Each
piece is a separate small program so that a full-size step fits one chip
after the program under test has freed its memory.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _std_sum(h):
    return jnp.sum(jnp.std(h.astype(jnp.float32), axis=0))


@partial(jax.jit, static_argnums=(1,), donate_argnums=(0,))
def _replace_last(g, f, value):
    return jnp.concatenate(
        [g[:-f], jnp.broadcast_to(value.astype(g.dtype), (f,) + g.shape[1:])])


def omniscient_linf(grads, f: int, margin: float):
    """Replace the last ``f`` workers of every leaf (step 2)."""
    leaves, tree = jax.tree_util.tree_flatten(grads)
    count = sum(math.prod(g.shape[1:]) for g in leaves)
    delta_bar = (2.0 / math.sqrt(math.pi)
                 * sum(float(_std_sum(g[:-f])) for g in leaves) / count)
    out = [_replace_last(g, f, jnp.mean(g[:-f].astype(jnp.float32), 0)
                         + margin * delta_bar) for g in leaves]
    return jax.tree_util.tree_unflatten(tree, out)


@jax.jit
def _sq_dists(g):
    x = g.reshape(g.shape[0], -1).astype(jnp.float32)
    return jnp.stack([jnp.sum(jnp.square(x - x[i]), axis=1)
                      for i in range(x.shape[0])])


def sq_dists(grads) -> np.ndarray:
    """``(n, n)`` squared distances over all coordinates of all leaves."""
    return sum(np.asarray(_sq_dists(g), np.float64)
               for g in jax.tree_util.tree_leaves(grads))


def krum_rounds(d2: np.ndarray, f: int) -> list:
    """Bulyan's selection (step 3): ``theta`` worker indices in order."""
    n = d2.shape[0]
    remaining = list(range(n))
    picked = []
    for _ in range(n - 2 * f):
        k = max(1, len(remaining) - f - 2)
        scores = []
        for i in remaining:
            others = sorted(d2[i, j] for j in remaining if j != i)
            scores.append(sum(others[:k]))
        best = remaining[int(np.argmin(scores))]
        picked.append(best)
        remaining.remove(best)
    return picked


@partial(jax.jit, static_argnums=(1,))
def _closest_to_median(sel, beta):
    s = jnp.sort(sel, axis=0)
    med = s[(sel.shape[0] - 1) // 2]
    order = jnp.argsort(jnp.abs(sel - med), axis=0)[:beta]
    return jnp.mean(jnp.take_along_axis(sel, order, axis=0), axis=0)


def bulyan_krum(grads, f: int):
    """``(aggregate pytree in float32, selected indices)`` (step 3)."""
    picked = krum_rounds(sq_dists(grads), f)
    beta = len(picked) - 2 * f
    idx = jnp.asarray(picked)
    agg = jax.tree_util.tree_map(
        lambda g: _closest_to_median(jnp.take(g, idx, axis=0)
                                     .astype(jnp.float32), beta), grads)
    return agg, picked


@partial(jax.jit, static_argnums=(5, 6, 7), donate_argnums=(0, 1, 2))
def _adamw_leaf(p, m, v, g, t, lr, wd, b1_b2_eps):
    b1, b2, eps = b1_b2_eps
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - b1 ** t)
    vh = v / (1 - b2 ** t)
    pf = p.astype(jnp.float32)
    new = pf - lr * (mh / (jnp.sqrt(vh) + eps) + wd * pf)
    return new.astype(p.dtype), m, v


def adamw(params, m, v, agg, t: int, opt: dict):
    """One AdamW step (step 4) at step number ``t`` (1-based)."""
    hp = (opt["b1"], opt["b2"], opt["eps"])
    out = jax.tree_util.tree_map(
        lambda p, mm, vv, g: _adamw_leaf(p, mm, vv, g, jnp.float32(t),
                                         opt["lr"],
                                         opt["weight_decay"], hp),
        params, m, v, agg)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


@jax.jit
def _norms(leaves):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in leaves]


@jax.jit
def _change_norms(new, old):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
            for x, y in zip(new, old)]


def leaf_norms(tree) -> dict:
    """``{leaf path: float32 L2 norm}`` of a pytree."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.device_get(_norms([x for _, x in flat]))
    return {jax.tree_util.keystr(k): float(v)
            for (k, _), v in zip(flat, norms)}


def change_norms(new, old) -> dict:
    """``{leaf path: ||new - old||}`` leaf by leaf."""
    flat = jax.tree_util.tree_flatten_with_path(new)[0]
    norms = jax.device_get(_change_norms(
        [x for _, x in flat], jax.tree_util.tree_leaves(old)))
    return {jax.tree_util.keystr(k): float(v)
            for (k, _), v in zip(flat, norms)}


def run_steps(model, c: dict, params, batches: list, *, f: int,
              margin: float, opt: dict, dtype=jnp.float32,
              half_batch: bool = False) -> dict:
    """The committee's first ``len(batches)`` steps from ``params``.

    Each batch is ``{"tokens", "labels"}`` of ``(n, sequences, S)``
    integers.  ``dtype`` is the precision of parameters, activations and
    gradients (the optimizer's moments stay float32); ``half_batch``
    computes every gradient on the first half of each sequence only.

    Returns ``{"loss": [honest mean loss per step], "agg_norms": {leaf:
    norm of the first step's aggregate}, "params": the parameters after
    the last step}``.
    """
    params = jax.tree_util.tree_map(lambda p: p.astype(dtype), params)

    def seq_loss(p, toks, labs):
        return jnp.mean(jnp.stack([model.loss(p, c, t, l)
                                   for t, l in zip(toks, labs)]))

    vg = jax.jit(jax.value_and_grad(seq_loss))
    zeros = lambda: jax.tree_util.tree_map(  # noqa: E731
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    m, v = zeros(), zeros()
    out = {"loss": []}
    for t, batch in enumerate(batches, start=1):
        toks, labs = np.asarray(batch["tokens"]), np.asarray(batch["labels"])
        if half_batch:
            half = toks.shape[-1] // 2
            toks, labs = toks[..., :half], labs[..., :half]
        losses, grads = [], []
        for w in range(toks.shape[0]):         # one worker at a time
            lw, gw = vg(params, jnp.asarray(toks[w]), jnp.asarray(labs[w]))
            losses.append(float(lw))
            grads.append(gw)
        grads = jax.tree_util.tree_map(lambda *g: jnp.stack(g), *grads)
        out["loss"].append(float(np.mean(losses[:-f])))
        grads = omniscient_linf(grads, f, margin)
        agg, _ = bulyan_krum(grads, f)
        del grads
        if t == 1:
            out["agg_norms"] = leaf_norms(agg)
        params, m, v = adamw(params, m, v, agg, t, opt)
    out["params"] = params
    return out
