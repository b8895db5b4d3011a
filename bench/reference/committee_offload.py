"""The committee's reference steps (``committee.run_steps``) for a model
whose seven float32 gradients do not fit on the chip beside its float32
parameters and AdamW state.

The protocol and every piece of arithmetic are ``committee``'s: the same
jitted programs for the honest deviation, the adversary's row, the
distances, Krum's rounds, the coordinate phase and AdamW.  Only where the
gradients live differs: each worker's gradient goes to host memory as
soon as it is computed, and the leaf-wise passes (the deviation, then
the adversary and the distances, then the coordinate phase and AdamW)
put one leaf's ``(n, ...)`` stack on the device at a time.  The chip then
holds the parameters, the moments and one stack, not seven gradients.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import committee as base


def _to_host_stacks(grads: list) -> list:
    """Workers' gradient leaves ``grads[w][i]`` -> ``(n, ...)`` host
    stacks per leaf, freeing each worker's copy as it is stacked."""
    out = []
    for i in range(len(grads[0])):
        out.append(np.stack([g[i] for g in grads]))
        for g in grads:
            g[i] = None
    return out


def run_steps(model, c: dict, params, batches: list, *, f: int,
              margin: float, opt: dict, dtype=jnp.float32,
              half_batch: bool = False) -> dict:
    """As ``committee.run_steps``, with the same arguments and result."""
    params = jax.tree_util.tree_map(lambda p: p.astype(dtype), params)
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    names = [jax.tree_util.keystr(k) for k, _ in flat]
    leaves = [x for _, x in flat]
    del params, flat

    def seq_loss(p, toks, labs):
        return jnp.mean(jnp.stack([model.loss(p, c, t, l)
                                   for t, l in zip(toks, labs)]))

    vg = jax.jit(jax.value_and_grad(seq_loss))
    m = [jnp.zeros(x.shape, jnp.float32) for x in leaves]
    v = [jnp.zeros(x.shape, jnp.float32) for x in leaves]
    hp = (opt["b1"], opt["b2"], opt["eps"])
    out = {"loss": []}
    for t, batch in enumerate(batches, start=1):
        toks, labs = np.asarray(batch["tokens"]), np.asarray(batch["labels"])
        if half_batch:
            half = toks.shape[-1] // 2
            toks, labs = toks[..., :half], labs[..., :half]
        p = jax.tree_util.tree_unflatten(tree, leaves)
        losses, grads = [], []
        for w in range(toks.shape[0]):         # one worker at a time
            lw, gw = vg(p, jnp.asarray(toks[w]), jnp.asarray(labs[w]))
            losses.append(float(lw))
            grads.append([np.asarray(x) for x in
                          jax.tree_util.tree_leaves(gw)])
            del gw
        del p
        out["loss"].append(float(np.mean(losses[:-f])))
        stacks = _to_host_stacks(grads)

        # the omniscient adversary (committee.omniscient_linf), leaf-wise
        count = sum(math.prod(s.shape[1:]) for s in stacks)
        delta_bar = (2.0 / math.sqrt(math.pi)
                     * sum(float(base._std_sum(jnp.asarray(s[:-f])))
                           for s in stacks) / count)
        d2 = 0.0
        for s in stacks:
            g = jnp.asarray(s)
            g = base._replace_last(g, f, jnp.mean(g[:-f].astype(jnp.float32),
                                                  0) + margin * delta_bar)
            s[-f:] = np.asarray(g[-f:])
            d2 = d2 + np.asarray(base._sq_dists(g), np.float64)
            del g

        # Bulyan over Krum (committee.bulyan_krum) and AdamW, leaf-wise
        picked = base.krum_rounds(d2, f)
        beta = len(picked) - 2 * f
        norms = []
        for i, s in enumerate(stacks):
            agg = base._closest_to_median(
                jnp.asarray(s[picked]).astype(jnp.float32), beta)
            stacks[i] = None
            if t == 1:
                norms.append(float(jnp.sqrt(jnp.sum(jnp.square(agg)))))
            leaves[i], m[i], v[i] = base._adamw_leaf(
                leaves[i], m[i], v[i], agg, jnp.float32(t), opt["lr"],
                opt["weight_decay"], hp)
        if t == 1:
            out["agg_norms"] = dict(zip(names, norms))
    out["params"] = jax.tree_util.tree_unflatten(tree, leaves)
    return out
