"""Plain reference of robust ensemble decoding: Bulyan over Krum applied,
position by position, to the stacked logits of every replica.

For one request the reference runs each replica's whole-sequence
forward pass over the prompt followed by the tokens that were served,
stacks the ``(n, S, vocab)`` logits, and aggregates every position's
``(n, vocab)`` slice with the committee's Bulyan (``committee``): Krum
rounds on the replicas' squared distances over the vocabulary, then the
coordinate-wise mean of the ``beta`` values closest to the median.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference.committee import _closest_to_median, krum_rounds


@jax.jit
def _position_dists(stack):
    """``stack``: (n, S, V) -> (S, n, n) squared distances."""
    x = stack.astype(jnp.float32)
    d = jnp.stack([jnp.sum(jnp.square(x - x[i]), axis=-1)
                   for i in range(x.shape[0])])              # (n, n, S)
    return jnp.moveaxis(d, -1, 0)


def aggregate(stack, f: int):
    """``(S, V)`` aggregate of an ``(n, S, V)`` logits stack."""
    d2 = np.asarray(_position_dists(stack), np.float64)
    picks = np.array([krum_rounds(d, f) for d in d2])          # (S, theta)
    sel = jnp.take_along_axis(stack, jnp.asarray(picks.T)[:, :, None], 0)
    return _closest_to_median(sel.astype(jnp.float32), picks.shape[1] - 2 * f)


def served_gap(agg, served) -> float:
    """Widest gap by which a served token's aggregated logit lies below
    the best at its position (``agg``: (T, V), ``served``: (T,))."""
    agg = np.asarray(agg, np.float64)
    got = agg[np.arange(len(served)), np.asarray(served)]
    return float(np.max(agg.max(axis=1) - got))
