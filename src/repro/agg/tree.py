"""Tree-path implementations of the stateless aggregation rules.

These are the bodies that used to live inline in the ``if gar == ...``
chain of ``repro.dist.robust.distributed_aggregate``.  Each consumes a
``TreeContext`` prepared by that engine (leaves with a leading worker
axis, a lazy distance-matrix closure over the configured backend, the
windowed coordinate phase) and returns a ``TreeAgg`` — so the rule
bodies stay mesh- and backend-agnostic while the engine keeps owning
the sharded machinery.

Each body runs inside the engine's ``agg`` scope and names its own
work: the rule's selection under ``select``, its coordinate-wise
combination under ``coordinate``, so a profile reads
``agg/select/...`` and ``agg/coordinate/...``.  ``ctx.dists()`` and
``ctx.coordinate_phase`` open ``gram`` and ``coordinate`` of their own,
so rules call them outside their other phases.

Registered via ``@register_tree_impl`` onto the dense rules declared in
``repro.core.gars``; the Bulyan family is attached by the resolver
(``repro.agg.registry``) since its bases are parametric.  The stateful
rules (buffered history, momentum centered-clip) live in
``repro.agg.buffered``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.agg.registry import TreeAgg, TreeContext, register_tree_impl
from repro.core import bulyan as bulyan_lib
from repro.core import gars
from repro.obs.trace import named_span

__all__ = ["bulyan_tree"]


@register_tree_impl("average")
def _average_tree(ctx: TreeContext) -> TreeAgg:
    with named_span("coordinate"):
        agg = [jnp.mean(l.astype(ctx.cdt), axis=0) for l in ctx.leaves]
    return TreeAgg(agg, ctx.uniform(), ctx.zeros())


@register_tree_impl("cwmed")
def _cwmed_tree(ctx: TreeContext) -> TreeAgg:
    with named_span("coordinate"):
        agg = [jnp.median(l.astype(ctx.cdt), axis=0) for l in ctx.leaves]
    return TreeAgg(agg, ctx.uniform(), ctx.zeros())


@register_tree_impl("trimmed_mean")
def _trimmed_mean_tree(ctx: TreeContext) -> TreeAgg:
    with named_span("coordinate"):
        agg = [jnp.mean(jnp.sort(l.astype(ctx.cdt), axis=0)
                        [ctx.f:ctx.n - ctx.f], axis=0) for l in ctx.leaves]
    return TreeAgg(agg, ctx.uniform(), ctx.zeros())


@register_tree_impl("krum")
def _krum_tree(ctx: TreeContext) -> TreeAgg:
    d2 = ctx.dists()
    with named_span("select"):
        scores = gars.krum_scores(d2, jnp.ones((ctx.n,), bool), ctx.f, ctx.n)
        i = jnp.argmin(scores)
    with named_span("coordinate"):
        agg = ctx.take_worker(i)
    with named_span("select"):
        selected = jax.nn.one_hot(i, ctx.n, dtype=ctx.cdt)
    return TreeAgg(agg, selected, scores)


@register_tree_impl("geomed")
def _geomed_tree(ctx: TreeContext) -> TreeAgg:
    d2 = ctx.dists()
    with named_span("select"):
        scores = gars.geomed_scores(d2, jnp.ones((ctx.n,), bool))
        i = jnp.argmin(scores)
    with named_span("coordinate"):
        agg = ctx.take_worker(i)
    with named_span("select"):
        selected = jax.nn.one_hot(i, ctx.n, dtype=ctx.cdt)
    return TreeAgg(agg, selected, scores)


@register_tree_impl("multikrum")
def _multikrum_tree(ctx: TreeContext) -> TreeAgg:
    d2 = ctx.dists()
    with named_span("select"):
        scores = gars.krum_scores(d2, jnp.ones((ctx.n,), bool), ctx.f, ctx.n)
        m = max(1, ctx.n - ctx.f - 2)
        _, top = jax.lax.top_k(-scores, m)
        selected = jnp.zeros((ctx.n,), ctx.cdt).at[top].set(1.0 / m)
    with named_span("coordinate"):
        agg = ctx.weighted_sum(selected)
    return TreeAgg(agg, selected, scores)


@register_tree_impl("brute")
def _brute_tree(ctx: TreeContext) -> TreeAgg:
    n, f = ctx.n, ctx.f
    dist2 = ctx.dists()
    with named_span("select"):
        diam = gars.brute_subset_diameters(dist2, n, f)
        idx = jnp.asarray(gars._subsets(n, n - f))
        best = jnp.argmin(diam)
        chosen = idx[best]
        selected = jnp.zeros((n,), ctx.cdt).at[chosen].set(1.0 / (n - f))
        member = jnp.zeros((len(idx), n), bool).at[
            jnp.arange(len(idx))[:, None], idx].set(True)
        scores = jnp.min(jnp.where(member, diam[:, None], jnp.inf), axis=0)
    with named_span("coordinate"):
        agg = ctx.weighted_sum(selected)
    return TreeAgg(agg, selected, scores)


def bulyan_tree(ctx: TreeContext, base: str = "krum") -> TreeAgg:
    """Distributed Bulyan(base) for the distance-only bases (krum/geomed).

    Phase 1 runs on the (n, n) distance matrix alone
    (``select_indices_from_dists``); phase 2 is the engine's windowed
    coordinate phase, applied per leaf so each leaf keeps its sharding,
    to the theta selected rows taken as dynamic slices of the leaf.

    Args:
      ctx: the engine-prepared tree context.
      base: phase-1 base rule, ``"krum"`` or ``"geomed"`` (bound by the
        resolver when building ``bulyan-<base>`` composites).

    Returns:
      A ``TreeAgg`` whose ``selected`` marks the theta = n - 2f
      phase-1 picks with weight 1.0.
    """
    d2 = ctx.dists()
    with named_span("select"):
        idx = bulyan_lib.select_indices_from_dists(d2, ctx.f, base=base)
    theta = idx.shape[0]
    agg = []
    for leaf in ctx.leaves:
        # theta dynamic row slices, not a gather: XLA fuses them into the
        # coordinate phase's sweep instead of materializing the rows
        with named_span("coordinate"):
            rows = jnp.stack([
                jax.lax.dynamic_index_in_dim(leaf, idx[t], 0,
                                             keepdims=False).astype(ctx.cdt)
                for t in range(theta)])
        agg.append(ctx.coordinate_phase(rows, ctx.f))
    with named_span("select"):
        selected = jnp.zeros((ctx.n,), ctx.cdt).at[idx].set(1.0)
    return TreeAgg(agg, selected, ctx.zeros())
