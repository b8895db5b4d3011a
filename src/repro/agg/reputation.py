"""Reputation-weighted aggregation: the ``reputation-<base>`` family.

Every quorum-bound rule in the registry inherits the paper's worker-count
arithmetic — Krum needs ``n >= 2f + 3``, Bulyan ``n >= 4f + 3`` — so none
of them can even *run* once half the committee is Byzantine.  ByGARS
(Regatti et al., arXiv:2006.13421) shows a different contract: learn a
per-worker **reputation score** from how well each submission agrees with
a trusted signal (the emitted aggregate, or a gradient computed on an
auxiliary clean batch), down-weight low-reputation workers before any
rule runs, and the defense tolerates an *arbitrary* number of attackers —
the one threat-model axis the quorum family cannot express.

``reputation-<base>`` wraps **any** registered base rule through the
unchanged registry (``resolve_rule("reputation-krum")``, nesting with the
``stale-`` / ``buffered-`` / ``fused-`` / ``bulyan-`` families in either
direction).  Per step it:

1. reads per-worker scores ``rep`` from the carried
   :class:`~repro.agg.state.AggState` (``reputation`` field, initialized
   to ones) and normalizes weights ``w = rep / max(rep)`` so the most
   trusted worker keeps scale exactly 1 and nobody is amplified;
2. replaces each worker's row by the **reputation blend**
   ``w_i * g_i + (1 - w_i) * g_w`` where ``g_w`` is the
   reputation-weighted mean — a fully distrusted worker degenerates into
   echoing the trusted consensus instead of submitting a zero row (pure
   scaling cannot defeat a colluding majority: identical colluding rows
   stay a tight selection-winning cluster at any scale, and zeroed rows
   cluster at the origin and freeze training).  Rows with ``w_i == 1``
   pass through untouched, so **uniform reputation reproduces the base
   rule bitwise**;
3. clamps the Byzantine bound to the largest ``f' <= f`` the base's
   quorum admits at this ``n`` (``reputation-<base>`` itself only
   requires ``base.min_n(0)`` workers — the arbitrary-f contract);
4. runs the base rule on the blended stack, then updates the scores by
   an EMA of the cosine agreement between each worker's **raw** row and
   the emitted aggregate:
   ``rep <- clip(rep_decay * ((1 - rep_lr) * rep + rep_lr * s), 0, 1)``
   with ``s = (1 + cos) / 2 in [0, 1]``.

When an auxiliary clean batch is available (``AggSpec(aux_batch=...)``),
the trainer scores agreement against the clean-batch gradient instead —
the ByGARS mechanism proper, and what breaks the bootstrap circularity
under a colluding majority (agreement with an aggregate the colluders
already own would *reward* them).  The same state doubles as the
staleness-adaptive learning-rate tail of Alistarh et al.
(arXiv:1803.08917): :func:`step_size_multiplier` maps the carried scores
to a scalar in ``(0, 1]`` the train steps multiply into the update when
``spec.rep_lr`` is set, so a distrusted committee also takes smaller
steps, not just reweighted ones.

See docs/reputation.md for the threat-model table (which rules survive
which f regime) and the serving-side per-slot ``(n, batch)`` layout.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.agg.registry import AggregatorRule
from repro.agg.state import AggState

__all__ = ["DEFAULT_REP_DECAY", "DEFAULT_REP_LR", "blend_stack",
           "make_reputation", "reputation_scale", "reputation_scores",
           "reputation_tail", "step_size_multiplier",
           "tree_reputation_scores", "update_reputation"]

#: EMA rate of the per-step reputation update (``rep_lr``)
DEFAULT_REP_LR = 0.5

#: multiplicative forgetting factor applied after the EMA (``rep_decay``);
#: 1.0 = no decay — reputation is forgotten only through disagreement
DEFAULT_REP_DECAY = 1.0

_EPS = 1e-12


def reputation_scale(state: AggState) -> jnp.ndarray:
    """Per-worker weights ``w = rep / max(rep)`` in ``(0, 1]``.

    Normalizing by the best-trusted worker means at least one weight is
    exactly 1 (``x / x == 1.0`` in floating point), nobody is ever
    amplified, and a fresh all-ones reputation yields weights of exactly
    1.0 everywhere — the anchor of the bitwise base-identity contract.
    The serving layout ``(n, batch)`` normalizes per slot (max over the
    worker axis, per column).

    Args:
      state: carried ``AggState`` with an allocated ``reputation``
        buffer — ``(n,)`` training layout or ``(n, batch)`` serving
        layout.

    Returns:
      Weights with the same shape as ``state.reputation``, fp32, in
      ``(0, 1]``.
    """
    rep = state.reputation.astype(jnp.float32)
    m = jnp.max(rep, axis=0, keepdims=True)
    return rep / jnp.maximum(m, _EPS)


def reputation_scores(grads: jnp.ndarray, target: jnp.ndarray, *,
                      rep_ndim: int = 1) -> jnp.ndarray:
    """Cosine-agreement scores in ``[0, 1]`` against a trusted target.

    The score is ``(1 + cos(g_i, target)) / 2`` — 1 for a worker aligned
    with the target, 0 for a sign-flipped one, 0.5 for an orthogonal (or
    zero) submission.  Computed in fp32 regardless of input dtypes.

    Args:
      grads: worker-stacked ``(n, *dims)`` submissions (the **raw** rows,
        pre-blend — workers are judged on what they sent).
      target: the trusted signal with shape ``dims`` — the emitted
        aggregate, or an auxiliary clean-batch gradient.
      rep_ndim: rank of the score array — 1 contracts everything after
        the worker axis into one ``(n,)`` score; 2 keeps the second axis
        (the serving layer's per-slot ``(n, batch)`` scores over
        ``(n, batch, vocab)`` logits stacks).

    Returns:
      ``(n,)`` (or ``(n, batch)``) fp32 scores in ``[0, 1]``.
    """
    g = grads.astype(jnp.float32)
    t = target.astype(jnp.float32)
    red = tuple(range(rep_ndim, g.ndim))
    tred = tuple(range(rep_ndim - 1, t.ndim))
    num = jnp.sum(g * t[None], axis=red)
    g2 = jnp.sum(g * g, axis=red)
    t2 = jnp.sum(t * t, axis=tred)
    cos = num / (jnp.sqrt(g2) * jnp.sqrt(t2)[None] + _EPS)
    return 0.5 * (1.0 + cos)


def update_reputation(rep: jnp.ndarray, scores: jnp.ndarray,
                      rep_lr: float = DEFAULT_REP_LR,
                      rep_decay: float = DEFAULT_REP_DECAY) -> jnp.ndarray:
    """One EMA step of the reputation schedule, clipped into ``[0, 1]``.

    ``rep <- clip(rep_decay * ((1 - rep_lr) * rep + rep_lr * scores),
    0, 1)``.  The clip also repairs out-of-range values flowing in from
    a corrupted checkpoint restore — reputation can never amplify
    (``> 1``) or go negative, mirroring the staleness clamp of
    ``repro.agg.staleness.stale_scale``.

    Args:
      rep: current ``(n,)`` / ``(n, batch)`` reputation.
      scores: agreement scores in ``[0, 1]``, same shape
        (:func:`reputation_scores`).
      rep_lr: EMA rate in ``[0, 1]`` — 0 freezes reputation, 1 replaces
        it with the instantaneous score.
      rep_decay: multiplicative forgetting factor in ``(0, 1]`` applied
        after the EMA; values below 1 make trust *erode* unless
        continuously re-earned (the defense against slowly-built-then-
        burned reputation).

    Returns:
      Updated reputation, fp32, clipped into ``[0, 1]``.
    """
    new = (1.0 - rep_lr) * rep.astype(jnp.float32) \
        + rep_lr * scores.astype(jnp.float32)
    return jnp.clip(rep_decay * new, 0.0, 1.0)


def step_size_multiplier(state: AggState) -> jnp.ndarray:
    """Scalar learning-rate multiplier in ``(0, 1]`` from carried trust.

    The mean of the normalized weights ``w = rep / max(rep)``: a fully
    trusted committee multiplies by exactly 1 (bitwise no-op), while a
    committee whose scores have collapsed shrinks the step — the
    staleness-adaptive step-size rule of Alistarh et al. folded onto the
    same state that reweights the stack.  Applied by
    :func:`reputation_tail` when ``spec.rep_lr`` is set.

    Args:
      state: carried ``AggState`` with an allocated ``reputation``
        buffer.

    Returns:
      fp32 scalar in ``(0, 1]``.
    """
    return jnp.mean(reputation_scale(state))


def reputation_tail(spec, rep: jnp.ndarray, state: AggState, agg: Any,
                    clean_scores: Callable[[], jnp.ndarray]
                    ) -> Tuple[AggState, Any, jnp.ndarray]:
    """Every train step's tail after a ``reputation-*`` rule.

    With ``spec.aux_batch`` set, the clean-batch scores replace the
    rule's own agreement update of this step (ByGARS proper: a signal a
    colluding majority cannot vote on); with ``spec.rep_lr`` set, the
    aggregate (a pytree; a flat vector is a one-leaf tree) is scaled by
    :func:`step_size_multiplier` in fp32.  ``rep`` is the reputation the
    step started from, ``state`` the rule's post-aggregation state, and
    ``clean_scores()`` — called only with ``aux_batch`` — the aggregated
    rows' scores in the caller's representation
    (:func:`reputation_scores` or :func:`tree_reputation_scores`).

    Returns:
      ``(state, agg, step_scale)``, the scale 1 without ``rep_lr``.
    """
    if spec.aux_batch is not None:
        lr = DEFAULT_REP_LR if spec.rep_lr is None else spec.rep_lr
        decay = DEFAULT_REP_DECAY if spec.rep_decay is None else spec.rep_decay
        state = state._replace(reputation=update_reputation(
            rep, clean_scores(), lr, decay))
    step_scale = jnp.ones((), jnp.float32)
    if spec.rep_lr:
        step_scale = step_size_multiplier(state)
        agg = jax.tree_util.tree_map(
            lambda a: (a.astype(jnp.float32) * step_scale).astype(a.dtype),
            agg)
    return state, agg, step_scale


def blend_stack(leaf: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Reputation blend of one worker-stacked leaf (bitwise at w == 1).

    Each worker row becomes ``w_i * g_i + (1 - w_i) * g_w`` with ``g_w``
    the reputation-weighted mean over the worker axis — a distrusted row
    degenerates into echoing the trusted consensus.  Rows whose weight is
    exactly 1 pass through untouched (the where-guard below), which is
    what makes uniform reputation reproduce the base rule bitwise.  Also
    used by ``repro.audit.invariants`` to replay the transformation the
    rule body applied.

    Args:
      leaf: worker-stacked ``(n, *dims)`` array (gradient leaf or logits
        stack).
      w: weights in ``[0, 1]`` of shape ``(n,)`` — or ``(n, batch)`` for
        the serving layout — broadcast over the trailing dims.

    Returns:
      The blended stack, same shape and dtype as ``leaf``.
    """
    wr = w.reshape(w.shape + (1,) * (leaf.ndim - w.ndim)).astype(leaf.dtype)
    den = jnp.maximum(jnp.sum(w, axis=0), _EPS).astype(leaf.dtype)
    wmean = jnp.sum(wr * leaf, axis=0) \
        / den.reshape(den.shape + (1,) * (leaf.ndim - 1 - den.ndim))
    # the where-guard (not algebraic simplification) carries the bitwise
    # contract: w == 1 must return the row itself, untouched by -0.0 /
    # rounding artifacts of the blend arithmetic
    return jnp.where(wr == 1.0, leaf, wr * leaf + (1.0 - wr) * wmean[None])


def tree_reputation_scores(leaves: Sequence[jnp.ndarray],
                           agg_leaves: Sequence[jnp.ndarray],
                           rep_ndim: int = 1) -> jnp.ndarray:
    """Tree-path :func:`reputation_scores`: cosine over all leaves at once.

    Accumulates the dot product and both squared norms per leaf (one
    contraction each — never materializing a flat ``(n, d)`` matrix,
    the sharded engine's invariant) and finalizes one global cosine over
    the concatenated coordinate space.  Used by the ``reputation-*``
    tree path and by the train steps' auxiliary clean-batch scoring
    (``AggSpec(aux_batch=...)``).

    Args:
      leaves: worker-stacked ``(n, *dims)`` gradient leaves (flat tree
        order).
      agg_leaves: trusted-target leaves with shapes ``dims`` — the
        emitted aggregate's leaves, or an auxiliary clean gradient's.
      rep_ndim: rank of the score array (see :func:`reputation_scores`).

    Returns:
      ``(n,)`` (or ``(n, batch)``) fp32 scores in ``[0, 1]``.
    """
    num = jnp.zeros((), jnp.float32)
    g2 = jnp.zeros((), jnp.float32)
    t2 = jnp.zeros((), jnp.float32)
    for leaf, agg in zip(leaves, agg_leaves):
        g = leaf.astype(jnp.float32)
        t = jnp.asarray(agg, jnp.float32)
        red = tuple(range(rep_ndim, g.ndim))
        tred = tuple(range(rep_ndim - 1, t.ndim))
        num = num + jnp.sum(g * t[None], axis=red)
        g2 = g2 + jnp.sum(g * g, axis=red)
        t2 = t2 + jnp.sum(t * t, axis=tred)
    cos = num / (jnp.sqrt(g2) * jnp.sqrt(t2)[None] + _EPS)
    return 0.5 * (1.0 + cos)


def _clamp_f(base: AggregatorRule, n: int, f: int) -> int:
    """Largest f' <= f the base quorum admits at this n (trace-time)."""
    f_eff = f
    while f_eff > 0 and base.min_n(f_eff) > n:
        f_eff -= 1
    return f_eff


def make_reputation(name: str, base: AggregatorRule,
                    rep_lr: float = DEFAULT_REP_LR,
                    rep_decay: float = DEFAULT_REP_DECAY) -> AggregatorRule:
    """Build the ``reputation-<base>`` composite around any registered rule.

    The composite is stateful with ``"reputation"`` prepended to the
    base's ``state_fields``.  Its quorum is ``base.min_n(0)`` — a
    *constant* in f, the arbitrary-f contract: the declared Byzantine
    bound is clamped to what the base admits at the actual worker count
    (identity whenever ``f`` already satisfies the base quorum), because
    the defense lives in the reputation blend, not in worker-count
    arithmetic.  A stateful base composes — the same ``AggState``
    carries both the reputation scores and the base's buffers — but
    nesting two reputation layers is rejected by the resolver.

    Args:
      name: composite registry name (``"reputation-<base>"``).
      base: the resolved base rule; its tree implementation is wrapped
        only when it has one.
      rep_lr: EMA rate of the per-step score update
        (:func:`update_reputation`).
      rep_decay: multiplicative forgetting factor of the schedule.

    Returns:
      A stateful :class:`AggregatorRule` with ``min_n = base.min_n(0)``
      (constant in f) and the base's invariants minus ``"trimmed"``
      (the f-trimmed-hull contract is stated at the *declared* f, which
      the clamp may legitimately reduce in the arbitrary-f regime).
    """
    state_fields: Tuple[str, ...] = (
        ("reputation",)
        + tuple(f for f in base.state_fields if f != "reputation"))
    min_n0 = base.min_n(0)

    def dense(grads, f, state):
        f_eff = _clamp_f(base, grads.shape[0], f)
        rep = state.reputation
        w = reputation_scale(state)
        scaled = blend_stack(grads, w.astype(grads.dtype))
        if base.stateful:
            res, state = base.dense_fn(scaled, f_eff, state)
        else:
            res = base.dense_fn(scaled, f_eff)
            state = state._replace(step=state.step + 1)
        scores = reputation_scores(grads, res.gradient, rep_ndim=rep.ndim)
        return res, state._replace(
            reputation=update_reputation(rep, scores, rep_lr, rep_decay))

    tree_fn = None
    if base.tree_fn is not None:
        def tree_fn(ctx, state):
            f_eff = _clamp_f(base, ctx.n, ctx.f)
            rep = state.reputation
            w = reputation_scale(state).astype(ctx.cdt)
            # blend in the accumulation dtype, then restore each leaf's
            # own dtype so the base rule sees the layout it always sees
            # (the round trip is exact at w == 1: the where-guard returns
            # the cast leaf, and casting back is lossless)
            scaled = [blend_stack(l.astype(ctx.cdt), w).astype(l.dtype)
                      for l in ctx.leaves]
            sctx = dataclasses.replace(ctx, leaves=tuple(scaled), f=f_eff)
            if base.stateful:
                out, state = base.tree_fn(sctx, state)
            else:
                out = base.tree_fn(sctx)
                state = state._replace(step=state.step + 1)
            scores = tree_reputation_scores(ctx.leaves, out.leaves,
                                            rep.ndim)
            return out, state._replace(
                reputation=update_reputation(rep, scores, rep_lr,
                                             rep_decay))

    return AggregatorRule(
        name=name, min_n=lambda f: min_n0, dense_fn=dense, tree_fn=tree_fn,
        byzantine_resilient=base.byzantine_resilient, stateful=True,
        state_fields=state_fields, history_window=base.history_window,
        # base invariants hold relative to the *blended* stack (the audit
        # replays the blend); "trimmed" is stated at the declared f and
        # may be weakened by the arbitrary-f clamp, so it is dropped
        invariants=tuple(i for i in base.invariants if i != "trimmed"),
        doc=f"reputation-blended worker stack fed to {base.name} "
            f"(ByGARS-style, arbitrary-f)")
