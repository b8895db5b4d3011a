"""The one train-step metrics schema every execution path shares.

Before this module, four near-duplicate ``metrics = {...}`` dicts lived
in ``repro.training.trainer`` (sync + async flat paths),
``repro.dist.train`` and ``repro.dist.async_train`` — and their key sets
had already drifted (``staleness_excess`` existed only on the sharded
async path, ``step_scale`` only on reputation-carrying ones).  All four
now assemble their dicts through :func:`core_metrics` /
:func:`async_extras`, so a metric name or dtype can only change here,
and :data:`METRIC_SCHEMA` is the canonical catalog the exporters, the
dashboard (``scripts/obs_report.py``) and the cross-path consistency
test validate against.

Every builder keeps the exact expressions the paths used before the
unification — values are bitwise what they were, only the assembly is
shared.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

__all__ = ["METRIC_SCHEMA", "async_extras", "core_metrics",
           "global_norm", "moe_metrics", "selection_weight"]

#: canonical metric catalog: name -> (paths, description).  ``paths`` is
#: a ``/``-joined subset of {sync, async} x {flat, dist}; ``all`` means
#: every train path emits it.
METRIC_SCHEMA: Dict[str, tuple] = {
    "loss": ("all", "mean honest-worker training loss at step start"),
    "byz_weight": ("all", "total selection weight landing on the "
                          "injected Byzantine rows (0 when f == 0)"),
    "agg_dev": ("all", "L2 distance between the emitted aggregate and "
                       "the honest mean (the poisoning-leeway probe)"),
    "grad_norm": ("all", "global L2 norm of the emitted aggregate"),
    "step_scale": ("reputation", "scalar step-size multiplier from "
                                 "carried trust (reputation-* rules "
                                 "with spec.rep_lr set)"),
    "staleness_mean": ("async", "mean per-worker slot age at "
                                "aggregation time"),
    "staleness_max": ("async", "oldest slot age in the aggregated bus"),
    "staleness_excess": ("async", "max overshoot beyond the bounded-"
                                  "staleness bound tau (0 = bound held)"),
    "delivered": ("async", "worker slots refreshed this step"),
    "moe_held_tokens": ("moe", "(token, slot) pairs routed to the held "
                               "experts, summed over workers and dropless "
                               "expert layers"),
    "moe_load_max_over_mean": ("moe", "largest (worker, layer, held "
                                      "expert) load over the mean"),
    "moe_idle_experts": ("moe", "(worker, layer, held expert) triples no "
                                "token reached: their gradient rows are "
                                "exactly zero"),
}


def global_norm(tree) -> jnp.ndarray:
    """Global L2 norm of a pytree, accumulated per leaf in fp32.

    One squared-sum contraction per leaf — never materializes a flat
    vector, so leaf shardings survive (the sharded engine's invariant).

    Args:
      tree: any pytree of arrays.

    Returns:
      fp32 scalar ``sqrt(sum_leaves sum(x^2))``.
    """
    total = jnp.zeros((), jnp.float32)
    for leaf in jax.tree_util.tree_leaves(tree):
        x = leaf.astype(jnp.float32)
        total = total + jnp.sum(x * x)
    return jnp.sqrt(total)


def selection_weight(selected: jnp.ndarray, n_honest: int) -> jnp.ndarray:
    """Total selection weight on the Byzantine rows (``byz_weight``).

    The stacked protocol appends the ``f`` injected rows after the
    ``n_honest`` honest ones, so their selection mass is the tail sum.

    Args:
      selected: ``(n,)`` per-worker selection mask/weights from the
        rule's result.
      n_honest: honest row count (static).

    Returns:
      fp32-compatible scalar — the tail sum when Byzantine rows exist,
      else a float32 zero (the historic both-paths convention).
    """
    if selected.shape[0] > n_honest:
        return jnp.sum(selected[n_honest:])
    return jnp.zeros((), jnp.float32)


def core_metrics(*, loss, grad_norm, agg_dev, byz_weight,
                 step_scale: Optional[jnp.ndarray] = None) -> Dict:
    """Assemble the four-key core metrics dict every train path emits.

    Args:
      loss: scalar training loss.
      grad_norm: scalar aggregate norm (``global_norm`` on the tree
        paths, ``jnp.linalg.norm`` on the flat ones).
      agg_dev: scalar aggregate-to-honest-mean deviation.
      byz_weight: scalar Byzantine selection mass
        (:func:`selection_weight`).
      step_scale: optional reputation step-size multiplier; included
        only when the path carries reputation (``None`` omits the key,
        preserving each path's historic key set).

    Returns:
      Dict with the canonical :data:`METRIC_SCHEMA` names.
    """
    metrics = {"loss": loss, "byz_weight": byz_weight,
               "agg_dev": agg_dev, "grad_norm": grad_norm}
    if step_scale is not None:
        metrics["step_scale"] = step_scale
    assert set(metrics) <= set(METRIC_SCHEMA)
    return metrics


def moe_metrics(loads: jnp.ndarray) -> Dict:
    """The dropless expert layers' load metrics.

    Args:
      loads: ``(n_workers, n_layers, E_held)`` int (token, slot) pairs
        per held expert (``forward_with_loads``, stacked over workers).

    Returns:
      Dict with ``moe_held_tokens``, ``moe_load_max_over_mean`` and
      ``moe_idle_experts``.
    """
    f = loads.astype(jnp.float32)
    return {"moe_held_tokens": jnp.sum(f),
            "moe_load_max_over_mean": jnp.max(f) / jnp.maximum(
                jnp.mean(f), 1e-9),
            "moe_idle_experts": jnp.sum(loads == 0).astype(jnp.float32)}


def async_extras(staleness: jnp.ndarray, excess: jnp.ndarray,
                 deliver: jnp.ndarray) -> Dict:
    """The four extra metrics the asynchronous paths add.

    Args:
      staleness: ``(n,)`` int per-worker slot age ``t - bus.versions``.
      excess: ``(n,)`` int per-worker overshoot of the bounded-staleness
        bound (``repro.dist.async_train.staleness_excess``).
      deliver: ``(n,)`` bool delivery mask of this step.

    Returns:
      Dict with ``staleness_mean`` / ``staleness_max`` /
      ``staleness_excess`` / ``delivered``, all fp32 scalars (the
      historic expressions, now shared by the flat and sharded async
      steps — ``staleness_excess`` used to exist only on the sharded
      one).
    """
    metrics = {
        "staleness_mean": jnp.mean(staleness.astype(jnp.float32)),
        "staleness_max": jnp.max(staleness).astype(jnp.float32),
        "staleness_excess": jnp.max(excess).astype(jnp.float32),
        "delivered": jnp.sum(deliver).astype(jnp.float32),
    }
    assert set(metrics) <= set(METRIC_SCHEMA)
    return metrics
