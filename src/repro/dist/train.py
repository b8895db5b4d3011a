"""The sharded Byzantine train step.

One jit-able function runs the paper's full protocol: per-worker
forward/backward (vmap over the leading worker axis of the batch),
in-graph Byzantine injection on the stacked gradient tree, tree-aware
robust aggregation, optimizer update.  Sharding enters only through the
input/output shardings — the identical step function executes unsharded
on a single device (the semantics reference of ``tests/test_dist.py``)
and GSPMD-partitioned on a pod: the worker axis lives on ``data``, the
parameters on ``model``, and the per-leaf Gram contractions of
``repro.dist.robust`` become local partial products plus an (n, n)
all-reduce.

The single-host flat-matrix reference lives in ``repro.training.trainer``.
The asynchronous variant of this step — the same protocol without the
per-step barrier, aggregating a ``GradientBus`` of versioned per-worker
slots under bounded staleness — is this step's body,
``make_step_body``, with the bus stage of ``repro.dist.async_train``
between injection and aggregation (bitwise this step at
``async_tau = 0``).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.agg.reputation import reputation_tail, tree_reputation_scores
from repro.agg.specs import AggSpec
from repro.agg.state import init_state
from repro.core.attacks import DELAY_ATTACKS
from repro.dist.robust import distributed_aggregate, inject_byzantine
from repro.models.config import ModelConfig
from repro.models.transformer import forward_with_loads
from repro.obs.schema import (core_metrics, global_norm, moe_metrics,
                              selection_weight)
from repro.obs.trace import named_span
from repro.optim import Optimizer

__all__ = ["init_agg_state", "make_loss_fn", "make_step_body",
           "make_train_step"]


def init_agg_state(spec: AggSpec, params, n_workers: int):
    """Zeroed ``AggState`` for a stateful GAR on the sharded path.

    Args:
      spec: the protocol spec (``gar`` / ``history_window`` select the
        rule and its window).
      params: the parameter pytree (or a ``ShapeDtypeStruct`` tree —
        only shapes are read, so this composes with ``jax.eval_shape``).
      n_workers: worker count, the leading axis of the gradient stacks.

    Returns:
      An ``AggState`` sized for per-worker gradient stacks of
      ``params``'s shapes, or ``None`` when the rule is stateless.
    """
    rule = spec.rule()
    if not rule.stateful:
        return None
    template = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct((n_workers,) + tuple(p.shape),
                                       p.dtype), params)
    return init_state(rule, template, flat=False)


def make_loss_fn(cfg: ModelConfig, impl: str = "auto",
                 with_loads: bool = False) -> Callable:
    """Token-level cross-entropy (fp32 logsumexp) plus the model's aux
    loss (MoE load balancing).  ``loss_fn(params, tokens, labels, extra)``;
    with ``with_loads`` it returns ``(loss, loads)``, the dropless expert
    layers' loads (``forward_with_loads``), for ``has_aux``.
    """

    def loss_fn(params, tokens, labels, extra=None):
        logits, aux, loads = forward_with_loads(params, cfg, tokens, extra,
                                                impl=impl)
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        loss = jnp.mean(logz - ll) + aux
        return (loss, loads) if with_loads else loss

    return loss_fn


def make_train_step(cfg: ModelConfig, spec: AggSpec,
                    optimizer: Optimizer, impl: str = "auto",
                    mesh=None) -> Callable:
    """Build the jit-able sharded Byzantine train step.

    Stateless GARs get the historic signature ``step(params, opt_state,
    batch) -> (params, opt_state, metrics)``; when ``spec.gar`` resolves
    to a stateful rule (``buffered-*`` / ``centered_clip_momentum`` /
    ``reputation-*``) the step becomes ``step(params, opt_state, batch,
    agg_state) -> (params, opt_state, metrics, agg_state)`` with the
    ``AggState`` carried by the caller (see ``init_agg_state``) —
    stateless runs pay nothing.  ``reputation-*`` runs additionally
    honor ``spec.aux_batch`` and ``spec.rep_lr`` through
    ``repro.agg.reputation.reputation_tail`` (the scale is reported as
    ``metrics["step_scale"]``).

    batch: ``{"tokens", "labels"[, "extra"]}`` with a leading worker axis
    ``(n_workers, per_worker_batch, ...)`` on every entry.  All n workers
    compute real gradients; when an attack is configured the last ``f``
    are overwritten in-graph by the omniscient adversary (it reads the
    honest gradients first, per the paper's threat model).

    ``mesh`` is only consulted by the Pallas distance backend (it pins the
    ``shard_map`` layout of the distance pass); the XLA backend keeps the
    step mesh-agnostic exactly as before — sharding enters via the
    input/output shardings the caller jits with.
    """
    run_step = make_step_body(cfg, spec, optimizer, impl, mesh)
    if spec.rule().stateful:
        return run_step

    def step(params, opt_state, batch):
        return run_step(params, opt_state, batch, None)[:3]

    return step


def make_step_body(cfg: ModelConfig, spec: AggSpec, optimizer: Optimizer,
                   impl: str = "auto", mesh=None,
                   bus: Optional[Callable] = None) -> Callable:
    """The one body of the sharded train steps, always 4-ary:
    ``step(params, opt_state, batch, agg_state) -> (params, opt_state,
    metrics, agg_state)``.

    Its stages, each under a scope: per-worker gradients
    (``train/grads``), injection (``train/inject``), the aggregation
    (``agg/...``), the reputation tail and the optimizer
    (``train/optimizer``), the diagnostics (``train/diagnostics``).
    ``bus`` is ``None`` for the synchronous step; the asynchronous one
    passes ``repro.dist.async_train.bus_stage`` (passed in: that module
    imports this one), run under ``train/bus`` before the aggregation,
    and carries the bus in ``agg_state``.
    """
    vgl = jax.value_and_grad(make_loss_fn(cfg, impl, with_loads=True),
                             has_aux=True)
    # a dropless expert layer's grouped matmul cannot be batched over
    # workers that route apart (``moe.moe_dropless``): one after another
    per_worker = ((lambda fn, *xs: jax.lax.map(lambda a: fn(*a), xs))
                  if cfg.moe_impl == "dropless" else
                  (lambda fn, *xs: jax.vmap(fn)(*xs)))
    rule = spec.rule()
    stateful = rule.stateful
    reputed = "reputation" in rule.state_fields

    def run_step(params, opt_state, batch, agg_state):
        tokens, labels = batch["tokens"], batch["labels"]
        extra = batch.get("extra")
        n = tokens.shape[0]
        spec.validate(n, distributed=True)
        f = spec.f
        n_h = n - f

        # each phase under one scope placed outside its transforms, so
        # the backward pass reads ``train/grads/vmap(transpose(jvp()))``;
        # the aggregation opens ``agg/...`` of its own
        with named_span("train/grads"):
            if extra is None:
                (losses, loads), grads = per_worker(
                    lambda t, l: vgl(params, t, l), tokens, labels)
            else:
                (losses, loads), grads = per_worker(
                    lambda t, l, e: vgl(params, t, l, e), tokens, labels,
                    extra)

        attacked = spec.attack != "none" and f > 0
        if attacked:
            with named_span("train/inject"):
                key = jax.random.fold_in(jax.random.PRNGKey(spec.seed),
                                         opt_state["step"])
                akw = dict(spec.attack_kwargs)
                akw.setdefault("gar_name", spec.gar)
                # the asynchronous runtime's attacks count bus steps
                t = opt_state["step"] if bus is None else agg_state.step
                if bus is not None and spec.attack in DELAY_ATTACKS:
                    akw.setdefault("prev", jax.tree_util.tree_map(
                        lambda l: l[n_h:], agg_state.bus.grads))
                grads = inject_byzantine(grads, f, spec.attack, key=key,
                                         step=t, **akw)

        if bus is not None:
            with named_span("train/bus"):
                agg_state, bus_metrics = bus(spec, agg_state, grads, n_h,
                                             attacked)
            grads = agg_state.bus.grads

        out = distributed_aggregate(
            grads, spec.f_declared, spec.effective_gar,
            agg_dtype=spec.agg_dtype,
            distance_backend=spec.distance_backend, mesh=mesh,
            state=agg_state if stateful else None,
            history_window=spec.history_window,
            rep_lr=spec.rep_lr, rep_decay=spec.rep_decay)
        agg, res = out[0], out[1]
        new_agg_state = out[2] if stateful else agg_state

        with named_span("train/optimizer"):
            step_scale = None
            if reputed:
                new_agg_state, agg, step_scale = reputation_tail(
                    spec, agg_state.reputation, new_agg_state, agg,
                    lambda: tree_reputation_scores(
                        jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(
                            vgl(params, *spec.aux_batch)[1])))
            new_params, new_state = optimizer.update(agg, opt_state, params)

        with named_span("train/diagnostics"):
            honest_mean = jax.tree_util.tree_map(
                lambda g: jnp.mean(g[:n_h].astype(jnp.float32), axis=0), grads)
            dev = jax.tree_util.tree_map(
                lambda a, m: a.astype(jnp.float32) - m, agg, honest_mean)
            metrics = core_metrics(
                loss=jnp.mean(losses[:n_h]),
                grad_norm=global_norm(agg),
                agg_dev=global_norm(dev),
                byz_weight=selection_weight(res.selected, n_h),
                step_scale=step_scale)
            if loads.size:
                metrics.update(moe_metrics(loads))
        if bus is not None:
            metrics.update(bus_metrics)
        return new_params, new_state, metrics, new_agg_state

    return run_step
