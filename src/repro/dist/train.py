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
slots under bounded staleness — lives in ``repro.dist.async_train``
(``make_async_train_step`` reuses ``make_loss_fn`` and reproduces this
step bitwise at ``async_tau = 0``).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.agg.specs import AggSpec
from repro.agg.state import init_state
from repro.dist.robust import distributed_aggregate, inject_byzantine
from repro.models.config import ModelConfig
from repro.models.transformer import forward_with_loads
from repro.obs.schema import (core_metrics, global_norm, moe_metrics,
                              selection_weight)
from repro.obs.trace import named_span
from repro.optim import Optimizer

__all__ = ["DistByzantineSpec", "init_agg_state", "make_loss_fn",
           "make_train_step"]

#: deprecation alias — the sharded spec is now the unified
#: ``repro.agg.AggSpec`` (same fields plus the single-host ones);
#: ``spec.validate(n_workers)`` keeps its historic trace-time call form
#: (the step builders additionally pass ``distributed=True`` to demand a
#: tree implementation — no longer inferred from the explicit count).
DistByzantineSpec = AggSpec


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


# per-leaf fp32 norm accumulation now lives in the shared metrics
# schema; the historic private name stays for the async step's import
_global_norm = global_norm


def make_train_step(cfg: ModelConfig, spec: DistByzantineSpec,
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
    honor ``spec.aux_batch`` (clean-batch ByGARS scoring overrides the
    agreement update) and a set ``spec.rep_lr`` (the aggregate is scaled
    by ``step_size_multiplier`` before the optimizer — reported as
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

        if spec.attack != "none" and f > 0:
            with named_span("train/inject"):
                key = jax.random.fold_in(jax.random.PRNGKey(spec.seed),
                                         opt_state["step"])
                akw = dict(spec.attack_kwargs)
                akw.setdefault("gar_name", spec.gar)
                grads = inject_byzantine(grads, f, spec.attack, key=key,
                                         step=opt_state["step"], **akw)

        out = distributed_aggregate(
            grads, spec.f_declared, spec.effective_gar,
            agg_dtype=spec.agg_dtype,
            distance_backend=spec.distance_backend, mesh=mesh,
            state=agg_state, history_window=spec.history_window,
            rep_lr=spec.rep_lr, rep_decay=spec.rep_decay)
        agg, res = out[0], out[1]
        new_agg_state = out[2] if stateful else None

        with named_span("train/optimizer"):
            step_scale = jnp.ones((), jnp.float32)
            if reputed:
                from repro.agg.reputation import (
                    DEFAULT_REP_DECAY, DEFAULT_REP_LR, step_size_multiplier,
                    tree_reputation_scores, update_reputation)
                if spec.aux_batch is not None:
                    # ByGARS proper: score raw submissions against the clean
                    # auxiliary gradient, overriding the rule's own
                    # agreement-with-the-aggregate update — the only signal
                    # a colluding majority cannot vote on
                    aux = tuple(spec.aux_batch)
                    _, clean = vgl(params, *aux)
                    scores = tree_reputation_scores(
                        jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(clean))
                    lr = (DEFAULT_REP_LR if spec.rep_lr is None
                          else spec.rep_lr)
                    decay = (DEFAULT_REP_DECAY if spec.rep_decay is None
                             else spec.rep_decay)
                    new_agg_state = new_agg_state._replace(
                        reputation=update_reputation(
                            agg_state.reputation, scores, lr, decay))
                if spec.rep_lr:
                    # staleness-adaptive step size (Alistarh et al.): the
                    # same carried trust scales the update magnitude
                    step_scale = step_size_multiplier(new_agg_state)
                    agg = jax.tree_util.tree_map(
                        lambda a: (a.astype(jnp.float32)
                                   * step_scale).astype(a.dtype), agg)
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
                step_scale=step_scale if reputed else None)
            if loads.size:
                metrics.update(moe_metrics(loads))
        return new_params, new_state, metrics, new_agg_state

    if stateful:
        return run_step

    def step(params, opt_state, batch):
        return run_step(params, opt_state, batch, None)[:3]

    return step
