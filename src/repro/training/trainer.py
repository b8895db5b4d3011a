"""Byzantine distributed-SGD training engine (single-host reference).

Faithful to the paper's protocol (§2): each of n - f honest workers draws
an i.i.d. mini-batch and submits a stochastic gradient; the omniscient
adversary reads them and fabricates f Byzantine submissions; the master
aggregates with a GAR and updates the model.  Everything happens in-graph
(the adversary included) so a training step is one jit'd call.

The aggregation rule is resolved through the unified registry
(``repro.agg``); stateful rules (``buffered-*``,
``centered_clip_momentum``) thread an explicit ``AggState`` through the
step and the trainer loop, while stateless rules keep the historic
signatures untouched.

The *asynchronous* flat reference (``make_async_byzantine_step`` /
``AsyncByzantineTrainer``) drops the per-step barrier: it is the
synchronous step's body with one more stage,
``repro.dist.async_train.bus_stage``, between injection and aggregation.
Submissions live in a ``GradientBus`` of per-worker versioned slots, an
in-graph delay schedule decides who delivers, and the rule — typically
a staleness-weighted ``stale-<base>`` — aggregates the slot stack.  With
``spec.async_tau = 0`` the async step reproduces the synchronous one
exactly (see docs/async-runtime.md).

The mesh-sharded production variants live in ``repro.dist.train`` /
``repro.dist.async_train`` — this module is the semantics reference
they are tested against.
"""
from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.agg.specs import AggSpec
from repro.agg.state import AggState, init_state
from repro.agg.reputation import reputation_scores, reputation_tail
from repro.core import attacks as attacks_lib
from repro.core import pytree as pt
from repro.dist.async_train import bus_stage, init_bus
from repro.obs.buffer import drain
from repro.obs.schema import core_metrics, selection_weight
from repro.optim import Optimizer


def init_flat_agg_state(spec: AggSpec, params,
                        n_rows: Optional[int] = None):
    """Zeroed ``AggState`` for a stateful GAR on the flat (n, d) path.

    Args:
      spec: the protocol spec; ``n_workers`` must be set (the flat path
        stacks all n submissions into one matrix).
      params: the parameter pytree — only the total coordinate count is
        read.
      n_rows: row count of the stacked matrix the rule will see —
        ``n_workers`` under attack, ``n_honest`` in clean mode
        (``None`` infers it from the spec's attack configuration).

    Returns:
      An ``AggState`` sized for the ``(n_rows, d)`` stacked matrix, or
      ``None`` when the rule is stateless.
    """
    rule = spec.rule()
    if not rule.stateful:
        return None
    if n_rows is None:
        n_rows = (spec.n_workers if spec.f > 0 and spec.attack != "none"
                  else spec.n_honest)
    d = sum(math.prod(p.shape) for p in jax.tree_util.tree_leaves(params))
    template = jax.ShapeDtypeStruct((n_rows, d), jnp.float32)
    return init_state(rule, template, flat=True)


def _flat_grad(grad_fn: Callable, params, batch) -> jnp.ndarray:
    """Flat ``(d,)`` gradient of one (clean auxiliary) batch, in the
    exact coordinate order of ``pt.stack_flatten`` (the scoring target
    must index the same space as the worker rows)."""
    clean = grad_fn(params, batch[0], batch[1])
    stacked = jax.tree_util.tree_map(lambda l: l[None], clean)
    flat, _ = pt.stack_flatten(stacked)
    return flat[0]


def make_byzantine_step(loss_fn: Callable, optimizer: Optimizer,
                        spec: AggSpec,
                        attack_on: bool = True) -> Callable:
    """Build a jit-able training step.

    loss_fn(params, x, y) -> scalar loss.
    batch: x (n_honest, b, ...), y (n_honest, b, ...) — per-honest-worker.
    Returns step(params, opt_state, x, y, key) ->
        (params, opt_state, metrics dict); a stateful GAR appends an
    ``agg_state`` argument and return slot (carried by the caller).
    """
    run_step = _step_body(loss_fn, optimizer, spec, attack_on)
    if spec.rule().stateful:
        return run_step

    def step(params, opt_state, x, y, key):
        return run_step(params, opt_state, x, y, key, None)[:3]

    return step


def _step_body(loss_fn: Callable, optimizer: Optimizer, spec: AggSpec,
               attack_on: bool = True,
               bus: Optional[Callable] = None) -> Callable:
    """The one body of the flat steps, always ``step(params, opt_state,
    x, y, key, agg_state) -> (params, opt_state, metrics, agg_state)``.

    ``bus`` is ``None`` for the synchronous step; the asynchronous one
    passes ``repro.dist.async_train.bus_stage``, run between injection
    and aggregation over the flat ``(n, d)`` matrix.
    """
    spec.validate()
    rule = spec.rule()
    reputed = "reputation" in rule.state_fields
    attack = attacks_lib.get_attack(spec.attack) if attack_on else None
    akw = dict(spec.attack_kwargs)

    def run_step(params, opt_state, x, y, key, agg_state):
        grad_fn = jax.grad(loss_fn)
        worker_grads = jax.vmap(lambda xi, yi: grad_fn(params, xi, yi))(x, y)
        flat, ctx = pt.stack_flatten(worker_grads)      # (n_honest, d)
        n_h = spec.n_honest

        attacked = attack is not None and spec.f > 0
        if attacked:
            kw = dict(akw)
            if attack in (attacks_lib.omniscient_lp,
                          attacks_lib.omniscient_linf,
                          attacks_lib.reputation_burn):
                kw.setdefault("step", opt_state["step"])
            if bus is not None and spec.attack in attacks_lib.DELAY_ATTACKS:
                kw.setdefault("prev", agg_state.bus.grads[n_h:])
                kw.setdefault("step", agg_state.step)
            byz = attack(flat, spec.f, key, **kw)
            full = jnp.concatenate([flat, byz], axis=0)
        else:
            full = flat

        if bus is not None:
            agg_state, bus_metrics = bus(spec, agg_state, full, n_h,
                                         attacked)
            full = agg_state.bus.grads

        if rule.stateful:
            res, new_state = rule.dense_fn(full, spec.f_declared, agg_state)
        else:
            res = rule.dense_fn(full, spec.f_declared)
            new_state = agg_state
        grad_out = res.gradient
        step_scale = None
        if reputed:
            new_state, grad_out, step_scale = reputation_tail(
                spec, agg_state.reputation, new_state, grad_out,
                lambda: reputation_scores(
                    full, _flat_grad(grad_fn, params, spec.aux_batch)))
        agg = pt.unflatten(grad_out, ctx)
        new_params, new_opt = optimizer.update(agg, opt_state, params)

        honest_mean = jnp.mean(full[:n_h], axis=0)
        metrics = core_metrics(
            loss=loss_fn(params, x[0], y[0]),
            byz_weight=selection_weight(res.selected, n_h),
            agg_dev=jnp.linalg.norm(res.gradient - honest_mean),
            grad_norm=jnp.linalg.norm(res.gradient),
            step_scale=step_scale)
        if bus is not None:
            metrics.update(bus_metrics)
        return new_params, new_opt, metrics, new_state

    return run_step


class ByzantineTrainer:
    """Convenience loop: data -> jit step -> metrics history.

    For stateful GARs the trainer owns the ``AggState``
    (``self.agg_state``), zero-initialized at construction and carried
    across ``run`` calls — the caller's loop stays unchanged.  When
    ``attack_until`` flips the protocol from attacked (n rows) to clean
    (n - f rows), per-worker history buffers no longer match the
    submission count and are re-initialized — the clean committee
    starts a fresh window; row-count-independent state (the
    ``centered_clip_momentum`` center) survives the flip.
    """

    def __init__(self, loss_fn, params, optimizer: Optimizer,
                 spec: AggSpec, seed: int = 0):
        self.spec = spec
        self.params = params
        self.optimizer = optimizer
        self.opt_state = optimizer.init(params)
        self._rule = spec.rule()
        self._attack_mode = spec.f > 0 and spec.attack != "none"
        self._stateful, self.agg_state, self._steps = self._program(loss_fn)
        self.key = jax.random.PRNGKey(seed)
        self.history: list = []

    def _program(self, loss_fn):
        """``(stateful, initial agg_state, {attack_on: jitted step})``."""
        steps = {on: jax.jit(make_byzantine_step(loss_fn, self.optimizer,
                                                 self.spec, attack_on=on))
                 for on in (True, False)}
        return (self._rule.stateful,
                init_flat_agg_state(self.spec, self.params), steps)

    def run(self, batcher, n_steps: int, attack_until: Optional[int] = None,
            eval_fn: Optional[Callable] = None, eval_every: int = 0,
            start_step: int = 0):
        for t in range(start_step, start_step + n_steps):
            x, y = batcher.batch(t)
            self.key, sub = jax.random.split(self.key)
            attacked = (attack_until is None) or (t < attack_until)
            use_attack = (attacked and self.spec.f > 0
                          and self.spec.attack != "none")
            fn = self._steps[use_attack]
            if self._stateful and use_attack != self._attack_mode:
                self._attack_mode = use_attack
                # per-worker buffers are row-count-dependent: the
                # history window, the (n,) reputation column *and* the
                # (cap, n) forensics ring must restart when the
                # committee changes size; the row-count-independent
                # clipping center survives
                if ({"history", "reputation", "obs"}
                        & set(self._rule.state_fields)):
                    rows = (self.spec.n_workers if use_attack
                            else self.spec.n_honest)
                    self.agg_state = init_flat_agg_state(
                        self.spec, self.params, n_rows=rows)
            args = (self.params, self.opt_state, jnp.asarray(x),
                    jnp.asarray(y), sub)
            if self._stateful:
                self.params, self.opt_state, m, self.agg_state = fn(
                    *args, self.agg_state)
            else:
                self.params, self.opt_state, m = fn(*args)
            rec = {k: float(v) for k, v in m.items()}
            rec["step"] = t
            if eval_fn and eval_every and t % eval_every == 0:
                rec["eval_acc"] = float(eval_fn(self.params))
            self.history.append(rec)
        return self.history

    def telemetry(self):
        """Drain the carried aggregation-forensics ring to host numpy.

        Args:
          (none) — reads ``self.agg_state.obs``.

        Returns:
          ``repro.obs.buffer.drain``'s dict (``pushed`` / ``records`` /
          ``selection_frequency``); empty when the spec was built
          without ``telemetry=True``.
        """
        obs = self.agg_state.obs if self.agg_state is not None else ()
        return drain(obs)


# ---------------------------------------------------------------------------
# the asynchronous flat reference (GradientBus over the (n, d) matrix)
# ---------------------------------------------------------------------------

def init_flat_async_state(spec: AggSpec, params,
                          n_rows: Optional[int] = None) -> AggState:
    """Zeroed bus-carrying ``AggState`` for the flat async path.

    Unlike ``init_flat_agg_state`` this never returns ``None``: the
    async runtime always carries a state, because the ``GradientBus``
    itself is the asynchrony — stateless rules get ``step`` + bus only,
    stateful rules (``stale-*``, ``buffered-*``) their buffers too.

    Args:
      spec: the protocol spec; ``n_workers`` must be set.
      params: the parameter pytree — only the total coordinate count is
        read.
      n_rows: row count of the stacked matrix / bus — ``n_workers``
        under attack, ``n_honest`` in clean mode (``None`` infers it
        from the spec's attack configuration).

    Returns:
      An ``AggState`` whose ``bus`` holds a zero ``(n_rows, d)`` slot
      matrix with ``step = versions = 0``.
    """
    rule = spec.rule()
    if n_rows is None:
        n_rows = (spec.n_workers if spec.f > 0 and spec.attack != "none"
                  else spec.n_honest)
    d = sum(math.prod(p.shape) for p in jax.tree_util.tree_leaves(params))
    template = jax.ShapeDtypeStruct((n_rows, d), jnp.float32)
    if rule.stateful:
        state = init_state(rule, template, flat=True)
    else:
        state = AggState(step=jnp.zeros((), jnp.int32))
    if state.bus == ():
        state = state._replace(bus=init_bus(template))
    return state


def make_async_byzantine_step(loss_fn: Callable, optimizer: Optimizer,
                              spec: AggSpec) -> Callable:
    """Build the jit-able asynchronous flat training step.

    The single-host reference of ``repro.dist.async_train
    .make_async_train_step``: :func:`make_byzantine_step`'s body with
    ``repro.dist.async_train.bus_stage`` between injection and
    aggregation, over the flat ``(n, d)`` matrix.

    Unlike ``make_byzantine_step`` there is no ``attack_on`` variant:
    the bus row count is baked into the carried state, so the lock-free
    protocol cannot re-synchronize mid-run (see
    :class:`AsyncByzantineTrainer`) — clean runs are expressed through
    the spec (``attack="none"`` or ``f=0``), which keeps the step's row
    count and :func:`init_flat_async_state`'s inference agreeing.

    Args:
      loss_fn: ``loss_fn(params, x, y) -> scalar``.
      optimizer: the ``repro.optim`` optimizer.
      spec: unified protocol spec (``n_workers`` set; async fields read).

    Returns:
      ``step(params, opt_state, x, y, key, agg_state) -> (params,
      opt_state, metrics, agg_state)`` — always the stateful signature;
      size the carried state with :func:`init_flat_async_state`.  With
      ``spec.async_tau = 0`` the step reproduces
      ``make_byzantine_step`` bitwise on identical inputs.
    """
    return _step_body(loss_fn, optimizer, spec, bus=bus_stage)


class AsyncByzantineTrainer(ByzantineTrainer):
    """Convenience loop for the asynchronous runtime (flat reference).

    :class:`ByzantineTrainer`'s loop driving
    :func:`make_async_byzantine_step`: the trainer owns the carried
    ``AggState`` — whose ``bus`` holds every worker's versioned slot —
    zero-initialized at construction and threaded across ``run`` calls.
    There is no ``attack_until`` switch: the bus row count is fixed at
    construction (n under attack, n_honest clean), matching the
    lock-free protocol where the committee never re-synchronizes.
    """

    def _program(self, loss_fn):
        step = jax.jit(make_async_byzantine_step(loss_fn, self.optimizer,
                                                 self.spec))
        return (True, init_flat_async_state(self.spec, self.params),
                {True: step, False: step})

    def run(self, batcher, n_steps: int,
            eval_fn: Optional[Callable] = None, eval_every: int = 0,
            start_step: int = 0):
        """:meth:`ByzantineTrainer.run` without ``attack_until``."""
        return super().run(batcher, n_steps, None, eval_fn, eval_every,
                           start_step)
