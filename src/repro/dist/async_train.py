"""The asynchronous bounded-staleness Byzantine train step.

The step is the synchronous step's body (``repro.dist.train``) with one
more stage, :func:`bus_stage`, between injection and aggregation; this
module holds the bus that stage drives.

The third runtime mode (train / serve / **async-train**): instead of the
synchronous barrier of ``repro.dist.train`` — every worker submits a
fresh gradient every step — the master aggregates whatever a
:class:`GradientBus` holds.  The bus is a jit-able pytree of per-worker
*versioned gradient slots*: a pytree of ``(n, *dims)`` leaves mirroring
the gradient tree, plus ``(n,)`` int32 ``versions`` (the step each
slot's gradient was computed at — hence against which parameters) and
``arrival_step`` (the step the master observed the write) arrays.

Arrival is simulated by an in-graph deterministic *delay schedule* with
per-worker bounded staleness ``tau_w`` (heterogeneous; Byzantine workers
additionally control their own arrival, see below): at global step t a
worker whose schedule fires recomputes its gradient at the *current*
parameters and overwrites its slot with ``versions[w] = t``; everyone
else's slot keeps the gradient it computed against older parameters.
One jitted step therefore simulates lock-free arrival on both the
single-device and the GSPMD-sharded mesh path — exactly like the sync
step, sharding enters only through the input/output shardings (the bus
slots shard like the worker-stacked gradients they mirror).

Aggregation goes through the unchanged ``repro.agg`` registry.  The
``stale-<base>`` rules (``repro.agg.staleness``) read per-worker
staleness ``t - versions`` from the :class:`~repro.agg.state.AggState`
— extended to carry the bus — and reweight the stack before any base
rule; plain rules aggregate the raw slots.  ``init_async_state`` is
``jax.eval_shape``-composable, so the 512-device dry-run lowers
``--async-tau N --gar stale-*`` abstractly.

Threat model: the delay schedule only binds *honest* workers.  A
Byzantine worker controls its own arrival — under an active attack the
last f workers deliver every step and stamp fresh versions (staleness
weighting cannot see through a lying timestamp; that is the point of
the ``stale_replay`` / ``slow_drift`` attacks, whose content exploits
the leeway staleness opens while *looking* fresh — the robust base rule
has to cut them by geometry).  With ``async_tau=0`` every honest worker
delivers every step and the async step reproduces
``repro.dist.train.make_train_step`` exactly (pinned by
``tests/test_async_train.py``).

The flat single-host reference of this runtime lives in
``repro.training.trainer`` (``make_async_byzantine_step`` /
``AsyncByzantineTrainer``); architecture notes in docs/async-runtime.md.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.agg.specs import AggSpec
from repro.agg.state import AggState, init_state
from repro.dist.train import make_step_body
from repro.obs.schema import async_extras
from repro.optim import Optimizer

__all__ = ["GradientBus", "bus_stage", "delivery_mask", "init_async_state",
           "init_bus", "make_async_train_step", "resolve_tau",
           "staleness_excess", "update_bus"]


class GradientBus(NamedTuple):
    """Per-worker versioned gradient slots (a jit-able pytree).

    grads:         pytree of ``(n, *dims)`` slot leaves — worker w's row
                   holds the gradient it last delivered, computed against
                   the parameters of step ``versions[w]``.
    versions:      ``(n,)`` int32 — compute step of each slot's gradient
                   (staleness at aggregation step t is ``t - versions``).
    arrival_step:  ``(n,)`` int32 — step the master last observed a
                   write into each slot (equals ``versions`` for honest
                   workers; a Byzantine worker may stamp a fresh version
                   on stale content, so the two can diverge in spirit —
                   the master can only ever observe arrival).
    """

    grads: Any
    versions: jnp.ndarray
    arrival_step: jnp.ndarray


def init_bus(template: Any) -> GradientBus:
    """Zeroed :class:`GradientBus` sized from a worker-stacked template.

    Args:
      template: pytree of ``(n, *dims)`` worker-stacked leaves (or
        ``jax.ShapeDtypeStruct`` leaves — only shapes/dtypes are read,
        so this composes with ``jax.eval_shape``).  A bare ``(n, d)``
        array is a valid single-leaf pytree (the flat-path layout).

    Returns:
      A bus with zero slots mirroring the template's structure and
      dtypes, and ``versions = arrival_step = 0`` — every delay
      schedule delivers all workers at step 0, so the zero slots are
      never aggregated.
    """
    leaves = jax.tree_util.tree_leaves(template)
    if not leaves:
        raise ValueError("empty bus template")
    n = leaves[0].shape[0]
    grads = jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, leaf.dtype), template)
    return GradientBus(grads=grads,
                       versions=jnp.zeros((n,), jnp.int32),
                       arrival_step=jnp.zeros((n,), jnp.int32))


def resolve_tau(tau: Any, n: int) -> jnp.ndarray:
    """Normalize a staleness bound to a per-worker ``(n,)`` int32 array.

    Args:
      tau: a non-negative int (homogeneous bound) or a length-n sequence
        of per-worker bounds (heterogeneous — e.g. fast pod-local
        workers at 0, cross-region stragglers at 8).
      n: worker count.

    Returns:
      ``(n,)`` int32 staleness bounds.  Raises ``ValueError`` for any
      negative bound (scalar or per-worker) or a sequence of the wrong
      length.  ``tau`` is static configuration — it must be concrete at
      trace time (the schedule's cycle length divides by ``tau + 1``).
    """
    if isinstance(tau, int):
        if tau < 0:
            raise ValueError(f"async_tau must be >= 0, got {tau}")
        return jnp.full((n,), tau, jnp.int32)
    arr = np.asarray(tau, dtype=np.int32)
    if arr.ndim == 0:
        arr = np.full((n,), int(arr), np.int32)
    if arr.shape != (n,):
        raise ValueError(
            f"per-worker async_tau needs shape ({n},), got {arr.shape}")
    if (arr < 0).any():
        raise ValueError(f"async_tau must be >= 0, got {tau!r}")
    return jnp.asarray(arr)


def delivery_mask(step, versions: jnp.ndarray, tau: jnp.ndarray,
                  schedule: str = "fixed", seed: int = 0) -> jnp.ndarray:
    """In-graph deterministic arrival mask for one async step.

    Args:
      step: () int32 global async step (traced).
      versions: ``(n,)`` int32 current slot versions (consulted by the
        ``random`` schedule's staleness-bound enforcement).
      tau: ``(n,)`` int32 per-worker staleness bounds (``resolve_tau``).
      schedule: ``"fixed"`` — staggered round-robin, worker w delivers
        when ``(step - w mod (tau_w+1)) % (tau_w + 1) == 0`` so same-tau
        workers spread their arrivals over the cycle; ``"random"`` —
        Bernoulli(1 / (tau_w + 1)) from a counter-based PRNG
        (``fold_in(seed, step)``), with delivery forced whenever the
        slot would otherwise exceed its bound.  Both schedules force
        delivery at step 0, so the zero-initialized bus never leaks
        into an aggregation.

    Returns:
      ``(n,)`` bool — True where worker w delivers a fresh gradient this
      step.  ``tau = 0`` yields all-True under both schedules (the
      synchronous special case).
    """
    n = versions.shape[0]
    step = jnp.asarray(step, jnp.int32)
    cycle = tau + 1
    if schedule == "fixed":
        phase = jnp.arange(n, dtype=jnp.int32) % cycle
        mask = (step - phase) % cycle == 0
    elif schedule == "random":
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        r = jax.random.uniform(key, (n,))
        mask = r * cycle.astype(jnp.float32) < 1.0
        mask = mask | ((step - versions) >= tau)
    else:
        raise ValueError(
            f"async_schedule must be 'fixed' or 'random', got "
            f"{schedule!r}")
    return mask | (step == 0)


def update_bus(bus: GradientBus, grads: Any, step,
               deliver: jnp.ndarray) -> GradientBus:
    """Write delivering workers' fresh gradients into their slots.

    Args:
      bus: the current bus.
      grads: pytree of ``(n, *dims)`` freshly computed gradients (same
        structure as ``bus.grads``).
      step: () int32 global async step — stamped as the version of every
        delivered slot.
      deliver: ``(n,)`` bool arrival mask (``delivery_mask``).

    Returns:
      The new bus: delivered rows overwritten (dtype-preserving
      ``where`` select), everyone else's slot, version and arrival
      untouched.  With an all-True mask the slot contents equal
      ``grads`` exactly — the bitwise anchor of the tau=0 sync
      equivalence.
    """
    def sel(old, new):
        m = deliver.reshape(deliver.shape + (1,) * (new.ndim - 1))
        return jnp.where(m, new.astype(old.dtype), old)

    step = jnp.asarray(step, jnp.int32)
    return GradientBus(
        grads=jax.tree_util.tree_map(sel, bus.grads, grads),
        versions=jnp.where(deliver, step, bus.versions),
        arrival_step=jnp.where(deliver, step, bus.arrival_step))


def staleness_excess(bus: GradientBus, step, tau: jnp.ndarray) -> jnp.ndarray:
    """Per-worker overshoot of the declared staleness bound.

    The bounded-staleness contract — every delay schedule must keep each
    honest worker's slot age at or below its ``tau_w`` — is exactly the
    kind of threshold invariant real Byzantine-tolerant systems break
    silently (the motivation of ``repro.audit``).  This helper makes the
    bound *observable*: the async step emits ``max(excess)`` as the
    ``staleness_excess`` metric every step, and the audit sweep asserts
    it stays 0 across the whole (tau, schedule) grid.

    Args:
      bus: the post-update bus of the current step.
      step: () int32 global async step the bus was just updated at.
      tau: ``(n,)`` int32 per-worker bounds (``resolve_tau``).

    Returns:
      ``(n,)`` int32 ``max(0, (step - versions) - tau)`` — 0 everywhere
      when the contract holds (a lying Byzantine version stamp shows up
      as 0 too: the master can only observe the stamped age).
    """
    staleness = jnp.asarray(step, jnp.int32) - bus.versions
    return jnp.maximum(staleness - tau, 0)


def init_async_state(spec: AggSpec, params: Any, n_workers: int) -> AggState:
    """Zeroed ``AggState`` carrying the bus for the async sharded path.

    Unlike the synchronous ``init_agg_state`` — which returns ``None``
    for stateless rules — the async runtime *always* carries a state:
    the bus itself is the asynchrony.  Rules with their own state
    (``stale-*``, ``buffered-*``, ``centered_clip_momentum``) get their
    buffers allocated alongside; plain rules get only ``step`` + bus.

    Args:
      spec: the protocol spec (``gar`` / ``history_window`` select the
        rule; ``attack``/``f`` size the bus for all n workers).
      params: the parameter pytree (or a ``ShapeDtypeStruct`` tree —
        only shapes are read, so this composes with ``jax.eval_shape``).
      n_workers: worker count, the leading axis of the gradient stacks.

    Returns:
      An ``AggState`` whose ``bus`` holds zero ``(n_workers, *dims)``
      slots in the parameter dtypes, with ``step = versions = 0``.
    """
    rule = spec.rule()
    template = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct((n_workers,) + tuple(p.shape),
                                       p.dtype), params)
    if rule.stateful:
        state = init_state(rule, template, flat=False)
    else:
        state = AggState(step=jnp.zeros((), jnp.int32))
    if state.bus == ():
        state = state._replace(bus=init_bus(template))
    return state


def bus_stage(spec: AggSpec, state: AggState, rows: Any, n_h: int,
              attacked: bool) -> Tuple[AggState, Dict]:
    """The asynchronous runtime's stage of a train step body.

    Both step bodies (the sharded ``repro.dist.train.make_step_body``,
    the flat ``repro.training.trainer`` reference) run it between
    injection and aggregation, then aggregate ``state.bus.grads``.
    ``rows`` is the worker-stacked ``(n, *dims)`` pytree of this step's
    submissions (a flat ``(n, d)`` matrix is a one-leaf tree), rows
    ``n_h:`` the Byzantine ones, which deliver every step when
    ``attacked``.

    Returns:
      ``(state, metrics)``: the state carrying the updated bus, its
      ``step`` advanced when the rule is stateless (a stateful rule
      advances it itself), and the ``async_extras`` of this step.
    """
    t = state.step
    n = jax.tree_util.tree_leaves(rows)[0].shape[0]
    tau = resolve_tau(spec.async_tau, n)
    deliver = delivery_mask(t, state.bus.versions, tau,
                            schedule=spec.async_schedule, seed=spec.seed)
    if attacked:
        # Byzantine workers control their own arrival: deliver every
        # step, stamping a fresh version on adversarial content
        deliver = deliver | (jnp.arange(n) >= n_h)
    bus = update_bus(state.bus, rows, t, deliver)
    state = state._replace(bus=bus)
    if not spec.rule().stateful:
        state = state._replace(step=t + 1)
    return state, async_extras(t - bus.versions,
                               staleness_excess(bus, t, tau), deliver)


def make_async_train_step(cfg, spec: AggSpec, optimizer: Optimizer,
                          impl: str = "auto", mesh=None) -> Callable:
    """Build the jit-able asynchronous sharded Byzantine train step.

    The synchronous step's body (``repro.dist.train.make_step_body``;
    the arguments are ``make_train_step``'s) with :func:`bus_stage`
    between injection and aggregation, under the scope ``train/bus``.
    All n workers compute fresh gradients at the current parameters, so
    every *delivered* gradient is genuinely evaluated at the parameters
    of its version step; the delay attacks read their previous slots;
    the rule aggregates the slot stack; the metrics add
    ``async_extras`` (``staleness_excess`` among them).  With
    ``spec.async_tau = 0`` this reproduces ``make_train_step`` bitwise.

    Returns:
      ``step(params, opt_state, batch, agg_state) -> (params, opt_state,
      metrics, agg_state)``, always stateful: the carried ``AggState``
      holds the :class:`GradientBus` (and the rule's own buffers); size
      it with ``init_async_state``.
    """
    return make_step_body(cfg, spec, optimizer, impl, mesh, bus=bus_stage)
