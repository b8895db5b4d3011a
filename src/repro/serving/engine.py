"""Batched serving engine: fixed-slot continuous batching over the decode
path, with an optional Byzantine-resilient ensemble mode.

Slots hold independent sequences; each engine step decodes one token for
every active slot (a single jit'd ``decode_step`` on the full batch).  New
requests are admitted into free slots via per-slot prefill.  This is the
"serve a small model with batched requests" driver of deliverable (b) and
exercises caches/positions exactly as the decode dry-run shapes do.
Admission's prefill is one jit'd program too, compiled once per prompt
length and cached by shape, so clients should bucket prompt lengths.

Each slot carries its own position counter (mixed-length batching ropes
and cache-writes per slot).  Admission is continuous: requests queue via
:meth:`ServingEngine.submit` and every :meth:`ServingEngine.step` drains
the queue into freed slots *before* decoding, so a slot vacated by a
finished request is refilled mid-stream without the caller orchestrating
anything.  Simplifications vs a production scheduler: no paged KV;
prefill runs at admission time on the slot's sub-batch; greedy sampling.

**Ensemble mode** (``ensemble=AggSpec(...)``): ``params`` is a
replica-stacked pytree (leading ``(n_replicas,)`` axis on every leaf, see
``repro.dist.serve_robust``), caches are kept per replica, and every
decode step aggregates the ``(n_replicas, n_slots, vocab)`` logits stack
through the ``repro.agg`` registry before sampling — Krum/Bulyan reject a
poisoned replica's distribution; stateful rules thread an ``AggState``
across tokens via ``self.agg_state``.  See docs/serving.md for the
architecture and the AggState-across-tokens contract.

**Speculative mode** (``ensemble.speculative_k >= 1``): each step drafts
a ``k``-token block on replica ``ensemble.draft_replica``, verifies all
``k`` positions in one batched robust-aggregation step
(``make_robust_verify_step``), and emits the longest draft prefix that
survives the aggregate plus one corrected token
(``repro.serving.speculative.accept_block``) — 1..k tokens per step per
slot.  ``speculative_k = 1`` runs the same machinery with no draft at
all and reproduces the per-token stream bitwise.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.models import decode_step, init_cache, prefill
from repro.models.config import ModelConfig
from repro.obs.trace import count_compiles, host_span

__all__ = ["Request", "ServingEngine"]


def _jit_with_first_token(prefill_step):
    """Jit ``prefill_step(params, tokens) -> (last_logits, cache, ...)``
    with the greedy first token appended to its outputs.

    ``last_logits`` is ``(1, vocab)``; its argmax (``lax.argmax``, the
    tie rule of ``jnp.argmax``) runs in the same program, so admission
    reads back one int32.  jit's cache keys the program on the prompt's
    shape: one compile per prompt length.
    """
    def step(params, tokens):
        out = prefill_step(params, tokens)
        return (*out, lax.argmax(out[0][0], 0, jnp.int32))

    return jax.jit(step)


@dataclasses.dataclass
class Request:
    """One serving request: a prompt plus generation bookkeeping.

    ``generated`` accumulates sampled token ids (filled by the engine);
    ``done`` flips when ``max_new_tokens`` have been produced;
    ``submitted_at`` is the ``time.perf_counter()`` stamp of
    :meth:`ServingEngine.submit` (``None`` when admitted directly).
    """

    rid: int
    prompt: np.ndarray           # (S0,) int32
    max_new_tokens: int
    generated: Optional[List[int]] = None
    done: bool = False
    submitted_at: Optional[float] = None


class ServingEngine:
    """Fixed-slot continuous-batching engine (optionally ensemble-robust).

    Plain mode: ``params`` is one parameter pytree and each step is one
    jit'd ``decode_step`` over all slots.  Ensemble mode (``ensemble=``
    an ``repro.agg.AggSpec``): ``params`` is a replica-stacked pytree (or
    a list of per-replica pytrees, stacked on entry), each step decodes
    every replica and aggregates the logits stack through
    ``spec.gar`` before greedy sampling; ``self.agg_state`` carries the
    ``AggState`` of stateful rules across tokens.

    Host-side counters (``positions``, ``last_token``) are int32 — the
    dtype the jit'd steps consume — so no implicit int64 promotion
    happens at the host/device boundary.

    ``counters`` holds the engine's running totals as plain numbers:
    ``admissions``, ``decode_steps``, ``queue_s`` (each admitted
    request's wait between :meth:`submit` and :meth:`admit`), and
    ``compiles`` / ``compile_s`` (the JAX compile work done inside
    :meth:`admit` and :meth:`step`, see
    :func:`repro.obs.trace.count_compiles`).  The prefill is compiled
    once per prompt length, so after every length has been seen an
    admission adds no compile.  Admission and decoding run
    under host spans (``serve/admit``, ``serve/step`` and their phases,
    :func:`repro.obs.trace.host_span`) that a profiler trace shows
    beside the device ops.
    """

    def __init__(self, params, cfg: ModelConfig, n_slots: int = 4,
                 cache_len: int = 512, sampler: str = "greedy",
                 ensemble=None, mesh=None):
        self.cfg = cfg
        self.n_slots = n_slots
        self.cache_len = cache_len
        self.ensemble = ensemble
        self.positions = np.zeros((n_slots,), np.int32)
        self.active: List[Optional[Request]] = [None] * n_slots
        self.pending: List[Request] = []
        self.last_token = np.zeros((n_slots,), np.int32)
        self.sampler = sampler
        self.agg_state = None
        self.spec_k = 0
        self.accept_counts: List[np.ndarray] = []
        self.counters: Dict[str, float] = {
            "admissions": 0, "decode_steps": 0, "queue_s": 0.0,
            "compiles": 0, "compile_s": 0.0}
        if ensemble is None:
            self.params = params
            self.cache = init_cache(cfg, n_slots, cache_len)
            self._decode = jax.jit(
                lambda p, c, t, pos: decode_step(p, cfg, c, t, pos))

            def last_logits(p, t):
                logits, cache = prefill(p, cfg, t, cache_len=cache_len)
                return logits[:, -1], cache

            self._prefill = _jit_with_first_token(last_logits)
            return
        # -- ensemble mode ----------------------------------------------------
        from repro.dist.serve_robust import (init_ensemble_state,
                                             make_robust_prefill_step,
                                             make_robust_serve_step,
                                             replicate_cache,
                                             stack_replicas)
        if isinstance(params, (list, tuple)):
            params = stack_replicas(params)
        self.params = params
        self.n_replicas = jax.tree_util.tree_leaves(params)[0].shape[0]
        self.cache = replicate_cache(init_cache(cfg, n_slots, cache_len),
                                     self.n_replicas)
        self.agg_state = init_ensemble_state(
            ensemble, self.n_replicas, n_slots, cfg.vocab_size)
        self._decode = jax.jit(
            make_robust_serve_step(cfg, ensemble, mesh=mesh))
        self._prefill = _jit_with_first_token(make_robust_prefill_step(
            cfg, ensemble, cache_len=cache_len, mesh=mesh))
        # -- speculative mode -------------------------------------------------
        k = int(getattr(ensemble, "speculative_k", 0) or 0)
        if k < 1:
            return
        from repro.dist.serve_robust import make_robust_verify_step
        from repro.serving.speculative import accept_block, make_draft_propose
        self.spec_k = k
        self.draft_replica = int(ensemble.draft_replica)
        if not 0 <= self.draft_replica < self.n_replicas:
            raise ValueError(
                f"draft_replica {self.draft_replica} out of range for "
                f"{self.n_replicas} replicas")
        self.draft_params = jax.tree_util.tree_map(
            lambda x: x[self.draft_replica], params)
        self.draft_cache = init_cache(cfg, n_slots, cache_len)
        self._propose = jax.jit(make_draft_propose(cfg, k))
        self._verify = jax.jit(make_robust_verify_step(cfg, ensemble,
                                                       mesh=mesh))
        self._accept = jax.jit(accept_block)

    # -- admission -----------------------------------------------------------

    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.active):
            if r is None:
                return i
        return None

    @staticmethod
    def _spliced(cache, slot: int, slot_cache, replicated: bool):
        """One slot's freshly prefilled cache written into a batched cache.

        Period caches are stacked ``(n_periods, B, ...)``, tail caches
        ``(B, ...)``; with ``replicated`` both carry an extra leading
        replica axis.
        """
        if not replicated:
            per, tail = (lambda fl, on: fl.at[:, slot].set(on[:, 0]),
                         lambda fl, on: fl.at[slot].set(on[0]))
        else:
            per, tail = (lambda fl, on: fl.at[:, :, slot].set(on[:, :, 0]),
                         lambda fl, on: fl.at[:, slot].set(on[:, 0]))
        return {
            "periods": jax.tree_util.tree_map(
                per, cache["periods"], slot_cache["periods"]),
            "tail": jax.tree_util.tree_map(
                tail, cache["tail"], slot_cache["tail"]),
        }

    def _splice_cache(self, slot: int, slot_cache) -> None:
        self.cache = self._spliced(self.cache, slot, slot_cache,
                                   self.ensemble is not None)

    def admit(self, req: Request) -> bool:
        """Admit one request into a free slot (returns False when full).

        Runs the prompt through per-slot prefill and splices the
        resulting cache into the batched cache.  In ensemble mode the
        first token is already robust: the replicas' last-position
        logits are aggregated through the configured rule (statelessly —
        the carried-state contract starts on the decode stream).
        """
        slot = self._free_slot()
        if slot is None:
            return False
        with host_span("serve/admit", rid=req.rid), \
                count_compiles(self.counters):
            self._admit_into(slot, req)
        return True

    def _admit_into(self, slot: int, req: Request) -> None:
        """Prefill ``req``, take its first token and splice its cache into
        ``slot``, one host span per phase."""
        if req.submitted_at is not None:
            self.counters["queue_s"] += time.perf_counter() - req.submitted_at
        self.counters["admissions"] += 1
        req.generated = []
        tokens = np.asarray(req.prompt, np.int32)[None, :]
        with host_span("serve/admit/prefill", rid=req.rid):
            out = self._prefill(self.params, tokens)
            slot_cache = out[1]
        with host_span("serve/admit/first_token", rid=req.rid):
            first = int(out[-1])
        with host_span("serve/admit/splice", rid=req.rid):
            self._splice_cache(slot, slot_cache)
            if self.spec_k:
                from repro.serving.speculative import draft_cache_view
                self.draft_cache = self._spliced(
                    self.draft_cache, slot,
                    draft_cache_view(slot_cache, self.draft_replica),
                    replicated=False)
        if self.ensemble is not None:
            # a reused slot must not inherit the previous occupant's
            # sliding-window / momentum aggregation history
            from repro.dist.serve_robust import reset_slot_state
            with host_span("serve/admit/reset", rid=req.rid):
                self.agg_state = reset_slot_state(self.agg_state, slot)
        self.active[slot] = req
        self.positions[slot] = len(req.prompt)
        self.last_token[slot] = first
        req.generated.append(first)

    def submit(self, req: Request) -> None:
        """Queue a request for admission at the next :meth:`step`.

        The engine owns the scheduling: queued requests enter freed slots
        mid-stream (continuous batching) without the caller tracking slot
        occupancy.
        """
        req.submitted_at = time.perf_counter()
        self.pending.append(req)

    def _admit_pending(self) -> None:
        while self.pending and self._free_slot() is not None:
            self.admit(self.pending.pop(0))

    # -- one decode step across all slots -------------------------------------

    def step(self) -> None:
        """Admit queued requests into free slots, then decode the batch.

        Per-token mode decodes one token for every active slot; ensemble
        mode additionally threads ``self.agg_state`` through the robust
        step so stateful rules accumulate their history across tokens;
        speculative mode emits 1..k tokens per slot (draft + batched
        robust verify + acceptance).  A no-op when nothing is active or
        queued.
        """
        self._admit_pending()
        if not any(r is not None for r in self.active):
            return
        self.counters["decode_steps"] += 1
        with host_span("serve/step"), count_compiles(self.counters):
            if self.spec_k:
                self._step_speculative()
            else:
                self._step_per_token()

    def _step_per_token(self) -> None:
        """One token for every active slot: decode, sample, emit."""
        with host_span("serve/step/decode"):
            tokens = jnp.asarray(self.last_token)[:, None]
            # per-slot positions: each sequence ropes/writes at its own
            # index
            pos = jnp.asarray(self.positions, jnp.int32)
            if self.ensemble is None:
                logits, self.cache = self._decode(self.params, self.cache,
                                                  tokens, pos)
                step_logits = logits[:, 0]
            else:
                step_logits, self.cache, _res, self.agg_state = self._decode(
                    self.params, self.cache, tokens, pos, self.agg_state)
        with host_span("serve/step/sample"):
            # the host waits here for the device's step
            nxt = np.asarray(jnp.argmax(step_logits, axis=-1), np.int32)
        with host_span("serve/step/emit"):
            for i, req in enumerate(self.active):
                if req is None:
                    continue
                self.last_token[i] = nxt[i]
                req.generated.append(int(nxt[i]))
                self.positions[i] += 1
                if len(req.generated) >= req.max_new_tokens:
                    req.done = True
                    self.active[i] = None

    def _step_speculative(self) -> None:
        """One speculative engine step: draft k-1, verify k, emit 1..k.

        The draft replica proposes a block per slot; one jit'd verify
        pass scores every position on every replica and aggregates
        robustly; :func:`repro.serving.speculative.accept_block` turns
        the aggregate into per-slot emissions.  Slots accept different
        prefix lengths, so per-slot position counters diverge — exactly
        what the ``pos``-vector decode contract supports.
        """
        tokens = jnp.asarray(self.last_token)
        pos = jnp.asarray(self.positions, jnp.int32)
        block, self.draft_cache = self._propose(
            self.draft_params, self.draft_cache, tokens, pos)
        agg_logits, self.cache, _diag, self.agg_state = self._verify(
            self.params, self.cache, block, pos, self.agg_state)
        emitted, count, _v = self._accept(block, agg_logits)
        emitted = np.asarray(emitted, np.int32)
        count = np.asarray(count, np.int32)
        self.accept_counts.append(count.copy())
        for i, req in enumerate(self.active):
            if req is None:
                continue
            c = min(int(count[i]), req.max_new_tokens - len(req.generated))
            req.generated.extend(int(t) for t in emitted[i, :c])
            self.positions[i] += c
            self.last_token[i] = int(emitted[i, c - 1])
            if len(req.generated) >= req.max_new_tokens:
                req.done = True
                self.active[i] = None

    def telemetry(self) -> Dict:
        """Drain the engine's aggregation forensics to host (numpy).

        Returns the :func:`repro.obs.buffer.drain` report of the carried
        ``AggState``'s metrics ring — empty when the ensemble spec does
        not set ``telemetry=True`` — extended with the speculative
        acceptance record: ``accept_counts`` is the ``(steps, n_slots)``
        per-step accepted-prefix-length history and ``accept_mean`` its
        scalar mean (0.0 before any speculative step ran), and with
        ``counters``, a copy of :attr:`counters`.
        """
        from repro.obs.buffer import drain
        obs = self.agg_state.obs if self.agg_state is not None else ()
        report = drain(obs)
        counts = (np.stack(self.accept_counts)
                  if self.accept_counts else np.zeros((0, self.n_slots),
                                                      np.int32))
        report["accept_counts"] = counts
        report["accept_mean"] = float(counts.mean()) if counts.size else 0.0
        report["counters"] = dict(self.counters)
        return report

    def run(self, requests: List[Request], max_steps: int = 1000
            ) -> Dict[int, List[int]]:
        """Serve a list of requests to completion (continuous batching).

        Queues everything via :meth:`submit`, steps the batch (each step
        drains the queue into freed slots before decoding) until
        everything is done or ``max_steps`` is hit, and returns
        ``{rid: generated tokens}``.
        """
        for req in requests:
            self.submit(req)
        results: Dict[int, List[int]] = {}
        for _ in range(max_steps):
            if not self.pending and not any(self.active):
                break
            self.step()
            for req in requests:
                if req.done and req.rid not in results:
                    results[req.rid] = req.generated
        for req in requests:
            results.setdefault(req.rid, req.generated or [])
        return results
