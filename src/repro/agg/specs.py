"""The unified Byzantine-protocol spec and the shared quorum check.

The single-host flat path and the sharded path used to carry two
near-duplicate spec dataclasses with three diverging quorum error
messages (the third lived in ``repro.dist.robust._check_quorum``).
They are now one spec type, :class:`AggSpec`, and one checker,
:func:`check_quorum`, used by every layer.

All fields are keyword-only (every call site in the repo already was),
so the two historic field orders can no longer conflict.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

from repro.agg.registry import resolve_rule

__all__ = ["AggSpec", "check_quorum"]


@dataclasses.dataclass(frozen=True, kw_only=True)
class AggSpec:
    """Static configuration of the Byzantine training protocol.

    One spec drives both runtimes: the single-host trainer reads
    ``n_workers`` from the spec, while the sharded train step takes the
    worker count from the batch's leading axis at trace time and leaves
    ``n_workers`` unset.  ``f`` is both the number of injected Byzantine
    workers and the bound the aggregation rule defends against
    (``declared_f`` overrides the latter).

    Fields beyond the shared core:
      agg_dtype / distance_backend — the sharded path's accumulation
        dtype contract and (n, n)-distance implementation (see
        ``repro.dist.robust``); the flat path ignores them.
      history_window — sliding-window length of ``buffered-*`` rules.
      seed — PRNG seed for in-graph attack noise on the sharded path
        (and for the ``random`` async delay schedule).
      async_tau / async_schedule — the asynchronous runtime's bounded
        staleness: per-worker maximal slot age (an int for a homogeneous
        bound or a tuple of per-worker bounds — heterogeneous, and
        attacker-controllable in the sense that Byzantine workers ignore
        it) and the deterministic delay schedule (``"fixed"`` staggered
        round-robin | ``"random"`` bounded Bernoulli).  Only the async
        step builders read them (``repro.dist.async_train``,
        ``repro.training.trainer.make_async_byzantine_step``);
        ``async_tau=0`` makes the async step reproduce the synchronous
        one exactly.
      speculative_k / draft_replica — robust speculative decoding
        (serving only): ``speculative_k`` is the verify-block length
        (``0``/``1`` = the per-token path), ``draft_replica`` the
        ensemble row whose parameters drive the cheap draft model.  Only
        the serving engine and ``repro.serving.speculative`` read them;
        the acceptance rule always tests drafts against the *robustly
        aggregated* verifier distribution, never a single replica.
      rep_lr / rep_decay — the ``reputation-*`` score schedule
        (``repro.agg.reputation``): EMA rate and forgetting factor,
        forwarded to ``resolve_rule``; ``None`` takes the module
        defaults.  A *set* (truthy) ``rep_lr`` additionally switches on
        the staleness-adaptive step-size tail: the train steps multiply
        the aggregated update by ``step_size_multiplier(state)`` when
        the resolved rule carries reputation.  Other rules ignore both.
      aux_batch — optional ``(inputs, labels)`` auxiliary clean batch
        (ByGARS): when set and the rule carries reputation, the trainer
        scores worker agreement against the gradient of the loss on
        this batch instead of the emitted aggregate — the variant that
        stays sound under a colluding majority, which can own the
        aggregate itself.  Excluded from spec equality (it holds
        arrays).
      telemetry — when True every layer resolves the GAR through
        ``effective_gar`` (the ``obs-<gar>`` forensics wrapper,
        ``repro.obs``), so the carried ``AggState.obs`` ring records
        per-worker selection/suspicion diagnostics each step.  The data
        path is bitwise-identical either way; attacks keep targeting
        the raw ``gar`` name.
    """

    f: int
    n_workers: Optional[int] = None
    gar: str = "bulyan-krum"
    attack: str = "none"
    attack_kwargs: tuple = ()          # (("gamma", 10.0), ...)
    declared_f: Optional[int] = None   # f the master *assumes* (>= actual)
    agg_dtype: str = "native"          # native | float32 | bfloat16
    distance_backend: str = "auto"     # auto | xla | pallas | fused
    history_window: int = 4            # buffered-* window length
    seed: int = 0
    async_tau: "int | tuple" = 0       # bounded staleness (scalar or per-worker)
    async_schedule: str = "fixed"      # fixed | random
    speculative_k: int = 0             # verify-block length (0/1 = per-token)
    draft_replica: int = 0             # ensemble row the draft model reads
    rep_lr: Optional[float] = None     # reputation-* EMA rate (None=default)
    rep_decay: Optional[float] = None  # reputation-* forgetting factor
    telemetry: bool = False            # aggregate through obs-<gar>
    aux_batch: Any = dataclasses.field(default=None, compare=False)

    @property
    def n_honest(self) -> int:
        """Honest worker count (requires ``n_workers``)."""
        if self.n_workers is None:
            raise ValueError("n_honest needs n_workers set on the spec")
        return self.n_workers - self.f

    @property
    def f_declared(self) -> int:
        """The bound the master aggregates with (defaults to ``f``)."""
        return self.declared_f if self.declared_f is not None else self.f

    @property
    def effective_gar(self) -> str:
        """The GAR name the runtime actually aggregates with.

        ``gar`` itself normally; with ``telemetry=True`` it is the
        idempotent ``obs-<gar>`` forensics wrapper (``repro.obs``),
        whose data path is bitwise the base rule's.  Attack plumbing
        keeps reading the raw ``gar`` — the attacker targets the
        defense, not its instrumentation.
        """
        if not self.telemetry:
            return self.gar
        from repro.obs.forensics import obs_name
        return obs_name(self.gar)

    def rule(self):
        """Resolve this spec's GAR through the registry.

        Args:
          (none) — reads ``effective_gar`` (``gar``, or its ``obs-``
          wrapper under ``telemetry=True``), ``history_window`` and the
          ``rep_lr`` / ``rep_decay`` reputation schedule.

        Returns:
          The resolved ``AggregatorRule``.
        """
        return resolve_rule(self.effective_gar,
                            history_window=self.history_window,
                            rep_lr=self.rep_lr, rep_decay=self.rep_decay)

    def validate(self, n_workers: Optional[int] = None, *,
                 distributed: bool = False) -> None:
        """Quorum-check this spec (both historic call forms).

        Args:
          n_workers: worker count to check against.  ``None`` falls back
            to ``self.n_workers`` (the single-host form
            ``spec.validate()``); the sharded step builders pass the
            batch's worker axis at trace time instead.
          distributed: when True, additionally require the rule to have
            a distributed (tree) implementation — e.g. ``bulyan-brute``
            is valid on the flat path but rejected here.  This used to
            be inferred from ``n_workers is not None``, which wrongly
            forced the tree requirement onto flat specs validated with
            an explicit worker count; the sharded step builders now opt
            in explicitly.

        Returns:
          None.  Raises ``KeyError`` for an unknown rule (or, with
          ``distributed=True``, a rule without a tree implementation)
          and ``ValueError`` for a quorum violation or a missing count.
        """
        n = self.n_workers if n_workers is None else n_workers
        if n is None:
            raise ValueError(
                "validate() needs n_workers — set it on the spec or pass "
                "it explicitly")
        check_quorum(self.effective_gar, n, self.f_declared,
                     distributed=distributed,
                     history_window=self.history_window)


def check_quorum(gar: str, n: int, f: int, *, distributed: bool = False,
                 history_window: Optional[int] = None) -> None:
    """The one quorum check every layer shares.

    Args:
      gar: rule name (resolved through the registry — raises ``KeyError``
        with the canonical "unknown GAR" message for unknown names).
      n: worker count.
      f: declared Byzantine bound.
      distributed: when True, additionally require a tree-path
        implementation (e.g. distributed Bulyan only supports the
        distance-only bases krum/geomed), raising ``KeyError`` like the
        old ``dist.robust._check_quorum`` did.
      history_window: forwarded to ``resolve_rule`` for buffered rules.

    Returns:
      None.  Raises ``ValueError`` as ``"{gar} requires n >= {need} for
      f={f}, got n={n}"`` when the quorum is violated — the single
      message all three layers now agree on.
    """
    rule = resolve_rule(gar, history_window=history_window)
    if distributed and rule.tree_fn is None:
        if gar.startswith("bulyan") or "-bulyan" in gar:
            raise KeyError(
                f"distributed bulyan needs a distance-only base "
                f"(krum/geomed), got {gar!r}")
        raise KeyError(f"{gar!r} has no distributed (tree) implementation")
    need = rule.min_n(f)
    if n < need:
        raise ValueError(
            f"{gar} requires n >= {need} for f={f}, got n={n}")
