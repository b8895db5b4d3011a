"""Sync vs async convergence-per-wall-clock across aggregation rules.

The asynchronous runtime trades per-step semantics (stale gradients in
the stack) for the removal of the per-step barrier: in a real deployment
the async step's wall-clock is set by the *fastest* workers while the
synchronous step waits for the slowest straggler.  The single-host
simulation here pays the same compute either way, so the CSV reports the
two halves of that trade separately:

  * measured us/call of the jitted step (sync vs async bookkeeping
    overhead — the bus select/write is the only extra work);
  * accuracy after a fixed step budget under bounded staleness tau
    (what asynchrony costs in convergence per *step*), from which the
    derived column computes ``straggler_speedup`` — the wall-clock
    advantage the async run banks once steps are priced by the fastest
    worker instead of the slowest (x(tau+1) on the staggered schedule).

Rows: ``gar_async/<rule>_tau<k>`` with the ``backend`` column tagging
``sync`` / ``async`` variants.  Attacked rows add the stale-replay
adversary so the staleness-aware rules' resilience shows up in the perf
trajectory alongside ``gar_backends`` / ``gar_buffered`` /
``serve_robust``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, make_eval, mnist_loss
from repro.data import ByzantineBatcher
from repro.models import simple
from repro.optim import fading_lr, get_optimizer
from repro.agg import AggSpec
from repro.training import AsyncByzantineTrainer, ByzantineTrainer


def _train(gar: str, attack: str, tau: int, steps: int, *, n_honest=30,
           f=9, seed=1):
    n = n_honest + (f if attack != "none" else 0)
    spec = AggSpec(
        n_workers=n, f=f if attack != "none" else 0, gar=gar,
        attack=attack, async_tau=tau, seed=seed,
        attack_kwargs=(("scale", -4.0),) if attack == "stale_replay"
        else ())
    cls = AsyncByzantineTrainer if tau is not None else ByzantineTrainer
    tr = cls(mnist_loss, simple.init_mnist_mlp(jax.random.PRNGKey(seed)),
             get_optimizer("sgd", fading_lr(1.0, 10000)), spec)
    batcher = ByzantineBatcher("mnist", spec.n_honest, 32, seed=seed,
                               noise=0.5)
    tr.run(batcher, 3)                      # compile + warm the carry
    t0 = time.time()
    tr.run(batcher, steps, start_step=3)
    wall = time.time() - t0
    acc = float(make_eval("mnist")(tr.params))
    return 1e6 * wall / steps, acc


def main(steps: int = 60, taus=(0, 3), seed: int = 1) -> None:
    """One row per (rule, tau, sync/async) on the miniature MNIST
    protocol: us/step measured, accuracy + the straggler-priced speedup
    derived.

    Args:
      steps: measured training steps per row (after a 3-step warmup).
      taus: staleness bounds for the async rows (0 = the degenerate
        sync-equivalent case, the overhead measurement).
      seed: PRNG seed threaded to init, batching and the attack noise —
        the accuracy columns are deterministic per seed.

    Returns:
      None (emits CSV rows).
    """
    rules = (("average", "none"), ("krum", "stale_replay"),
             ("stale-krum", "stale_replay"),
             ("stale-bulyan-krum", "stale_replay"))
    sync_rows = {}
    for gar, attack in rules:
        base = gar.replace("stale-", "")
        if (base, attack) not in sync_rows:
            sync_rows[(base, attack)] = _train(base, attack, None, steps,
                                               seed=seed)
            us0, acc0 = sync_rows[(base, attack)]
            emit(f"gar_async/{base}_sync", us0, f"acc={acc0:.3f}", "sync")
        us_sync, acc_sync = sync_rows[(base, attack)]
        for tau in taus:
            us, acc = _train(gar, attack, tau, steps, seed=seed)
            # per-step wall-clock if steps are priced by the fastest
            # worker (async) vs the slowest straggler (sync barrier):
            # the staggered schedule lets a tau-stale worker lag tau+1
            # steps behind the barrier pace
            speedup = (tau + 1) * us_sync / us
            emit(f"gar_async/{gar}_tau{tau}", us,
                 f"acc={acc:.3f};sync_acc={acc_sync:.3f};"
                 f"straggler_speedup={speedup:.2f}", "async")


if __name__ == "__main__":
    main()
