"""Arbitrary-f accuracy: reputation-weighted aggregation vs the quorum
family under an anti-aligned colluding majority.

The paper's quorum arithmetic caps every rule's tolerable f — Krum at
``n >= 2f + 3``, Bulyan at ``n >= 4f + 3`` — so at a fixed committee of
``n_total`` workers the quorum family simply *refuses to run* once f
crosses its bound.  ``reputation-<base>`` (ByGARS-style, see
``repro.agg.reputation``) has a quorum constant in f: it runs at any
attacker fraction and defends by down-weighting workers whose
submissions disagree with a clean auxiliary-batch gradient
(``AggSpec(aux_batch=...)`` — agreement with the emitted aggregate
alone bootstraps wrong once the colluders own the aggregate).

Rows: ``gar_reputation/<rule>_f<k>`` at f in {n/4, n/2, 3n/4} of
``n_total = 12`` workers under the ``colluding_majority`` attack with
``direction="anti"``, plus the clean ``average`` baseline.  The attack
is *norm-bounded* — the paper's own hidden-vulnerability regime: the
tight anti-aligned cluster wins Krum's selection outright at Krum's own
admissible f (the ``krum_f3`` row collapses) while a coordinate mean
barely moves (``average`` rides it out — but carries no Byzantine
guarantee once the bound is lifted), and ``reputation-krum`` repairs
the selection at every f.  Quorum-refused combinations emit a
``refused=quorum`` row instead of an accuracy — the refusal is the
datum.  The derived column carries ``acc`` and ``clean_frac`` (accuracy
as a fraction of the clean baseline); the ISSUE 9 acceptance bar is
``clean_frac >= 0.9`` for ``reputation-krum`` at f = n/2.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, make_eval, mnist_loss
from repro.agg import AggSpec
from repro.data import ByzantineBatcher
from repro.data.synthetic import mnist_like
from repro.models import simple
from repro.optim import fading_lr, get_optimizer
from repro.training import ByzantineTrainer

N_TOTAL = 12


def _aux_batch(seed: int = 123, batch: int = 64, noise: float = 0.5):
    """One clean MNIST batch: the trusted scoring signal of ByGARS."""
    x, y = mnist_like(batch, 10 ** 6, seed=seed, noise=noise)
    return jnp.asarray(x), jnp.asarray(y)


def _train(gar: str, f: int, steps: int, seed: int = 1):
    """Train one (rule, f) cell; returns (us_per_step, accuracy)."""
    reputed = gar.startswith("reputation-")
    n_honest = N_TOTAL - f
    spec = AggSpec(
        n_workers=N_TOTAL if f else n_honest, f=f, gar=gar,
        attack="colluding_majority" if f else "none", seed=seed,
        # eps is in delta_bar units along a *unit* direction, and this
        # easy task's honest workers agree to ~3 decimal places
        # (delta_bar ~ 0.006 vs a mean-gradient norm ~ 1.75): 300 puts
        # the cluster a full gradient-norm anti-aligned — norm-bounded
        # enough to look plausible, tight enough to win krum's selection
        attack_kwargs=(("direction", "anti"), ("eps", 300.0)) if f else (),
        rep_lr=0.9 if reputed else None,
        aux_batch=_aux_batch() if reputed else None)
    tr = ByzantineTrainer(
        mnist_loss, simple.init_mnist_mlp(jax.random.PRNGKey(seed)),
        # eta0 = 0.3 (the fig2 setting): the 12-worker committee's
        # aggregate is noisier than the 30-worker benches', and 1.0
        # diverges even clean
        get_optimizer("sgd", fading_lr(0.3, 10000)), spec)
    batcher = ByzantineBatcher("mnist", n_honest, 32, seed=seed, noise=0.5)
    tr.run(batcher, 3)                      # compile + warm the carry
    t0 = time.time()
    tr.run(batcher, steps, start_step=3)
    wall = time.time() - t0
    acc = float(make_eval("mnist")(tr.params))
    return 1e6 * wall / steps, acc


def main(steps: int = 40, seed: int = 1) -> None:
    """One row per (rule, f) on the fixed-committee MNIST protocol.

    Args:
      steps: measured training steps per cell (after a 3-step warmup).
      seed: PRNG seed threaded to init, batching and the attack — the
        accuracy columns are deterministic per seed.

    Returns:
      None (emits CSV rows).
    """
    us0, clean = _train("average", 0, steps, seed=seed)
    emit("gar_reputation/average_clean", us0, f"acc={clean:.3f}")
    fs = (N_TOTAL // 4, N_TOTAL // 2, 3 * N_TOTAL // 4)
    # krum is the defeated baseline: the tight cluster wins its selection
    # at krum's own admissible f=3, and it refuses past the quorum (as
    # does bulyan everywhere here); average rides the bounded offset out
    # but has no guarantee; reputation-krum holds at every f
    for gar in ("average", "krum", "bulyan-krum", "reputation-krum"):
        for f in fs:
            try:
                AggSpec(n_workers=N_TOTAL, f=f, gar=gar).validate()
            except ValueError:
                # the quorum family cannot even run here — the refusal
                # is the row (reputation-* must never land in it)
                emit(f"gar_reputation/{gar}_f{f}", 0.0, "refused=quorum")
                continue
            us, acc = _train(gar, f, steps, seed=seed)
            emit(f"gar_reputation/{gar}_f{f}", us,
                 f"acc={acc:.3f};clean_frac={acc / max(clean, 1e-9):.3f}")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="short runs (the CI smoke setting)")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    print("name,backend,us_per_call,derived")
    main(steps=120 if args.full else 40, seed=args.seed)
