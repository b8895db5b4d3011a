"""Paper Fig. 6: the cost of Bulyan without an adversary — accuracy at a
fixed step vs mini-batch size, Average vs Bulyan(Krum), n = 39 workers,
f declared 9 but zero actual Byzantines.

Expected: Bulyan's convergence-speed loss shrinks to ~nothing at a
reasonable batch size (paper: 24 images/batch for MNIST).
"""
from __future__ import annotations

from benchmarks.common import emit, run_experiment


def main(steps: int = 60) -> None:
    for batch in (4, 12, 24, 48):
        accs = {}
        for gar in ("average", "bulyan-krum"):
            r = run_experiment(kind="mnist", gar=gar, attack="none",
                               n_honest=39, f=0, steps=steps, batch=batch,
                               attack_kwargs=(), eval_every=steps)
            # note: f=0 actual; Bulyan still *declares* f=9 via declared_f
            accs[gar] = r
        # re-run bulyan with declared f=9 (the paper's setting)
        import jax
        from repro.data import ByzantineBatcher
        from repro.models import simple
        from repro.optim import fading_lr, get_optimizer
        from repro.agg import AggSpec
        from repro.training import ByzantineTrainer
        from benchmarks.common import make_eval, mnist_loss
        spec = AggSpec(n_workers=39, f=0, gar="bulyan-krum",
                       attack="none", declared_f=9)
        tr = ByzantineTrainer(mnist_loss,
                              simple.init_mnist_mlp(jax.random.PRNGKey(1)),
                              get_optimizer("sgd", fading_lr(1.0, 10000)),
                              spec)
        import time
        t0 = time.time()
        tr.run(ByzantineBatcher("mnist", 39, batch, seed=1), steps)
        us = 1e6 * (time.time() - t0) / steps
        acc_b = float(make_eval("mnist")(tr.params))
        emit(f"fig6/batch{batch}", us,
             f"avg={accs['average']['final_acc']:.3f};"
             f"bulyan_f9={acc_b:.3f};"
             f"gap={accs['average']['final_acc'] - acc_b:+.3f}")


if __name__ == "__main__":
    main()
