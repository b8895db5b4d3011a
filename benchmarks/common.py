"""Shared helpers for the paper-figure benchmarks.

Worker counts are the paper's own (§5.2):
  Fig 2/3:  Krum/GeoMed 30 honest + 27 Byzantine (n = 2f+3 minimal quorum),
            Brute 6 + 5, Average 30 + 0 (the clean reference).
  Fig 4/5:  30 honest + 9 Byzantine (n = 39 = 4f+3, Bulyan's minimal quorum).
  Fig 6:    n = 39 workers, no adversary, f declared 9.

The omniscient attack uses the paper's §B closed-form gamma estimate (the
"linear regression" shortcut) with a safety margin; the per-step
``byz_weight`` metric verifies the submission is actually selected.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.agg import AggSpec
from repro.data import ByzantineBatcher
from repro.data.synthetic import cifar_like, mnist_like
from repro.models import simple
from repro.optim import fading_lr, get_optimizer
from repro.training import ByzantineTrainer


def mnist_loss(params, x, y):
    return simple.classification_loss(
        simple.mnist_mlp_forward(params, x), y, params)


def cifar_loss(params, x, y):
    return simple.classification_loss(
        simple.cifar_cnn_forward(params, x), y, params)


def make_eval(kind: str, n: int = 1000, noise: float = 0.5):
    if kind == "mnist":
        xe, ye = mnist_like(n, 10 ** 6, seed=0, noise=noise)
        fwd = simple.mnist_mlp_forward
    else:
        xe, ye = cifar_like(n, 10 ** 6, seed=0, noise=noise)
        fwd = simple.cifar_cnn_forward
    xe, ye = jnp.asarray(xe), jnp.asarray(ye)

    def eval_fn(params):
        return simple.accuracy(fwd(params, xe), ye)

    return eval_fn


def run_experiment(*, kind: str, gar: str, attack: str, n_honest: int,
                   f: int, steps: int, batch: int = 16, eta0: float = 0.3,
                   r_eta: float = 10000.0, attack_until: Optional[int] = None,
                   attack_kwargs: tuple = (), eval_every: int = 5,
                   noise: float = 0.5, seed: int = 1) -> Dict:
    key = jax.random.PRNGKey(seed)
    if kind == "mnist":
        params = simple.init_mnist_mlp(key)
        loss = mnist_loss
    else:
        params = simple.init_cifar_cnn(key)
        loss = cifar_loss
    spec = AggSpec(n_workers=n_honest + f, f=f, gar=gar,
                   attack=attack, attack_kwargs=attack_kwargs)
    opt = get_optimizer("sgd", fading_lr(eta0, r_eta))
    trainer = ByzantineTrainer(loss, params, opt, spec, seed=seed)
    eval_fn = make_eval(kind, noise=noise)
    t0 = time.time()
    trainer.run(ByzantineBatcher(kind, n_honest, batch, seed=seed,
                                 noise=noise), steps,
                attack_until=attack_until, eval_fn=eval_fn,
                eval_every=eval_every)
    wall = time.time() - t0
    accs = [(h["step"], h["eval_acc"]) for h in trainer.history
            if "eval_acc" in h]
    acc_vals = [a for _, a in accs]
    to90 = next((s for s, a in accs if a >= 0.9), None)
    return {
        "final_acc": float(eval_fn(trainer.params)),
        "accs": accs,
        "mean_acc": float(np.mean(acc_vals)) if acc_vals else 0.0,
        "steps_to_90": to90,
        "us_per_step": 1e6 * wall / steps,
        "mean_byz_weight": float(np.mean(
            [h["byz_weight"] for h in trainer.history])),
        "max_agg_dev": float(np.max(
            [h["agg_dev"] for h in trainer.history])),
        "history": trainer.history,
    }


def emit(name: str, us: float, derived: str, backend: str = "-") -> None:
    """One CSV row: ``name,backend,us_per_call,derived``.

    ``backend`` tags rows produced under a specific distance backend
    (``xla`` / ``pallas``); ``"-"`` marks backend-independent rows.
    """
    print(f"{name},{backend},{us:.0f},{derived}", flush=True)
