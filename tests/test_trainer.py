"""End-to-end Byzantine training behaviour (the paper's §5 claims, in
miniature): the attack poisons Krum's aggregate by Omega(sqrt(d)); Bulyan's
stays at honest-noise level; clean training learns."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.agg import AggSpec
from repro.data import ByzantineBatcher
from repro.data.synthetic import mnist_like
from repro.models import simple
from repro.optim import fading_lr, get_optimizer
from repro.training import ByzantineTrainer

KEY = jax.random.PRNGKey(1)


def loss_fn(params, x, y):
    return simple.classification_loss(
        simple.mnist_mlp_forward(params, x), y, params)


def _eval(params):
    xe, ye = mnist_like(1000, 10 ** 6, seed=0)
    return float(simple.accuracy(
        simple.mnist_mlp_forward(params, jnp.asarray(xe)), jnp.asarray(ye)))


def test_clean_training_learns():
    spec = AggSpec(n_workers=7, f=0, gar="average", attack="none")
    tr = ByzantineTrainer(loss_fn, simple.init_mnist_mlp(KEY),
                          get_optimizer("sgd", fading_lr(1.0, 10000)), spec)
    tr.run(ByzantineBatcher("mnist", 7, 32), 25)
    assert _eval(tr.params) > 0.9


def test_attack_poisons_krum_but_not_bulyan_step0():
    devs = {}
    for gar in ("krum", "bulyan-krum"):
        spec = AggSpec(n_workers=15, f=3, gar=gar,
                       attack="omniscient_lp",
                       attack_kwargs=(("gar_name", "krum"),))
        tr = ByzantineTrainer(loss_fn, simple.init_mnist_mlp(KEY),
                              get_optimizer("sgd", 0.1), spec)
        tr.run(ByzantineBatcher("mnist", spec.n_honest, 64), 1)
        devs[gar] = tr.history[0]["agg_dev"]
    assert devs["krum"] > 5 * devs["bulyan-krum"]
    assert devs["krum"] > 1.0


def test_byzantine_weight_metrics():
    spec = AggSpec(n_workers=15, f=3, gar="krum",
                   attack="omniscient_lp",
                   attack_kwargs=(("gar_name", "krum"),))
    tr = ByzantineTrainer(loss_fn, simple.init_mnist_mlp(KEY),
                          get_optimizer("sgd", 0.05), spec)
    tr.run(ByzantineBatcher("mnist", spec.n_honest, 64), 2)
    assert tr.history[0]["byz_weight"] >= 1.0  # the attack is selected


def test_bulyan_under_attack_still_learns():
    spec = AggSpec(n_workers=15, f=3, gar="bulyan-krum",
                   attack="omniscient_lp",
                   attack_kwargs=(("gar_name", "krum"),
                                  ("coord", "top")))
    tr = ByzantineTrainer(loss_fn, simple.init_mnist_mlp(KEY),
                          get_optimizer("sgd", fading_lr(1.0, 10000)), spec)
    tr.run(ByzantineBatcher("mnist", spec.n_honest, 64), 25)
    assert _eval(tr.params) > 0.85


def test_quorum_validation():
    with pytest.raises(ValueError):
        AggSpec(n_workers=9, f=3, gar="bulyan-krum").validate()


def test_attack_until_epoch_switches_off():
    spec = AggSpec(n_workers=15, f=3, gar="krum",
                   attack="omniscient_lp",
                   attack_kwargs=(("gar_name", "krum"),))
    tr = ByzantineTrainer(loss_fn, simple.init_mnist_mlp(KEY),
                          get_optimizer("sgd", 0.05), spec)
    tr.run(ByzantineBatcher("mnist", spec.n_honest, 64), 4, attack_until=2)
    assert tr.history[0]["byz_weight"] >= 1.0
    assert tr.history[3]["byz_weight"] == 0.0
