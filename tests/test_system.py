"""End-to-end behaviour of the paper's system (replaces the scaffold
placeholder): the full pipeline — data -> per-worker grads -> omniscient
attack -> GAR -> optimizer — reproduces the paper's headline contrast in
one step, and the LM stack trains under Bulyan."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.agg import AggSpec
from repro.configs import get_reduced
from repro.data import ByzantineBatcher
from repro.data.synthetic import lm_batches
from repro.dist.train import make_loss_fn, make_train_step
from repro.models import init_model
from repro.models import simple
from repro.optim import get_optimizer
from repro.training import ByzantineTrainer

KEY = jax.random.PRNGKey(0)


def test_headline_krum_vs_bulyan_one_round():
    """One aggregation round on real MLP gradients: the lp attack moves
    Krum's aggregate by Omega(sqrt(d)) on the attacked coordinate while
    Bulyan remains sigma-close to the honest mean (paper §3 + Prop 2)."""
    def loss_fn(params, x, y):
        return simple.classification_loss(
            simple.mnist_mlp_forward(params, x), y, params)

    devs = {}
    for gar in ("krum", "bulyan-krum"):
        spec = AggSpec(n_workers=15, f=3, gar=gar,
                       attack="omniscient_lp",
                       attack_kwargs=(("gar_name", "krum"),))
        tr = ByzantineTrainer(loss_fn, simple.init_mnist_mlp(KEY),
                              get_optimizer("sgd", 0.1), spec)
        tr.run(ByzantineBatcher("mnist", spec.n_honest, 83), 1)
        devs[gar] = tr.history[0]["agg_dev"]
    assert devs["krum"] > 5 * devs["bulyan-krum"]


def test_lm_training_under_attack_loss_decreases():
    """A small transformer trains on the Markov LM stream with Bulyan under
    the linf attack: loss must decrease (convergence claim, Cor. 2)."""
    cfg = get_reduced("llama3_2_3b")
    params = init_model(KEY, cfg)
    opt = get_optimizer("adam", 3e-3)
    spec = AggSpec(f=1, gar="bulyan-krum",
                   attack="omniscient_linf")
    step = jax.jit(make_train_step(cfg, spec, opt))
    state = opt.init(params)
    n, b, s = 7, 2, 64
    stream_vocab = 128  # small enough that 40 steps cover the table
    losses = []
    for t in range(40):
        toks = np.stack([lm_batches(stream_vocab, b, s, t * n + w,
                                    seed=3)[0] for w in range(n)])
        labs = np.stack([lm_batches(stream_vocab, b, s, t * n + w,
                                    seed=3)[1] for w in range(n)])
        batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labs)}
        params, state, m = step(params, state, batch)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.5, losses
