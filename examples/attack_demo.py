"""Reproduce the paper's Fig. 2/4 dynamics in miniature: train the paper's
MNIST MLP with Byzantine workers under the §3.2 attack and watch accuracy
per aggregation rule.

    PYTHONPATH=src python examples/attack_demo.py [--steps 120] [--f 9]

``--async-tau N`` switches to the asynchronous bounded-staleness runtime
(mirroring serve_demo.py's poisoned-replica demo): honest workers
deliver through a GradientBus under staleness bound N and the Byzantine
workers run the stale-replay attack — replaying a once-credible stale
gradient forever while stamping fresh arrivals.  Plain ``average`` is
flipped away from the converged clean run; the staleness-aware
``stale-krum`` / ``stale-bulyan-krum`` rules hold (see
docs/async-runtime.md):

    PYTHONPATH=src python examples/attack_demo.py --async-tau 3
"""
import argparse

import jax
import jax.numpy as jnp

from repro.data import ByzantineBatcher
from repro.data.synthetic import mnist_like
from repro.launch.device import enable_compile_cache
from repro.models import simple
from repro.optim import fading_lr, get_optimizer
from repro.agg import AggSpec
from repro.training import AsyncByzantineTrainer, ByzantineTrainer


def loss_fn(params, x, y):
    return simple.classification_loss(
        simple.mnist_mlp_forward(params, x), y, params)


def main_async(args):
    """Stale-replay vs the staleness-aware rules under bounded staleness."""
    xe, ye = mnist_like(1500, 10 ** 6, seed=0, noise=0.5)
    xe, ye = jnp.asarray(xe), jnp.asarray(ye)

    def eval_fn(params):
        return simple.accuracy(simple.mnist_mlp_forward(params, xe), ye)

    tau = args.async_tau
    print(f"async runtime: tau = {tau} (staggered fixed schedule), "
          f"n = {args.n_honest}+{args.f}, attack = stale-replay "
          f"(amplified stale content re-recorded every tau+1 steps)")
    accs = {}
    for gar, attack, f in (("average", "none", 0),
                           ("average", "stale_replay", args.f),
                           ("stale-krum", "stale_replay", args.f),
                           ("stale-bulyan-krum", "stale_replay", args.f)):
        spec = AggSpec(
            n_workers=args.n_honest + f, f=f, gar=gar, attack=attack,
            async_tau=tau,
            attack_kwargs=(("scale", -4.0), ("hold", tau + 1))
            if f else ())
        tr = AsyncByzantineTrainer(
            loss_fn, simple.init_mnist_mlp(jax.random.PRNGKey(1)),
            get_optimizer("sgd", fading_lr(args.eta0, 10000)), spec)
        tr.run(ByzantineBatcher("mnist", spec.n_honest, 83, seed=1,
                                noise=0.5),
               args.steps, eval_fn=eval_fn, eval_every=args.steps // 6)
        curve = " ".join(f"{h['step']}:{h['eval_acc']:.2f}"
                         for h in tr.history if "eval_acc" in h)
        final = float(eval_fn(tr.params))
        accs[(gar, attack)] = final
        tag = f"{gar}{' (clean ref)' if attack == 'none' else ' (attacked)'}"
        print(f"{tag:<32} acc: {curve}  final={final:.3f}  "
              f"stal_mean={tr.history[-1]['staleness_mean']:.2f}")
    clean = accs[("average", "none")]
    flipped = accs[("average", "stale_replay")] < clean - 0.15
    held = all(accs[(g, "stale_replay")] > clean - 0.05
               for g in ("stale-krum", "stale-bulyan-krum"))
    print(f"stale-replay flips the converged average run: "
          f"{'YES' if flipped else 'NO'}")
    print(f"stale-krum / stale-bulyan-krum hold: "
          f"{'YES' if held else 'NO'}")
    if not (flipped and held):
        raise SystemExit("demo expectation failed")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--n-honest", type=int, default=30)
    ap.add_argument("--f", type=int, default=9)
    ap.add_argument("--eta0", type=float, default=1.0)
    ap.add_argument("--async-tau", type=int, default=None,
                    help="run the asynchronous bounded-staleness demo "
                         "with this staleness bound (stale-replay vs "
                         "stale-krum/stale-bulyan)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.async_tau is not None:
        main_async(args)
        return

    xe, ye = mnist_like(1500, 10 ** 6, seed=0)
    xe, ye = jnp.asarray(xe), jnp.asarray(ye)

    def eval_fn(params):
        return simple.accuracy(simple.mnist_mlp_forward(params, xe), ye)

    print(f"n = {args.n_honest}+{args.f}, eta0 = {args.eta0}, "
          f"attack = omniscient lp (closed-form gamma, 'top' coordinate)")
    for gar in ("average", "krum", "geomed", "bulyan-krum"):
        attack = "none" if gar == "average" else "omniscient_lp"
        f = 0 if gar == "average" else args.f
        base = gar.replace("bulyan-", "")
        spec = AggSpec(n_workers=args.n_honest + f, f=f, gar=gar,
                       attack=attack,
                       attack_kwargs=(("gar_name", base),
                                      ("gamma", "closed"),
                                      ("coord", "top"),
                                      ("margin", 0.8)))
        tr = ByzantineTrainer(
            loss_fn, simple.init_mnist_mlp(jax.random.PRNGKey(1)),
            get_optimizer("sgd", fading_lr(args.eta0, 10000)), spec)
        tr.run(ByzantineBatcher("mnist", spec.n_honest, 83, seed=1),
               args.steps, eval_fn=eval_fn, eval_every=args.steps // 6)
        curve = " ".join(f"{h['step']}:{h['eval_acc']:.2f}"
                         for h in tr.history if "eval_acc" in h)
        tag = f"{gar}{' (clean ref)' if gar == 'average' else ' (attacked)'}"
        print(f"{tag:<28} acc: {curve}  final={float(eval_fn(tr.params)):.3f}")


if __name__ == "__main__":
    main()
