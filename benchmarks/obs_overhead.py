"""Telemetry overhead: the forensics ring must cost < 5% of a train step.

Times the jitted flat Byzantine train step with ``telemetry=False`` and
``telemetry=True`` on identical data for several defended GARs, and
emits one ``obs/overhead_<gar>`` row per rule plus the headline
``obs/overhead`` row whose ``derived`` column carries the worst-case
ratio — the acceptance gate the CI fast job greps for.

The instrumented step is the *same computation* plus the in-graph
diagnostics (per-worker distances, selection mask, ring write), so the
ratio measures exactly what ``obs-*`` composites add.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, mnist_loss
from repro.agg import AggSpec
from repro.models import simple
from repro.optim import get_optimizer
from repro.training.trainer import (init_flat_agg_state,
                                    make_byzantine_step)


def _make_timer(spec: AggSpec, params, opt, x, y, reps: int):
    """Compile the flat step for ``spec``; return a us/call sampler."""
    step = jax.jit(make_byzantine_step(mnist_loss, opt, spec,
                                       attack_on=spec.attack != "none"))
    key = jax.random.PRNGKey(0)
    state = init_flat_agg_state(spec, params)
    opt_state = opt.init(params)
    stateful = spec.rule().stateful

    def call(p, o, s):
        if stateful:
            return step(p, o, x, y, key, s)
        return step(p, o, x, y, key) + (s,)

    out = call(params, opt_state, state)  # compile
    jax.block_until_ready(out)

    def sample() -> float:
        p, o, s = params, opt_state, state
        t0 = time.perf_counter()
        for _ in range(reps):
            out = call(p, o, s)
            p, o = out[0], out[1]
            s = out[-1] if stateful else s
        jax.block_until_ready(out)
        return 1e6 * (time.perf_counter() - t0) / reps

    return sample


def main(gars=("krum", "cwmed", "bulyan-krum"), n_workers: int = 15,
         f: int = 3, batch: int = 64, reps: int = 15,
         rounds: int = 5) -> None:
    """Emit the off/on/ratio rows for each defended GAR.

    Off and on are sampled in **interleaved rounds** (off, on, off, on,
    ...) and each side takes its best round, so slow machine-load drift
    cancels instead of landing entirely on one side of the ratio.

    Args:
      gars: base rule names to instrument (each must satisfy its quorum
        at ``(n_workers, f)``).
      n_workers: committee size of the flat protocol.
      f: injected Byzantine rows.
      batch: per-worker batch size.
      reps: timed calls per round (after one compile call).
      rounds: interleaved off/on rounds; each side keeps its minimum.
    """
    from repro.data import ByzantineBatcher

    params = simple.init_mnist_mlp(jax.random.PRNGKey(0))
    worst = 0.0
    for gar in gars:
        spec_off = AggSpec(n_workers=n_workers, f=f, gar=gar,
                           attack="signflip", telemetry=False)
        spec_on = AggSpec(n_workers=n_workers, f=f, gar=gar,
                          attack="signflip", telemetry=True)
        opt = get_optimizer("sgd", 0.05)
        x, y = ByzantineBatcher("mnist", spec_off.n_honest, batch).batch(0)
        x, y = jnp.asarray(x), jnp.asarray(y)
        sample_off = _make_timer(spec_off, params, opt, x, y, reps)
        sample_on = _make_timer(spec_on, params, opt, x, y, reps)
        off, on = float("inf"), float("inf")
        for _ in range(rounds):
            off = min(off, sample_off())
            on = min(on, sample_on())
        ratio = on / off
        worst = max(worst, ratio)
        emit(f"obs/overhead_{gar}", on - off,
             f"off={off:.0f}us;on={on:.0f}us;ratio={ratio:.3f}")
    emit("obs/overhead", 0,
         f"worst_ratio={worst:.3f};gate=1.05")


if __name__ == "__main__":
    main()
