"""The plain references against the program, on the CPU at reduced
sizes, with the weights the benchmark generates."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import config
from harness import common, weights
from reference import committee, ensemble


def _tiny(name):
    cfg = copy.deepcopy(config(name))
    if cfg["model_type"] == "qwen2":
        cfg["config"].update(hidden_size=128, intermediate_size=256,
                             num_attention_heads=4, num_key_value_heads=4,
                             num_hidden_layers=2, vocab_size=512)
    else:
        cfg["config"].update(d_model=64, n_layer=2, vocab_size=500,
                             d_state=16, headdim=16)
    return cfg


@pytest.mark.parametrize("name", ["qwen1.5-4b-1L", "mamba2-130m"])
def test_reference_logits_match_the_program(name):
    from repro.models import forward

    cfg = _tiny(name)
    ref = common.module("reference", cfg["reference"])
    mcfg = common.module("families", cfg["model_type"]).program_config(cfg)
    params = weights.make_params(ref, cfg["config"], 3)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 96), 0,
                              mcfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got, _ = forward(dict(params, tail={}), mcfg, toks)
        want = jnp.stack([ref.logits(params, cfg["config"], t)
                          for t in toks])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def _stack(seed, n=7, shapes=((40, 3), (17,))):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return {f"l{i}": jax.random.normal(k, (n,) + s)
            for i, (k, s) in enumerate(zip(keys, shapes))}


@pytest.mark.parametrize("seed", range(4))
def test_bulyan_krum_matches_the_programs_aggregation(seed):
    from repro.dist.robust import distributed_aggregate

    grads = _stack(seed)
    want, _ = distributed_aggregate(grads, 1, "bulyan-krum",
                                    distance_backend="xla")
    got, picked = committee.bulyan_krum(grads, 1)
    assert len(picked) == 5
    for k in grads:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-6, atol=1e-6)


def test_omniscient_linf_matches_the_programs_injection():
    from repro.dist.robust import inject_byzantine

    grads = _stack(9)
    want = inject_byzantine(grads, 1, "omniscient_linf", margin=3.0)
    got = committee.omniscient_linf(dict(grads), 1, 3.0)
    for k in grads:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-6, atol=1e-6)


def test_adamw_matches_the_programs_optimizer():
    from repro.optim import get_optimizer

    opt = {"lr": 3e-4, "weight_decay": 0.01, "b1": 0.9, "b2": 0.999,
           "eps": 1e-8}
    params = _stack(1, n=2)
    prog = get_optimizer("adamw", opt["lr"], weight_decay=0.01)
    state = prog.init(params)
    p, m, v = (params, jax.tree_util.tree_map(jnp.zeros_like, params),
               jax.tree_util.tree_map(jnp.zeros_like, params))
    q = params
    for t in range(1, 4):
        g = _stack(10 + t, n=2)
        q, state = prog.update(g, state, q)
        p, m, v = committee.adamw(p, m, v, g, t, opt)
    for k in params:
        np.testing.assert_allclose(p[k], q[k], rtol=1e-6, atol=1e-7)


def test_ensemble_aggregate_matches_the_program_position_by_position():
    from repro.dist.serve_robust import aggregate_logits

    stack = jax.random.normal(jax.random.PRNGKey(4), (7, 5, 64))
    stack = stack.at[-1].mul(-10.0)
    got = ensemble.aggregate(stack, 1)
    for s in range(stack.shape[1]):
        want, _ = aggregate_logits(stack[:, s], 1, "bulyan-krum",
                                   distance_backend="xla")
        np.testing.assert_allclose(got[s], want, rtol=1e-6, atol=1e-6)


def test_served_gap_is_the_widest_shortfall():
    agg = np.array([[0.0, 2.0, 1.0], [5.0, 4.5, 0.0]])
    assert ensemble.served_gap(agg, [1, 0]) == 0.0
    assert ensemble.served_gap(agg, [2, 1]) == pytest.approx(1.0)
