"""Registry-layer tests: every rule resolves through one resolver, the
dense (flat) and distributed (tree) paths of each rule agree on identical
data, the merged spec serves both historic call forms, and the stateful
buffered rules actually depend on their carried history."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.agg import (AggSpec, AggState, check_quorum, init_state, quorum,
                       resolve_rule, rule_names)
from repro.core import pytree as pt
from repro.dist.robust import distributed_aggregate

KEY = jax.random.PRNGKey(7)

# every stateless name the registry serves, incl. the composite family
STATELESS = ["average", "cwmed", "trimmed_mean", "krum", "geomed",
             "multikrum", "brute", "centered_clip", "bulyan-krum",
             "bulyan-geomed"]
STATEFUL = ["buffered-cwmed", "buffered-krum", "buffered-bulyan-krum",
            "centered_clip_momentum"]


def _stacked_tree(n, key=KEY):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"a": {"w": jax.random.normal(k1, (n, 8, 16))},
            "b": jax.random.normal(k2, (n, 64)),
            "c": jax.random.normal(k3, (n, 2, 3, 4))}


class TestResolver:
    def test_every_historic_name_resolves(self):
        for name in STATELESS + STATEFUL:
            rule = resolve_rule(name)
            assert rule.dense_fn is not None, name

    def test_registry_lists_base_rules(self):
        assert {"average", "krum", "multikrum", "geomed", "brute", "cwmed",
                "trimmed_mean", "centered_clip"} <= set(rule_names())

    def test_unknown_name_raises_keyerror(self):
        with pytest.raises(KeyError, match="unknown GAR"):
            resolve_rule("no-such-rule")

    def test_composites_are_cached(self):
        assert resolve_rule("bulyan-krum") is resolve_rule("bulyan-krum")
        assert (resolve_rule("buffered-cwmed")
                is resolve_rule("buffered-cwmed"))
        # a different window is a different rule
        assert (resolve_rule("buffered-cwmed", history_window=2)
                is not resolve_rule("buffered-cwmed", history_window=3))

    def test_old_get_gar_delegates(self):
        from repro.core import get_gar
        assert get_gar("krum") is resolve_rule("krum").dense_fn

    def test_quorums_unchanged(self):
        assert quorum("krum", 2) == 7
        assert quorum("bulyan-krum", 2) == 11
        assert quorum("buffered-krum", 2) == 7  # base's quorum

    def test_buffered_needs_stateless_base(self):
        with pytest.raises(KeyError, match="stateless base"):
            resolve_rule("buffered-centered_clip_momentum")


class TestSpecUnification:
    def test_old_names_are_one_type(self):
        """The per-path spec names are gone: every path takes AggSpec."""
        import repro.dist
        import repro.dist.train
        import repro.training
        import repro.training.trainer
        for mod in (repro.dist, repro.dist.train, repro.training,
                    repro.training.trainer):
            assert not hasattr(mod, "DistByzantineSpec"), mod
            assert not hasattr(mod, "ByzantineSpec"), mod

    def test_both_validate_forms_work(self):
        AggSpec(n_workers=15, f=3, gar="krum").validate()
        AggSpec(f=3, gar="krum").validate(15)

    def test_quorum_messages_agree(self):
        msgs = []
        for call in (lambda: AggSpec(n_workers=6, f=3,
                                     gar="krum").validate(),
               lambda: AggSpec(f=3, gar="krum").validate(6),
               lambda: check_quorum("krum", 6, 3)):
            with pytest.raises(ValueError) as e:
                call()
            msgs.append(str(e.value))
        assert len(set(msgs)) == 1, msgs
        assert "krum requires n >= 9 for f=3, got n=6" in msgs[0]

    def test_spec_is_frozen_and_replaceable(self):
        spec = AggSpec(f=2, gar="bulyan-krum")
        assert dataclasses.replace(spec, gar="krum").gar == "krum"
        with pytest.raises(dataclasses.FrozenInstanceError):
            spec.f = 3

    def test_sharded_validate_requires_tree_impl(self):
        """Only the *explicit* distributed opt-in rejects tree-less
        rules: bulyan-brute is fine on the flat path, rejected on the
        sharded one (its phase 1 needs the gradients, not just
        distances)."""
        AggSpec(n_workers=7, f=1, gar="bulyan-brute").validate()
        with pytest.raises(KeyError, match="distance-only"):
            AggSpec(f=1, gar="bulyan-brute").validate(
                7, distributed=True)

    @pytest.mark.parametrize("gar", ["bulyan-brute", "stale-bulyan-brute",
                                     "bulyan-cwmed"])
    def test_flat_validate_with_explicit_n_stays_flat(self, gar):
        """Regression: ``validate(n)`` used to infer ``distributed``
        from ``n_workers is not None``, wrongly demanding a tree
        implementation from flat specs validated with an explicit
        worker count."""
        AggSpec(f=1, gar=gar).validate(8)           # must not raise
        with pytest.raises(KeyError, match="bulyan"):
            AggSpec(f=1, gar=gar).validate(8, distributed=True)

    def test_distributed_keyerror_messages(self):
        """Both canonical distributed KeyError texts survive: the
        bulyan-family hint and the generic no-tree-implementation."""
        with pytest.raises(KeyError,
                           match="needs a distance-only base"):
            check_quorum("stale-bulyan-brute", 9, 1, distributed=True)
        rule_names()  # populate the registry
        from repro.agg.registry import RULES
        treeless = [n for n, r in RULES.items() if r.tree_fn is None]
        for name in treeless:
            with pytest.raises(KeyError,
                               match="no distributed"):
                check_quorum(name, resolve_rule(name).min_n(1), 1,
                             distributed=True)


class TestDenseTreeParity:
    """Every registered rule produces identical output via the core dense
    path and dist.distributed_aggregate on a stacked pytree."""

    @pytest.mark.parametrize("gar", STATELESS)
    def test_stateless_parity(self, gar):
        n, f = 11, 2
        tree = _stacked_tree(n)
        rule = resolve_rule(gar)
        agg, _ = distributed_aggregate(tree, f, gar)
        flat, ctx = pt.stack_flatten(tree)
        want = pt.unflatten(rule.dense_fn(flat, f).gradient, ctx)
        for a, w in zip(jax.tree_util.tree_leaves(agg),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(a, w, rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("gar", STATEFUL)
    def test_stateful_parity_across_steps(self, gar):
        n, f = 11, 2
        rule = resolve_rule(gar)
        trees = [_stacked_tree(n, jax.random.PRNGKey(s)) for s in range(3)]
        flat0, ctx = pt.stack_flatten(trees[0])
        dense_state = init_state(rule, flat0)
        tree_state = None
        for tree in trees:
            flat, ctx = pt.stack_flatten(tree)
            dres, dense_state = rule.dense_fn(flat, f, dense_state)
            agg, _, tree_state = distributed_aggregate(
                tree, f, gar, state=tree_state)
            want = pt.unflatten(dres.gradient, ctx)
            for a, w in zip(jax.tree_util.tree_leaves(agg),
                            jax.tree_util.tree_leaves(want)):
                np.testing.assert_allclose(a, w, rtol=1e-4, atol=1e-5)
        assert int(dense_state.step) == int(tree_state.step) == 3


class TestBufferedStatefulness:
    def test_same_inputs_different_history_different_output(self):
        """The new capability in one assertion: a buffered rule's output
        on identical submissions depends on the carried history."""
        n, f = 11, 2
        rule = resolve_rule("buffered-cwmed")
        g = jax.random.normal(jax.random.PRNGKey(0), (n, 32))
        other = 3.0 + jax.random.normal(jax.random.PRNGKey(1), (n, 32))

        fresh = init_state(rule, g)
        res_fresh, _ = rule.dense_fn(g, f, fresh)

        # absorb a different submission first -> different history
        warm = init_state(rule, g)
        _, warm = rule.dense_fn(other, f, warm)
        res_warm, _ = rule.dense_fn(g, f, warm)

        assert not np.allclose(res_fresh.gradient, res_warm.gradient)
        # the window mean pulls the output toward the absorbed history
        np.testing.assert_allclose(
            res_warm.gradient,
            np.median(np.asarray((g + other) / 2.0), axis=0),
            rtol=1e-4, atol=1e-5)

    def test_window_ring_buffer_evicts(self):
        """After window W more steps the old history is fully evicted."""
        n, f, w = 9, 1, 2
        rule = resolve_rule("buffered-cwmed", history_window=w)
        g = jax.random.normal(jax.random.PRNGKey(2), (n, 16))
        poison = 100.0 + jnp.zeros((n, 16))
        state = init_state(rule, g)
        _, state = rule.dense_fn(poison, f, state)
        for _ in range(w):
            res, state = rule.dense_fn(g, f, state)
        np.testing.assert_allclose(res.gradient,
                                   np.median(np.asarray(g), axis=0),
                                   rtol=1e-4, atol=1e-5)

    def test_centered_clip_momentum_carries_center(self):
        n, f = 9, 1
        rule = resolve_rule("centered_clip_momentum")
        g = jax.random.normal(jax.random.PRNGKey(3), (n, 16))
        far = 50.0 + jax.random.normal(jax.random.PRNGKey(4), (n, 16))
        s0 = init_state(rule, g)
        _, s_far = rule.dense_fn(far, f, s0)
        res_warm, _ = rule.dense_fn(g, f, s_far)
        res_cold, _ = rule.dense_fn(g, f, init_state(rule, g))
        # warm start from the far center clips toward it -> different agg
        assert not np.allclose(res_warm.gradient, res_cold.gradient)

    def test_bare_array_tree_self_initializes_correctly(self):
        """A bare (n, d) array is a valid pytree for the distributed
        engine; the self-initialized state must use the tree (tuple)
        buffer layout and the result must match the dense rule."""
        n, f = 9, 1
        g = jax.random.normal(jax.random.PRNGKey(5), (n, 32))
        rule = resolve_rule("buffered-cwmed")
        agg, _, state = distributed_aggregate(g, f, "buffered-cwmed")
        assert agg.shape == (32,)
        assert isinstance(state.history, tuple)
        dres, _ = rule.dense_fn(g, f, init_state(rule, g))
        np.testing.assert_allclose(agg, dres.gradient, rtol=1e-4,
                                   atol=1e-5)

    def test_state_is_a_jitable_carry(self):
        n, f = 9, 1
        rule = resolve_rule("buffered-krum")
        tree = _stacked_tree(n)
        state = init_state(rule, tree)

        @jax.jit
        def step(t, s):
            agg, _, s = distributed_aggregate(t, f, "buffered-krum",
                                              state=s)
            return agg, s

        _, state = step(tree, state)
        _, state = step(tree, state)
        assert int(state.step) == 2
        assert isinstance(state, AggState)


class TestTrainerIntegration:
    def test_buffered_rule_through_byzantine_trainer(self):
        """Acceptance: a stateful buffered-* rule runs through
        ByzantineTrainer with its AggState carried across steps."""
        from repro.data import ByzantineBatcher
        from repro.models import simple
        from repro.optim import get_optimizer
        from repro.training import ByzantineTrainer

        def loss_fn(params, x, y):
            return simple.classification_loss(
                simple.mnist_mlp_forward(params, x), y, params)

        spec = AggSpec(n_workers=9, f=1, gar="buffered-cwmed",
                       attack="signflip", history_window=3)
        tr = ByzantineTrainer(loss_fn, simple.init_mnist_mlp(KEY),
                              get_optimizer("sgd", 0.1), spec)
        tr.run(ByzantineBatcher("mnist", spec.n_honest, 16), 4)
        assert int(tr.agg_state.step) == 4
        assert len(tr.history) == 4

    def test_momentum_center_survives_attack_until_flip(self):
        """attack_until resizes only per-worker history buffers; the
        row-count-independent centered_clip_momentum center (the whole
        point of the momentum defense) must survive the flip."""
        from repro.data import ByzantineBatcher
        from repro.models import simple
        from repro.optim import get_optimizer
        from repro.training import ByzantineTrainer

        def loss_fn(params, x, y):
            return simple.classification_loss(
                simple.mnist_mlp_forward(params, x), y, params)

        spec = AggSpec(n_workers=9, f=1,
                       gar="centered_clip_momentum",
                       attack="signflip")
        tr = ByzantineTrainer(loss_fn, simple.init_mnist_mlp(KEY),
                              get_optimizer("sgd", 0.1), spec)
        tr.run(ByzantineBatcher("mnist", spec.n_honest, 16), 4,
               attack_until=2)
        assert int(tr.agg_state.step) == 4  # never re-zeroed
        assert float(jnp.sum(jnp.abs(tr.agg_state.center))) > 0.0

    def test_buffered_rule_through_dist_train_step(self):
        """Acceptance: the same rule through the dist make_train_step."""
        from repro.configs import get_reduced
        from repro.dist.train import (init_agg_state, make_loss_fn,
                                      make_train_step)
        from repro.models import init_model
        from repro.optim import get_optimizer

        cfg = get_reduced("llama3_2_3b")
        params = init_model(jax.random.PRNGKey(0), cfg)
        opt = get_optimizer("momentum", 1e-2)
        spec = AggSpec(f=0, gar="buffered-cwmed",
                       history_window=2)
        step = jax.jit(make_train_step(cfg, spec, opt))
        n, b, s = 4, 2, 16
        batch = {
            "tokens": jax.random.randint(KEY, (n, b, s), 0, cfg.vocab_size),
            "labels": jax.random.randint(KEY, (n, b, s), 0, cfg.vocab_size),
        }
        agg_state = init_agg_state(spec, params, n)
        assert int(agg_state.step) == 0
        params, opt_state, m, agg_state = step(params, opt.init(params),
                                               batch, agg_state)
        params, opt_state, m, agg_state = step(params, opt_state, batch,
                                               agg_state)
        assert int(agg_state.step) == 2
        assert bool(jnp.isfinite(m["loss"]))
