"""Contract battery for reputation-weighted aggregation (``repro.agg.
reputation``), the arbitrary-f family ``reputation-<base>``.

Pins the subsystem's load-bearing promises:

* **uniform reputation reproduces the base rule bitwise** — dense and
  tree paths, stateless and stateful bases, including the nested
  ``stale-`` / ``buffered-`` / ``fused-`` composites;
* the quorum is **constant in f** (``min_n(f) == base.min_n(0)``), so
  ``reputation-<base>`` runs in the f >= n/2 regime where the quorum
  family's canonical refusal fires;
* reputation **monotonically burns down** under the build-then-burn
  attack, and auxiliary-batch scoring defeats the anti-aligned colluding
  majority that drags ``average`` and fools under-declared ``krum``;
* the carried scores round-trip through the checkpoint store bitwise
  and compose with ``jax.eval_shape``;
* (hypothesis, when installed) weights live in [0, 1] with max exactly
  1, are invariant to rescaling the raw scores, and the score update is
  permutation-equivariant.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.agg import (AggSpec, check_quorum, init_state, resolve_rule,
                       reputation_scale, reputation_scores,
                       step_size_multiplier, tree_reputation_scores,
                       update_reputation)
from repro.agg.state import AggState
from repro.core import attacks
from repro.dist.robust import distributed_aggregate

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    from hypothesis.extra import numpy as hnp
    _HAS_HYPOTHESIS = True
except ImportError:  # the battery below degrades to a visible skip
    _HAS_HYPOTHESIS = False

KEY = jax.random.PRNGKey(7)

# every registered base family: plain rules plus one of each composite
# prefix (the resolver nests reputation- around all of them)
BASES = ["average", "brute", "centered_clip", "centered_clip_momentum",
         "cwmed", "geomed", "krum", "multikrum", "trimmed_mean",
         "bulyan-krum", "buffered-cwmed", "stale-krum", "fused-krum"]


def _base_result(base, g, f):
    """Run the base rule as the identity tests' reference."""
    rule = resolve_rule(base)
    if rule.stateful:
        res, _ = rule.dense_fn(g, f, init_state(rule, g))
        return res
    return rule.dense_fn(g, f)


class TestUniformIdentity:
    """Fresh (all-ones) reputation must be invisible to the base rule."""

    @pytest.mark.parametrize("base", BASES)
    def test_dense_bitwise(self, base):
        f = 2
        n = resolve_rule(base).min_n(f) + 1
        g = jax.random.normal(KEY, (n, 24), jnp.float32)
        rule = resolve_rule(f"reputation-{base}")
        assert rule.stateful
        assert rule.state_fields[0] == "reputation"
        state = init_state(rule, g)
        res, new_state = rule.dense_fn(g, f, state)
        want = _base_result(base, g, f)
        assert np.array_equal(np.asarray(res.gradient),
                              np.asarray(want.gradient))
        if want.selected is not None:
            assert np.array_equal(np.asarray(res.selected),
                                  np.asarray(want.selected))
        assert int(new_state.step) == 1
        rep = np.asarray(new_state.reputation)
        assert rep.shape == (n,)
        assert rep.min() >= 0.0 and rep.max() <= 1.0

    @pytest.mark.parametrize("base", [b for b in BASES
                                      if resolve_rule(b).tree_fn is not None])
    def test_tree_bitwise(self, base):
        f = 2
        n = resolve_rule(base).min_n(f) + 1
        kw, kb = jax.random.split(KEY)
        tree = {"w": jax.random.normal(kw, (n, 4, 3), jnp.float32),
                "b": jax.random.normal(kb, (n, 5), jnp.float32)}
        out = distributed_aggregate(tree, f, f"reputation-{base}")
        agg, _, new_state = out  # reputation-* is always stateful
        ref = distributed_aggregate(tree, f, base)
        ref_agg = ref[0]
        for a, b in zip(jax.tree_util.tree_leaves(agg),
                        jax.tree_util.tree_leaves(ref_agg)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        rep = np.asarray(new_state.reputation)
        assert rep.shape == (n,)
        assert rep.min() >= 0.0 and rep.max() <= 1.0

    def test_nested_identity_both_orders(self):
        # stale- around reputation- (and the reverse) still reproduces
        # the plain base at fresh state: zero staleness scales by 1,
        # uniform reputation blends by identity
        f, n = 2, 8
        tree = {"w": jax.random.normal(KEY, (n, 6), jnp.float32)}
        ref = distributed_aggregate(tree, f, "krum")[0]
        for name in ("reputation-stale-krum", "stale-reputation-krum"):
            agg, _, state = distributed_aggregate(tree, f, name)
            assert np.array_equal(np.asarray(agg["w"]),
                                  np.asarray(ref["w"]))
            rep = np.asarray(state.reputation)
            assert rep.min() >= 0.0 and rep.max() <= 1.0

    @pytest.mark.parametrize("base", BASES)
    def test_min_n_constant_in_f(self, base):
        rule = resolve_rule(f"reputation-{base}")
        base_rule = resolve_rule(base)
        assert rule.min_n(0) == rule.min_n(7) == base_rule.min_n(0)


class TestResolver:
    def test_reputation_cannot_nest_reputation(self):
        with pytest.raises(KeyError, match="nest"):
            resolve_rule("reputation-reputation-krum")

    def test_state_field_order_tracks_wrap_order(self):
        assert resolve_rule("reputation-stale-krum").state_fields == \
            ("reputation", "bus")
        assert resolve_rule("stale-reputation-krum").state_fields == \
            ("bus", "reputation")

    def test_composites_cache_on_schedule_params(self):
        assert resolve_rule("reputation-krum") is \
            resolve_rule("reputation-krum")
        assert resolve_rule("reputation-krum", rep_lr=0.25) is not \
            resolve_rule("reputation-krum")

    def test_unknown_gar_message_names_the_family(self):
        with pytest.raises(KeyError, match="reputation-<base>"):
            resolve_rule("no-such-rule")


class TestArbitraryF:
    """f >= n/2: quorum rules refuse canonically; reputation-* runs."""

    def test_quorum_family_refuses_canonically(self):
        n, f = 8, 4
        with pytest.raises(ValueError) as ei:
            check_quorum("krum", n, f)
        assert str(ei.value) == f"krum requires n >= 11 for f={f}, got n={n}"
        check_quorum("reputation-krum", n, f)  # must not raise

    def test_reputation_refusal_uses_the_same_message(self):
        with pytest.raises(ValueError) as ei:
            check_quorum("reputation-krum", 2, 6)
        assert str(ei.value) == "reputation-krum requires n >= 3 for " \
                                "f=6, got n=2"

    def test_colluding_majority_defeats_quorum_rules_not_reputation(self):
        # n = 2f: half the committee submits one identical anti-aligned
        # point a bounded distance off the honest mean
        n, f, d = 8, 4, 32
        honest = 1.0 + 0.3 * jax.random.normal(KEY, (n - f, d), jnp.float32)
        byz = attacks.colluding_majority(honest, f, eps=30.0,
                                         direction="anti")
        full = jnp.concatenate([honest, byz], axis=0)
        clean = jnp.mean(honest, axis=0)

        def dev(v):
            return float(jnp.linalg.norm(v - clean))

        # average is dragged by the cluster
        d_avg = dev(_base_result("average", full, 0).gradient)
        # krum with an under-declared f "satisfies" its quorum and picks
        # a colluder: the identical cluster is the tightest neighborhood
        res_k = _base_result("krum", full, 1)
        sel = np.asarray(res_k.selected)  # (n,) selection mask
        assert sel[n - f:].sum() >= 1.0 and sel[:n - f].sum() == 0.0
        d_krum = dev(res_k.gradient)
        # both deviate by several times the honest-mean noise (~0.9 here)
        assert min(d_avg, d_krum) > 3.0

        # reputation-krum at the TRUE f, scored against an auxiliary
        # clean gradient (the train steps' AggSpec(aux_batch=...) path:
        # override the rule's agreement update from the pre-step scores)
        rule = resolve_rule("reputation-krum")
        state = init_state(rule, full)
        for _ in range(8):
            rep_prev = state.reputation
            res, state = rule.dense_fn(full, f, state)
            state = state._replace(reputation=update_reputation(
                rep_prev, reputation_scores(full, clean)))
        rep = np.asarray(state.reputation)
        assert rep[n - f:].max() < 0.15   # colluders distrusted
        assert rep[:n - f].min() > 0.8    # honest workers keep trust
        assert dev(res.gradient) < 0.2 * min(d_avg, d_krum)


class TestBurnDecay:
    def test_reputation_burn_decays_monotonically(self):
        n, f, d, build = 9, 3, 16, 3
        rule = resolve_rule("reputation-cwmed")
        base = 1.0 + 0.2 * jax.random.normal(KEY, (n - f, d), jnp.float32)
        state = init_state(rule, jnp.zeros((n, d), jnp.float32))
        byz_rep = []
        for t in range(8):
            honest = base + 0.05 * jax.random.normal(
                jax.random.fold_in(KEY, t), base.shape, jnp.float32)
            byz = attacks.reputation_burn(honest, f, step=t, build=build)
            full = jnp.concatenate([honest, byz], axis=0)
            _, state = rule.dense_fn(full, f, state)
            byz_rep.append(float(np.asarray(state.reputation)[n - f:].mean()))
        # build phase: mean-echoing keeps the attacker fully trusted
        assert byz_rep[build - 1] > 0.8
        # burn phase: every step must strictly erode the score
        burn = byz_rep[build:]
        assert all(b < a for a, b in zip(burn, burn[1:]))
        assert burn[-1] < 0.15
        rep = np.asarray(state.reputation)
        assert rep[:n - f].min() > rep[n - f:].max()


class TestReputationMath:
    def test_scores_map_alignment_to_unit_interval(self):
        t = jnp.asarray([1.0, 2.0, 3.0, 4.0], jnp.float32)
        orth = jnp.asarray([2.0, -1.0, 4.0, -3.0], jnp.float32)
        g = jnp.stack([t, -t, orth, jnp.zeros_like(t)])
        s = np.asarray(reputation_scores(g, t))
        np.testing.assert_allclose(s, [1.0, 0.0, 0.5, 0.5], atol=1e-6)

    def test_tree_scores_match_flat_concatenation(self):
        k1, k2 = jax.random.split(KEY)
        a = jax.random.normal(k1, (5, 3, 2), jnp.float32)
        b = jax.random.normal(k2, (5, 4), jnp.float32)
        ta, tb = jnp.mean(a, 0), jnp.mean(b, 0)
        tree = np.asarray(tree_reputation_scores([a, b], [ta, tb]))
        flat = np.asarray(reputation_scores(
            jnp.concatenate([a.reshape(5, -1), b], axis=1),
            jnp.concatenate([ta.ravel(), tb])))
        np.testing.assert_allclose(tree, flat, rtol=1e-6)

    def test_update_repairs_out_of_range_restores(self):
        rep = jnp.asarray([-0.5, 2.0, 0.5], jnp.float32)
        new = np.asarray(update_reputation(
            rep, jnp.asarray([0.5, 0.5, 0.5]), 0.0, 1.0))
        assert new.min() >= 0.0 and new.max() <= 1.0

    def test_uniform_trust_multiplies_step_by_exactly_one(self):
        state = AggState(step=jnp.zeros((), jnp.int32),
                         reputation=jnp.ones((6,), jnp.float32))
        assert float(step_size_multiplier(state)) == 1.0
        assert np.array_equal(np.asarray(reputation_scale(state)),
                              np.ones(6, np.float32))


class TestCheckpointAndTracing:
    def test_checkpoint_roundtrip_continues_bitwise(self, tmp_path):
        from repro.checkpoint.store import load_checkpoint, save_checkpoint
        rule = resolve_rule("reputation-krum")
        f, n = 2, 8
        g = jax.random.normal(KEY, (n, 12), jnp.float32)
        state = init_state(rule, g)
        for t in range(3):
            _, state = rule.dense_fn(g + 0.01 * t, f, state)
        path = str(tmp_path / "agg_state")
        save_checkpoint(path, state, step=3)
        loaded, step = load_checkpoint(path, init_state(rule, g))
        assert step == 3
        for a, b in zip(jax.tree_util.tree_leaves(state),
                        jax.tree_util.tree_leaves(loaded)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        r1, s1 = rule.dense_fn(g, f, state)
        r2, s2 = rule.dense_fn(g, f, loaded)
        assert np.array_equal(np.asarray(r1.gradient),
                              np.asarray(r2.gradient))
        assert np.array_equal(np.asarray(s1.reputation),
                              np.asarray(s2.reputation))

    def test_eval_shape_composability(self):
        rule = resolve_rule("reputation-krum")
        tmpl = jax.ShapeDtypeStruct((9, 16), jnp.float32)
        st0 = jax.eval_shape(lambda: init_state(rule, tmpl))
        assert st0.reputation.shape == (9,)
        assert st0.reputation.dtype == jnp.float32

        def step(g, s):
            res, s2 = rule.dense_fn(g, 4, s)
            return res.gradient, s2

        out, s2 = jax.eval_shape(step, tmpl,
                                 init_state(rule, jnp.zeros((9, 16))))
        assert out.shape == (16,)
        assert s2.reputation.shape == (9,)

    def test_dist_init_agg_state_under_eval_shape(self):
        from repro.dist.train import init_agg_state
        spec = AggSpec(f=2, n_workers=7, gar="reputation-krum")
        params = {"w": jax.ShapeDtypeStruct((4, 3), jnp.float32),
                  "b": jax.ShapeDtypeStruct((3,), jnp.float32)}
        st0 = jax.eval_shape(lambda: init_agg_state(spec, params, 7))
        assert st0.reputation.shape == (7,)

    def test_jit_carry(self):
        rule = resolve_rule("reputation-krum")
        g = jax.random.normal(KEY, (8, 10), jnp.float32)
        state = init_state(rule, g)

        @jax.jit
        def step(grads, s):
            res, s2 = rule.dense_fn(grads, 2, s)
            return res.gradient, s2

        for _ in range(3):
            out, state = step(g, state)
        assert np.isfinite(np.asarray(out)).all()
        assert int(state.step) == 3


class TestTrainerIntegration:
    def test_flat_trainer_crushes_signflip_and_scales_steps(self):
        from repro.data import ByzantineBatcher
        from repro.models import simple
        from repro.optim import get_optimizer
        from repro.training import ByzantineTrainer

        def loss(params, x, y):
            return simple.classification_loss(
                simple.mnist_mlp_forward(params, x), y, params)

        spec = AggSpec(n_workers=9, f=2, gar="reputation-krum",
                       attack="signflip", rep_lr=0.5)
        tr = ByzantineTrainer(loss, simple.init_mnist_mlp(KEY),
                              get_optimizer("sgd", 0.05), spec)
        tr.run(ByzantineBatcher("mnist", spec.n_honest, 32), 5)
        rep = np.asarray(tr.agg_state.reputation)
        assert rep.shape == (9,)
        # sign-flipped submissions anti-align with the aggregate, so the
        # agreement EMA pushes the Byzantine tail of the stack below the
        # honest workers
        assert rep[-2:].max() < rep[:-2].min()
        assert 0.0 < tr.history[-1]["step_scale"] <= 1.0


if _HAS_HYPOTHESIS:
    @st.composite
    def _stacks(draw):
        n = draw(st.integers(2, 6))
        d = draw(st.integers(1, 8))
        elems = st.floats(-100.0, 100.0, width=32)
        g = draw(hnp.arrays(np.float32, (n, d), elements=elems))
        t = draw(hnp.arrays(np.float32, (d,), elements=elems))
        perm = draw(st.permutations(list(range(n))))
        return g, t, np.asarray(perm)

    _reps = hnp.arrays(
        np.float32, hnp.array_shapes(min_dims=1, max_dims=1,
                                     min_side=2, max_side=8),
        elements=st.floats(0.0, 1.0, width=32),
    ).filter(lambda r: float(r.max()) > 1e-6)

    def _state_of(rep):
        return AggState(step=jnp.zeros((), jnp.int32),
                        reputation=jnp.asarray(rep))

    class TestPropertyBattery:
        @given(rep=_reps)
        @settings(max_examples=50, deadline=None)
        def test_weights_unit_interval_max_exactly_one(self, rep):
            w = np.asarray(reputation_scale(_state_of(rep)))
            assert w.min() >= 0.0 and w.max() <= 1.0
            assert w.max() == 1.0  # x / x is exactly 1.0 in fp

        @given(rep=_reps, c=st.floats(0.1, 10.0))
        @settings(max_examples=50, deadline=None)
        def test_weights_invariant_to_rescaling(self, rep, c):
            w1 = np.asarray(reputation_scale(_state_of(rep)))
            w2 = np.asarray(reputation_scale(_state_of(c * rep)))
            np.testing.assert_allclose(w1, w2, rtol=1e-5, atol=1e-6)

        @given(data=_stacks())
        @settings(max_examples=50, deadline=None)
        def test_scores_permutation_equivariant(self, data):
            g, t, perm = data
            sp = np.asarray(reputation_scores(jnp.asarray(g[perm]),
                                              jnp.asarray(t)))
            s = np.asarray(reputation_scores(jnp.asarray(g),
                                             jnp.asarray(t)))
            assert np.array_equal(sp, s[perm])  # row-independent: bitwise
            assert s.min() >= -1e-5 and s.max() <= 1.0 + 1e-5

        @given(rep=hnp.arrays(np.float32, (5,),
                              elements=st.floats(-10.0, 10.0, width=32)),
               scores=hnp.arrays(np.float32, (5,),
                                 elements=st.floats(0.0, 1.0, width=32)),
               lr=st.floats(0.0, 1.0), decay=st.floats(0.01, 1.0))
        @settings(max_examples=50, deadline=None)
        def test_update_always_lands_in_unit_interval(self, rep, scores,
                                                      lr, decay):
            new = np.asarray(update_reputation(jnp.asarray(rep),
                                               jnp.asarray(scores),
                                               lr, decay))
            assert new.min() >= 0.0 and new.max() <= 1.0
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_battery_requires_hypothesis():
        """Visible placeholder for the hypothesis-backed battery above."""
