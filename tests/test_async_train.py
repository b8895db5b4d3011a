"""Asynchronous bounded-staleness runtime tests.

Pins the three contracts of ``repro.dist.async_train`` +
``repro.agg.staleness``:

  * tau = 0 degenerates to synchrony: the async step (flat and sharded
    builders) is bitwise-equal to the synchronous step on identical
    inputs — attacks and ``stale-*`` rules included;
  * the GradientBus respects its bounded-staleness ring: versions wrap
    through the delivery cycle, staleness never exceeds tau, slots hold
    exactly the gradient delivered at their version step;
  * the delay-exploiting ``stale_replay`` attack defeats plain
    ``average`` but not ``stale-bulyan-krum`` (nor ``stale-krum``) on
    the miniature MNIST protocol.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.agg import AggSpec, init_state, quorum, resolve_rule, rule_names
from repro.core import pytree as pt
from repro.data import ByzantineBatcher
from repro.data.synthetic import mnist_like
from repro.dist.async_train import (GradientBus, delivery_mask,
                                    init_async_state, init_bus,
                                    make_async_train_step, resolve_tau,
                                    update_bus)
from repro.dist.robust import distributed_aggregate
from repro.models import simple
from repro.optim import fading_lr, get_optimizer
from repro.training import (AsyncByzantineTrainer, init_flat_async_state,
                            make_async_byzantine_step, make_byzantine_step)

KEY = jax.random.PRNGKey(11)


def mnist_loss(params, x, y):
    return simple.classification_loss(
        simple.mnist_mlp_forward(params, x), y, params)


# ---------------------------------------------------------------------------
# bus mechanics
# ---------------------------------------------------------------------------

class TestBusMechanics:
    def test_resolve_tau_forms(self):
        np.testing.assert_array_equal(resolve_tau(3, 4), [3, 3, 3, 3])
        np.testing.assert_array_equal(resolve_tau((0, 1, 2, 8), 4),
                                      [0, 1, 2, 8])
        with pytest.raises(ValueError):
            resolve_tau(-1, 4)
        with pytest.raises(ValueError):
            resolve_tau((1, 2), 4)
        with pytest.raises(ValueError):
            resolve_tau((-1, 2, 0, 1), 4)   # per-worker bounds too

    def test_tau0_delivers_everyone_every_step(self):
        tau = resolve_tau(0, 9)
        versions = jnp.zeros((9,), jnp.int32)
        for sched in ("fixed", "random"):
            for t in range(5):
                m = delivery_mask(t, versions, tau, sched)
                assert bool(jnp.all(m)), (sched, t)

    def test_ring_wraparound_bounded_staleness(self):
        """Across several full delivery cycles (the ring wrapping), every
        slot holds exactly the gradient delivered at its version step
        and staleness never exceeds the per-worker bound."""
        n, d = 6, 8
        tau = resolve_tau((0, 1, 2, 3, 3, 2), n)
        base = jax.random.normal(KEY, (n, d))
        bus = init_bus(base)
        payloads = []
        for t in range(14):   # > 3 cycles of the largest tau+1
            fresh = base * (t + 1)          # step-tagged payload
            payloads.append(np.asarray(fresh))
            m = delivery_mask(t, bus.versions, tau, "fixed")
            bus = update_bus(bus, fresh, t, m)
            stal = t - np.asarray(bus.versions)
            assert stal.min() >= 0
            assert (stal <= np.asarray(tau)).all(), (t, stal)
            # slot w == the gradient computed at step versions[w]
            vers = np.asarray(bus.versions)
            want = np.stack([payloads[vers[w]][w] for w in range(n)])
            np.testing.assert_array_equal(np.asarray(bus.grads), want)
        # a tau=0 worker is always fresh; a tau=3 worker actually wrapped
        assert int(bus.versions[0]) == 13
        versions_seen = set()
        bus2 = init_bus(base)
        for t in range(8):
            m = delivery_mask(t, bus2.versions, tau, "fixed")
            bus2 = update_bus(bus2, base, t, m)
            versions_seen.add(int(bus2.versions[3]))
        assert len(versions_seen) > 1   # the tau=3 slot re-arms mid-run

    def test_random_schedule_respects_bound(self):
        n = 7
        tau = resolve_tau(3, n)
        bus = init_bus(jnp.zeros((n, 4)))
        for t in range(25):
            m = delivery_mask(t, bus.versions, tau, "random", seed=5)
            bus = update_bus(bus, jnp.zeros((n, 4)), t, m)
            assert (t - np.asarray(bus.versions) <= 3).all()

    def test_unknown_schedule_raises(self):
        with pytest.raises(ValueError, match="async_schedule"):
            delivery_mask(0, jnp.zeros((3,), jnp.int32),
                          resolve_tau(1, 3), "lossy")


# ---------------------------------------------------------------------------
# stale-<base> through the registry
# ---------------------------------------------------------------------------

class TestStaleRules:
    def test_stale_wraps_every_registered_base(self):
        """Acceptance: stale-<base> resolves for every registered rule
        (plus the composite families) with no per-rule forks, and with
        an all-fresh bus reproduces the base bitwise."""
        n, f, d = 11, 2, 24
        g = jax.random.normal(KEY, (n, d))
        bases = rule_names() + ["bulyan-krum", "buffered-cwmed"]
        for base_name in bases:
            rule = resolve_rule(f"stale-{base_name}")
            assert rule.stateful and "bus" in rule.state_fields, base_name
            assert rule.min_n(f) == quorum(base_name, f)
            base = resolve_rule(base_name)
            state = init_state(rule, g)
            res, state2 = rule.dense_fn(g, f, state)
            if base.stateful:
                bres, _ = base.dense_fn(g, f, init_state(base, g))
            else:
                bres = base.dense_fn(g, f)
            np.testing.assert_array_equal(np.asarray(res.gradient),
                                          np.asarray(bres.gradient),
                                          err_msg=base_name)
            assert int(state2.step) == 1, base_name

    def test_weight_schedules(self):
        from repro.agg import stale_weights
        s = jnp.asarray([0, 1, 3])
        np.testing.assert_allclose(stale_weights(s, "inv"),
                                   [1.0, 0.5, 0.25])
        np.testing.assert_allclose(stale_weights(s, "exp", lam=1.0),
                                   np.exp([0.0, -1.0, -3.0]), rtol=1e-6)
        with pytest.raises(ValueError, match="staleness weight"):
            stale_weights(s, "poly")

    def test_staleness_reweights_average(self):
        n, f, d = 8, 1, 16
        g = jax.random.normal(KEY, (n, d))
        rule = resolve_rule("stale-average")
        state = init_state(rule, g)
        versions = jnp.asarray([4] * (n - 1) + [0], jnp.int32)
        state = state._replace(step=jnp.asarray(4, jnp.int32),
                               bus=state.bus._replace(versions=versions))
        res, _ = rule.dense_fn(g, f, state)
        w = np.ones(n)
        w[-1] = 1.0 / 5.0          # staleness 4 under the inv schedule
        want = (np.asarray(g) * w[:, None]).mean(0)
        np.testing.assert_allclose(np.asarray(res.gradient), want,
                                   rtol=1e-4, atol=1e-6)

    @pytest.mark.parametrize("gar", ["stale-cwmed", "stale-krum",
                                     "stale-bulyan-krum",
                                     "stale-exp-trimmed_mean",
                                     "stale-buffered-cwmed"])
    def test_dense_tree_parity_under_staleness(self, gar):
        n, f = 11, 2
        k1, k2 = jax.random.split(KEY)
        tree = {"a": jax.random.normal(k1, (n, 4, 6)),
                "b": jax.random.normal(k2, (n, 32))}
        versions = jnp.asarray([0, 1, 2, 3, 3, 3, 2, 1, 0, 3, 2],
                               jnp.int32)
        rule = resolve_rule(gar)
        flat, ctx = pt.stack_flatten(tree)
        ds = init_state(rule, flat)
        ds = ds._replace(step=jnp.asarray(3, jnp.int32),
                         bus=ds.bus._replace(versions=versions))
        dres, _ = rule.dense_fn(flat, f, ds)
        ts = init_state(rule, tree, flat=False)
        ts = ts._replace(step=jnp.asarray(3, jnp.int32),
                         bus=ts.bus._replace(versions=versions))
        agg, _, ts2 = distributed_aggregate(tree, f, gar, state=ts)
        want = pt.unflatten(dres.gradient, ctx)
        for a, w in zip(jax.tree_util.tree_leaves(agg),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                       rtol=1e-4, atol=1e-5)
        assert int(ts2.step) == 4

    def test_stale_nesting_rejected(self):
        with pytest.raises(KeyError, match="nest"):
            resolve_rule("stale-stale-krum")

    def test_unknown_base_propagates(self):
        with pytest.raises(KeyError, match="unknown GAR"):
            resolve_rule("stale-no-such-rule")

    def test_dashless_stale_typo_rejected(self):
        """The stale_replay *attack* name (or a stalekrum typo) passed
        as a GAR must error loudly, not resolve to stale-average."""
        for typo in ("stale_replay", "stalekrum", "stale"):
            with pytest.raises(KeyError, match="unknown GAR"):
                resolve_rule(typo)


# ---------------------------------------------------------------------------
# tau = 0 reproduces the synchronous steps exactly
# ---------------------------------------------------------------------------

class TestTau0Equivalence:
    @pytest.mark.parametrize("gar,attack", [
        ("krum", "omniscient_lp"), ("stale-bulyan-krum", "none")])
    def test_flat_step_bitwise(self, gar, attack):
        f = 3 if attack != "none" else 0
        n_h = 12
        base = gar.replace("stale-", "")
        spec = AggSpec(
            n_workers=n_h + f, f=f, gar=gar, attack=attack,
            attack_kwargs=(("gar_name", "krum"),) if f else (),
            async_tau=0)
        sspec = AggSpec(
            n_workers=n_h + f, f=f, gar=base, attack=attack,
            attack_kwargs=spec.attack_kwargs)
        params = simple.init_mnist_mlp(KEY)
        opt = get_optimizer("sgd", 0.1)
        sync = jax.jit(make_byzantine_step(mnist_loss, opt, sspec))
        astep = jax.jit(make_async_byzantine_step(mnist_loss, opt, spec))
        x, y = ByzantineBatcher("mnist", n_h, 16).batch(0)
        x, y = jnp.asarray(x), jnp.asarray(y)
        k = jax.random.PRNGKey(9)
        p1, o1, m1 = sync(params, opt.init(params), x, y, k)
        st = init_flat_async_state(spec, params)
        p2, o2, m2, st2 = astep(params, opt.init(params), x, y, k, st)
        for a, b in zip(jax.tree_util.tree_leaves((p1, o1)),
                        jax.tree_util.tree_leaves((p2, o2))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for key in m1:
            np.testing.assert_array_equal(np.asarray(m1[key]),
                                          np.asarray(m2[key]))
        assert float(m2["staleness_max"]) == 0.0
        assert int(st2.step) == 1

    @pytest.mark.parametrize("gar,attack,f", [
        ("krum", "signflip", 2), ("stale-krum", "stale_replay", 2)])
    def test_dist_step_bitwise(self, gar, attack, f):
        """The sharded builder (executed unsharded — the identical step
        function runs under GSPMD, see tests/test_dist.py) at tau=0
        equals the synchronous make_train_step bitwise."""
        from repro.configs import get_reduced
        from repro.dist.train import make_train_step
        from repro.models import init_model

        cfg = get_reduced("llama3_2_3b")
        params = init_model(jax.random.PRNGKey(0), cfg)
        opt = get_optimizer("momentum", 1e-2)
        n, b, s = 7, 2, 16
        batch = {"tokens": jax.random.randint(KEY, (n, b, s), 0,
                                              cfg.vocab_size),
                 "labels": jax.random.randint(KEY, (n, b, s), 0,
                                              cfg.vocab_size)}
        spec = AggSpec(f=f, gar=gar, attack=attack, async_tau=0)
        sspec = AggSpec(f=f, gar=gar.replace("stale-", ""),
                        attack=attack)
        sync = jax.jit(make_train_step(cfg, sspec, opt))
        astep = jax.jit(make_async_train_step(cfg, spec, opt))
        p1, o1, m1 = sync(params, opt.init(params), batch)
        st = init_async_state(spec, params, n)
        p2, o2, m2, st2 = astep(params, opt.init(params), batch, st)
        for a, bb in zip(jax.tree_util.tree_leaves((p1, o1)),
                         jax.tree_util.tree_leaves((p2, o2))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))
        for key in m1:
            # params/opt-state are bitwise; metrics are compared at ulp
            # tolerance (the two programs may fuse the honest-mean
            # diagnostic differently)
            np.testing.assert_allclose(np.asarray(m1[key]),
                                       np.asarray(m2[key]),
                                       rtol=0, atol=1e-6)
        assert float(m2["delivered"]) == n
        assert int(st2.step) == 1

    def test_dist_step_runs_dropless_experts_bitwise(self):
        """The benchmark's expert configuration at a small width: dropless
        held-share experts, whose grouped matmul runs the workers one
        after another.  At tau=0 the asynchronous sharded step equals
        make_train_step bitwise on it and reports the expert loads."""
        from test_mla_moe import _cfg, _params, _tokens, family
        from repro.dist.train import make_train_step

        cfg = _cfg()
        mcfg = family.program_config(cfg)
        params = dict(_params(cfg), tail={})
        n = 7
        toks, labs = _tokens(cfg, n=n)
        batch = {"tokens": toks[:, None], "labels": labs[:, None]}
        opt = get_optimizer("adamw", 3e-4, weight_decay=0.01)
        kw = dict(f=1, gar="bulyan-krum", attack="omniscient_linf",
                  attack_kwargs=(("margin", 3.0),))
        spec = AggSpec(async_tau=0, **kw)
        sync = jax.jit(make_train_step(mcfg, AggSpec(**kw), opt))
        astep = jax.jit(make_async_train_step(mcfg, spec, opt))
        p1, o1, m1 = sync(params, opt.init(params), batch)
        st = init_async_state(spec, params, n)
        p2, o2, m2, st2 = astep(params, opt.init(params), batch, st)
        for a, bb in zip(jax.tree_util.tree_leaves((p1, o1)),
                         jax.tree_util.tree_leaves((p2, o2))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))
        moe = [k for k in m1 if k.startswith("moe_")]
        assert moe
        for key in m1:
            np.testing.assert_array_equal(np.asarray(m1[key]),
                                          np.asarray(m2[key]), err_msg=key)
        assert float(m2["delivered"]) == n
        assert int(st2.step) == 1


# ---------------------------------------------------------------------------
# the delay attacks vs the staleness-aware defenses
# ---------------------------------------------------------------------------

class TestStaleReplayDefense:
    def _run(self, gar, attack, steps=40):
        spec = AggSpec(n_workers=39, f=9, gar=gar, attack=attack,
                       async_tau=3,
                       attack_kwargs=(("scale", -4.0), ("hold", 4))
                       if attack == "stale_replay" else ())
        tr = AsyncByzantineTrainer(
            mnist_loss, simple.init_mnist_mlp(KEY),
            get_optimizer("sgd", fading_lr(1.0, 10000)), spec)
        tr.run(ByzantineBatcher("mnist", spec.n_honest, 32, seed=1,
                                noise=0.5), steps)
        xe, ye = mnist_like(800, 10 ** 6, seed=0, noise=0.5)
        acc = float(simple.accuracy(
            simple.mnist_mlp_forward(tr.params, jnp.asarray(xe)),
            jnp.asarray(ye)))
        return acc, tr

    def test_stale_replay_defeats_average_not_stale_bulyan(self):
        acc_avg, tr_avg = self._run("average", "stale_replay")
        acc_bul, _ = self._run("stale-bulyan-krum", "stale_replay")
        assert acc_avg < 0.85, acc_avg          # poisoned
        assert acc_bul > 0.95, acc_bul          # defense holds
        # the replayed rows really ride the bus: byz weight in average
        assert tr_avg.history[-1]["byz_weight"] > 0.0

    def test_stale_krum_holds_too(self):
        acc, _ = self._run("stale-krum", "stale_replay")
        assert acc > 0.9, acc

    def test_clean_async_training_learns(self):
        spec = AggSpec(n_workers=30, f=0, gar="stale-krum",
                       attack="none", async_tau=3)
        tr = AsyncByzantineTrainer(
            mnist_loss, simple.init_mnist_mlp(KEY),
            get_optimizer("sgd", fading_lr(1.0, 10000)), spec)
        tr.run(ByzantineBatcher("mnist", 30, 32, seed=1), 30)
        xe, ye = mnist_like(800, 10 ** 6, seed=0)
        acc = float(simple.accuracy(
            simple.mnist_mlp_forward(tr.params, jnp.asarray(xe)),
            jnp.asarray(ye)))
        assert acc > 0.9
        assert tr.history[-1]["staleness_mean"] > 0.0  # genuinely async

    def test_slow_drift_biases_average(self):
        """The drift integrates: average's deviation from the honest
        mean grows across steps while stale-bulyan's stays flat."""
        spec = AggSpec(n_workers=39, f=9, gar="average",
                       attack="slow_drift", async_tau=3,
                       attack_kwargs=(("eps", 1.0),))
        tr = AsyncByzantineTrainer(
            mnist_loss, simple.init_mnist_mlp(KEY),
            get_optimizer("sgd", fading_lr(1.0, 10000)), spec)
        tr.run(ByzantineBatcher("mnist", spec.n_honest, 32, seed=1), 30)
        devs = [h["agg_dev"] for h in tr.history]
        assert devs[-1] > 3 * max(devs[0], 1e-3)


# ---------------------------------------------------------------------------
# state plumbing
# ---------------------------------------------------------------------------

class TestAsyncStatePlumbing:
    def test_init_async_state_composes_with_eval_shape(self):
        from repro.configs import get_reduced
        from repro.models import init_model

        cfg = get_reduced("llama3_2_3b")
        params = jax.eval_shape(
            lambda: init_model(jax.random.PRNGKey(0), cfg))
        for gar in ("krum", "stale-bulyan-krum", "stale-buffered-cwmed"):
            spec = AggSpec(f=1, gar=gar, async_tau=2)
            st = jax.eval_shape(lambda: init_async_state(spec, params, 7))
            assert isinstance(st.bus, GradientBus)
            assert st.bus.versions.shape == (7,)
            assert st.step.dtype == jnp.int32

    def test_flat_state_always_carries_bus(self):
        params = simple.init_mnist_mlp(KEY)
        for gar in ("average", "stale-krum"):
            spec = AggSpec(n_workers=9, f=1, gar=gar,
                           attack="signflip", async_tau=1)
            st = init_flat_async_state(spec, params)
            assert isinstance(st.bus, GradientBus)
            assert st.bus.grads.shape[0] == 9
        spec = AggSpec(n_workers=9, f=0, gar="average",
                       attack="none")
        st = init_flat_async_state(spec, params)
        assert st.bus.grads.shape[0] == 9   # clean mode: n_honest rows

    def test_async_state_is_a_jitable_carry(self):
        n, f, d = 9, 1, 12
        g = jax.random.normal(KEY, (n, d))
        rule = resolve_rule("stale-cwmed")
        state = init_state(rule, g)

        @jax.jit
        def one(x, s):
            res, s = rule.dense_fn(x, f, s)
            return res.gradient, s

        _, state = one(g, state)
        _, state = one(g, state)
        assert int(state.step) == 2


# ---------------------------------------------------------------------------
# bounded-staleness observability + restore hygiene
# ---------------------------------------------------------------------------

class TestStalenessExcessAndRestore:
    @pytest.mark.parametrize("tau", [0, 2])
    def test_fixed_schedule_never_exceeds_declared_bound(self, tau):
        """``metrics["staleness_excess"]`` must stay 0 for the whole run
        under a ``fixed`` schedule — the deterministic round-robin delay
        pattern is tautologically within its own declared tau (the gap
        the audit sweep also pins; a nonzero value here means the bus
        update and the delivery mask disagree about ages)."""
        from repro.configs import get_reduced
        from repro.models import init_model

        cfg = get_reduced("llama3_2_3b")
        params = init_model(jax.random.PRNGKey(0), cfg)
        opt = get_optimizer("sgd", 1e-2)
        n, b, s = 7, 2, 16
        batch = {"tokens": jax.random.randint(KEY, (n, b, s), 0,
                                              cfg.vocab_size),
                 "labels": jax.random.randint(KEY, (n, b, s), 0,
                                              cfg.vocab_size)}
        spec = AggSpec(f=0, gar="stale-krum", attack="none",
                       async_tau=tau, async_schedule="fixed")
        astep = jax.jit(make_async_train_step(cfg, spec, opt))
        state = init_async_state(spec, params, n)
        opt_state = opt.init(params)
        for _ in range(2 * tau + 3):
            params, opt_state, m, state = astep(params, opt_state, batch,
                                                state)
            assert float(m["staleness_excess"]) == 0.0
            assert float(m["staleness_max"]) <= tau

    @pytest.mark.parametrize("name", ["stale-reputation-krum",
                                      "reputation-stale-krum"])
    def test_negative_restore_ages_clamp_through_reputation(self, name):
        """A bus restored with versions ahead of a zeroed step counter
        (the checkpoint-mismatch shape) yields *negative* slot ages.
        Both nesting orders must clamp the stale scale to 1 — bitwise
        the plain base at fresh reputation — instead of amplifying rows
        or pushing reputation out of [0, 1]."""
        n, f, d = 9, 2, 12
        rule = resolve_rule(name)
        g = jax.random.normal(KEY, (n, d), jnp.float32)
        state = init_state(rule, g)
        state = state._replace(bus=state.bus._replace(
            versions=jnp.full((n,), 5, jnp.int32)))  # step=0: age -5
        res, new_state = rule.dense_fn(g, f, state)
        assert bool(jnp.all(jnp.isfinite(res.gradient)))
        ref = resolve_rule("krum").dense_fn(g, f)
        np.testing.assert_array_equal(np.asarray(res.gradient),
                                      np.asarray(ref.gradient))
        rep = np.asarray(new_state.reputation)
        assert rep.min() >= 0.0 and rep.max() <= 1.0
