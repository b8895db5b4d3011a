"""Adversarial self-audit tests: the harness passes on the shipped tree,
catches intentionally broken rules, and the satellite bugfixes (explicit
distributed flag, staleness clamp, canonical quorum errors, bounded
staleness) stay fixed."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.agg import AggSpec, check_quorum, init_state, resolve_rule
from repro.agg.registry import AggregatorRule
from repro.agg.staleness import stale_scale
from repro.audit import (AuditReport, SweepConfig, audit_roster, certify,
                         check_quorum_contract, check_rule_output,
                         effective_stack, measure_leeway, run_sweep)
from repro.audit.invariants import (check_convex, check_finite, check_hull,
                                    check_trimmed)
from repro.audit.leeway import slope
from repro.core.types import AggResult

KEY = jax.random.PRNGKey(3)

#: tiny grid: every rule family and contract section, minimal corners
TINY = SweepConfig(d=8, fs=(1,), extra_n=(0,),
                   attacks=("none", "omniscient_lp", "signflip",
                            "stale_replay"),
                   steps=2, taus=(0, 2), quorum_fs=(1, 2))


class TestSweep:
    def test_tiny_sweep_is_clean(self):
        report = run_sweep(TINY)
        assert report.cases > 300
        assert report.ok(), report.violations
        # every section actually ran
        assert set(report.sections) == {"invariants", "quorum",
                                        "identity", "arbitrary-f",
                                        "staleness", "fp32",
                                        "speculative"}

    def test_roster_covers_every_family(self):
        roster = audit_roster()
        from repro.agg import rule_names
        assert set(rule_names()) <= set(roster)
        for prefix in ("bulyan-", "buffered-", "stale-", "stale-exp-",
                       "reputation-"):
            assert any(r.startswith(prefix) for r in roster), prefix
        for name in roster:
            assert resolve_rule(name).dense_fn is not None, name


class TestInvariantCheckers:
    """The checkers must *fail* on doctored outputs — a harness that
    can't catch a violation certifies nothing."""

    def _stack(self, n=5, d=4):
        return np.asarray(jax.random.normal(KEY, (n, d)), np.float32)

    def test_finite_catches_nan(self):
        assert check_finite(jnp.asarray([1.0, jnp.nan]))
        assert not check_finite(jnp.asarray([1.0, 2.0]))

    def test_hull_catches_escape(self):
        stack = self._stack()
        inside = stack.mean(axis=0)
        outside = stack.max(axis=0) + 1.0
        assert not check_hull(jnp.asarray(inside), stack)
        assert check_hull(jnp.asarray(outside), stack)

    def test_trimmed_catches_extreme(self):
        stack = self._stack(n=7)
        med = np.median(stack, axis=0)
        assert not check_trimmed(jnp.asarray(med), stack, f=2)
        assert check_trimmed(jnp.asarray(stack.max(axis=0)), stack, f=2)

    def test_convex_catches_lying_certificate(self):
        stack = self._stack()
        w = np.zeros(5, np.float32)
        w[0] = 1.0
        # certificate says worker 0, gradient is worker 1
        assert check_convex(jnp.asarray(stack[1]), jnp.asarray(w), stack)
        assert not check_convex(jnp.asarray(stack[0]), jnp.asarray(w),
                                stack)
        # weights that don't sum to 1 / go negative
        assert check_convex(jnp.asarray(stack[0]),
                            jnp.asarray(2.0 * w), stack)
        w2 = np.full(5, 0.4, np.float32)
        w2[0] = -0.6
        assert check_convex(jnp.asarray(w2 @ stack), jnp.asarray(w2),
                            stack)

    def test_weakened_rule_fails_the_output_audit(self):
        """A doctored 'krum' whose certificate lies about the winner is
        exactly the regression the declared-invariant dispatch exists
        to catch."""
        krum = resolve_rule("krum")

        def lying(grads, f):
            res = krum.dense_fn(grads, f)
            # report the right winner, emit a shifted aggregate
            return AggResult(res.gradient + 10.0, res.selected,
                             res.scores)

        fake = dataclasses.replace(krum, name="lying-krum",
                                   dense_fn=lying)
        grads = jnp.asarray(self._stack(n=9, d=4))
        res = fake.dense_fn(grads, 2)
        eff = effective_stack(fake, grads, None)
        violations = check_rule_output(fake, res.gradient, res.selected,
                                       eff, 2)
        assert violations  # hull + convex both blow up

    def test_effective_stack_recomputes_stale_scale(self):
        rule = resolve_rule("stale-krum")
        grads = jnp.asarray(self._stack(n=9, d=4))
        state = init_state(rule, grads)
        state = state._replace(
            step=jnp.asarray(4, jnp.int32),
            bus=state.bus._replace(
                versions=jnp.asarray([4, 3, 2, 1, 4, 3, 2, 1, 4],
                                     jnp.int32)))
        eff = effective_stack(rule, grads, state)
        scale = np.asarray(stale_scale(state), np.float32)
        np.testing.assert_allclose(
            eff, np.asarray(grads) * scale[:, None], rtol=1e-6)


class TestQuorumContract:
    """Satellite: every composite family raises the single canonical
    ValueError below quorum and the canonical KeyError distributed."""

    FAMILIES = ["krum", "bulyan-krum", "buffered-cwmed", "buffered-krum",
                "buffered-bulyan-krum", "stale-krum", "stale-cwmed",
                "stale-bulyan-krum", "stale-exp-krum", "stale-exp-cwmed",
                "stale-buffered-cwmed"]

    @pytest.mark.parametrize("gar", FAMILIES)
    def test_canonical_value_error(self, gar):
        assert check_quorum_contract(gar, 2) == []
        need = resolve_rule(gar).min_n(2)
        with pytest.raises(ValueError) as e:
            check_quorum(gar, need - 1, 2)
        assert str(e.value) == (
            f"{gar} requires n >= {need} for f=2, got n={need - 1}")

    @pytest.mark.parametrize("gar", ["bulyan-brute", "stale-bulyan-brute",
                                     "buffered-bulyan-cwmed"])
    def test_distributed_keyerror_for_treeless_composites(self, gar):
        with pytest.raises(KeyError, match="distance-only base"):
            check_quorum(gar, 11, 2, distributed=True)
        check_quorum(gar, 11, 2)  # flat path: fine

    def test_contract_checker_spots_a_drifted_message(self):
        """check_quorum_contract itself must flag a rule whose min_n
        and quorum error disagree — simulate by probing a composite
        with the wrong window (same message, so this passes) and a
        plain bogus name (KeyError -> caught upstream)."""
        assert check_quorum_contract("buffered-cwmed", 1,
                                     history_window=2) == []
        with pytest.raises(KeyError, match="unknown GAR"):
            check_quorum_contract("no-such-rule", 1)


class TestStalenessClamp:
    """Satellite: checkpoint-restore can leave bus versions ahead of the
    carried step; staleness must clamp at 0, never amplify."""

    def _state(self, rule, n=9, d=4, step=0, versions=None):
        grads = jnp.zeros((n, d), jnp.float32)
        state = init_state(rule, grads)
        if versions is not None:
            state = state._replace(
                step=jnp.asarray(step, jnp.int32),
                bus=state.bus._replace(
                    versions=jnp.asarray(versions, jnp.int32)))
        return state

    def test_negative_staleness_clamps_to_fresh(self):
        rule = resolve_rule("stale-krum")
        # restored bus stamped ahead of a zeroed step counter
        state = self._state(rule, step=0, versions=[5] * 9)
        np.testing.assert_array_equal(np.asarray(stale_scale(state)),
                                      np.ones(9, np.float32))

    def test_never_amplifies_mixed_clock_skew(self):
        rule = resolve_rule("stale-krum")
        state = self._state(rule, step=3,
                            versions=[9, 3, 1, 0, 7, 3, 2, 5, 3])
        scale = np.asarray(stale_scale(state))
        assert (scale <= 1.0 + 1e-7).all()
        assert scale.max() == pytest.approx(1.0)

    def test_restore_path_output_equals_base(self):
        """A uniformly future-stamped bus (the restore corner) must be
        bitwise the base rule, inv and exp weights alike."""
        grads = jnp.asarray(
            np.asarray(jax.random.normal(KEY, (9, 6)), np.float32))
        for name, base in (("stale-krum", "krum"),
                           ("stale-exp-cwmed", "cwmed")):
            rule = resolve_rule(name)
            state = self._state(rule, step=0, versions=[4] * 9)
            got, _ = rule.dense_fn(grads, 2, state)
            want = resolve_rule(base).dense_fn(grads, 2)
            np.testing.assert_array_equal(np.asarray(got.gradient),
                                          np.asarray(want.gradient))


class TestStalenessBound:
    def test_staleness_excess_reads_overshoot(self):
        from repro.dist.async_train import (GradientBus, resolve_tau,
                                            staleness_excess)
        bus = GradientBus(grads=jnp.zeros((4, 2)),
                          versions=jnp.asarray([5, 3, 1, 6], jnp.int32),
                          arrival_step=jnp.zeros((4,), jnp.int32))
        tau = resolve_tau(2, 4)
        np.testing.assert_array_equal(
            np.asarray(staleness_excess(bus, 6, tau)), [0, 1, 3, 0])
        # a future-stamped (lying) version shows as no excess: the
        # master can only observe the stamp
        np.testing.assert_array_equal(
            np.asarray(staleness_excess(bus, 5, tau)), [0, 0, 2, 0])

    def test_async_step_emits_the_metric(self):
        from repro.configs import get_reduced
        from repro.dist.async_train import (init_async_state,
                                            make_async_train_step)
        from repro.models import init_model
        from repro.optim import get_optimizer

        cfg = get_reduced("llama3_2_3b")
        opt = get_optimizer("sgd", 1e-2)
        spec = AggSpec(f=1, gar="stale-krum", attack="signflip", async_tau=2)
        params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0),
                                                   cfg))
        batch = {"tokens": jax.ShapeDtypeStruct((7, 1, 8), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((7, 1, 8), jnp.int32)}
        out = jax.eval_shape(make_async_train_step(cfg, spec, opt), params,
                             jax.eval_shape(opt.init, params), batch,
                             jax.eval_shape(lambda: init_async_state(
                                 spec, params, 7)))
        assert out[2]["staleness_excess"].shape == ()


class TestLeeway:
    # Proposition 2 is asymptotic — at d = 16 the honest sampling noise
    # still dominates Bulyan's margin, so the ladder starts at 64 with
    # the paper-shaped committee (n = 15 = 4f + 3)
    DIMS = (64, 256)

    @pytest.fixture(scope="class")
    def report(self):
        return measure_leeway(
            rules=("average", "krum", "bulyan-krum",
                   ("bulyan-weak", "bulyan-krum", 0)),
            dims=self.DIMS, n_h=12, f=3, seed=11)

    def test_margins_scale_like_the_paper(self, report):
        rules = report["rules"]
        # Krum-family leeway and the average's poisoning margin grow
        assert rules["krum"]["slope_abs"] > 0.3
        assert rules["average"]["slope_abs"] > 0.3
        # Bulyan's relative margin shrinks (Proposition 2)
        assert rules["bulyan-krum"]["slope_rel"] < -0.25
        assert report["gamma"]["krum"]["slope"] > 0.3

    def test_weakened_rule_fails_certification(self, report):
        violations = certify(
            report,
            expectations={"bulyan-weak": ("rel", None, -0.25)})
        assert any("bulyan-weak" in v for v in violations)

    def test_healthy_rules_certify(self, report):
        violations = certify(
            report,
            expectations={"krum": ("abs", 0.3, None),
                          "bulyan-krum": ("rel", None, -0.25)})
        assert violations == []

    def test_baseline_gate_catches_margin_regression(self, report):
        doctored = json.loads(json.dumps(report))  # deep copy
        doctored["rules"]["bulyan-krum"]["margin_abs"] = [
            m * 10.0 for m in
            doctored["rules"]["bulyan-krum"]["margin_abs"]]
        violations = certify(report, expectations={},
                             baseline=doctored)
        assert any("bulyan-krum" in v for v in violations)
        assert certify(report, expectations={}, baseline=report) == []

    def test_report_is_deterministic(self):
        a = measure_leeway(rules=("krum",), dims=(16, 64), n_h=8, f=2,
                           seed=9)
        b = measure_leeway(rules=("krum",), dims=(16, 64), n_h=8, f=2,
                           seed=9)
        assert a == b

    def test_slope_fits_loglog(self):
        dims = (4, 16, 64)
        assert slope(dims, [np.sqrt(d) for d in dims]) == \
            pytest.approx(0.5, abs=1e-6)
        assert slope(dims, [10.0 / np.sqrt(d) for d in dims]) == \
            pytest.approx(-0.5, abs=1e-6)


class TestFp32Probes:
    def test_gram_probe_tight_on_bf16(self):
        from repro.kernels.probes import gram_fp32_contract_error
        assert gram_fp32_contract_error(n=4, d=512, block_d=256) < 1e-4

    def test_coord_probe_tight_on_bf16(self):
        from repro.kernels.probes import coord_fp32_contract_error
        assert coord_fp32_contract_error(theta=7, f=1, d=512,
                                         block_d=256) < 1e-4

    def test_probe_detects_low_precision_accumulation(self):
        """The probe's oracle casts the same quantized values to fp32 —
        an (emulated) bf16 accumulator at d >> 1/eps_bf16 must show."""
        from repro.kernels.ref import pairwise_gram_ref
        g = (jax.random.normal(KEY, (4, 4096), jnp.float32)
             .astype(jnp.bfloat16))
        want = pairwise_gram_ref(g.astype(jnp.float32))
        # emulate a kernel that accumulates the squared-norm reduction
        # in bf16 instead of fp32 (the ref itself upcasts first)
        sq = jnp.sum(g * g, axis=-1).astype(jnp.float32)
        g32 = g.astype(jnp.float32)
        lowp = jnp.maximum(
            sq[:, None] + sq[None, :] - 2.0 * (g32 @ g32.T), 0.0
        ) * (1.0 - jnp.eye(4, dtype=jnp.float32))
        err = float(jnp.max(jnp.abs(lowp - want))) / float(
            jnp.max(jnp.abs(want)))
        assert err > 1e-4  # the contract tolerance would flag it


class TestSweepCatchesInjectedBugs:
    def test_report_aggregation(self):
        r = AuditReport()
        r.add("a", 3, [])
        r.add("a", 2, ["boom"])
        r.add("b", 1, [])
        assert r.cases == 6 and not r.ok()
        assert r.sections == {"a": (5, 1), "b": (1, 0)}

    def test_hull_violation_is_reported_not_raised(self):
        avg = resolve_rule("average")

        def escaped(grads, f):
            res = avg.dense_fn(grads, f)
            return AggResult(res.gradient + 100.0, res.selected,
                             res.scores)

        fake = dataclasses.replace(avg, name="escaped-average",
                                   dense_fn=escaped)
        grads = jnp.asarray(
            np.asarray(jax.random.normal(KEY, (5, 4)), np.float32))
        res = fake.dense_fn(grads, 1)
        violations = check_rule_output(
            fake, res.gradient, res.selected,
            effective_stack(fake, grads, None), 1)
        assert any("hull" in v for v in violations)
